# Convenience targets mirroring CI. Tier-1 verify == `make test`
# (the default lane; `slow`-marked sweeps run via `make test-slow`).
PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-slow test-all smoke bench docs-check chaos-check

test:  ## default tier-1 lane (slow sweeps excluded via pyproject addopts)
	$(PY) -m pytest -x -q

docs-check:  ## docstring audit (repro.stream/cur/spsd/obs/serve) + docs/paper_map.md anchors
	$(PY) tools/check_docstrings.py

test-slow:  ## heavy sweeps + multi-device subprocess scenarios
	$(PY) -m pytest -x -q -m slow

test-all:  ## both lanes
	$(PY) -m pytest -x -q -m "slow or not slow"

smoke:  ## quick accuracy-experiment artifacts (CI)
	$(PY) -m benchmarks.cur_decomp --smoke
	$(PY) -m benchmarks.stream_bench --smoke
	$(PY) -m benchmarks.spsd_approx --smoke

chaos-check:  ## stream suite under seeded FaultPlan (crash + NaN + straggler): zero factor divergence
	$(PY) tools/chaos_check.py
	$(PY) -m pytest -q tests/test_resilient.py

bench:  ## the paper's accuracy experiments, CSV on stdout (speed: bench/ on the chip)
	$(PY) -m benchmarks.run
