"""The check that decides ``correct``, at small sizes on the CPU.

Each cell's run passes with its own limits (``limits/<cell>.json``); both
controls fail them (the program's own bfloat16 path, ``dtype=bfloat16``, and
the plain reference computed in bfloat16 put in the program's place); and a
run with the timed path broken underneath comes out not correct, once for
each fault the cell can have.
"""

import dataclasses
import time
import types

import jax
import pytest

import calibrate
import harness
from conftest import FAKE_PEAK, cpu_devices, small_spec

CELLS = ["cur_32k.countsketch", "spsd_rbf_32k.countsketch", "cur_32k.gaussian",
         "cur_128k.countsketch.4chip"]


@pytest.fixture(autouse=True)
def fresh_programs(monkeypatch):
    """Every test traces anew (a fault patched in must reach the compiled
    programs), and the Gaussian cell takes the ``panel_update`` kernel in
    interpret mode as it does on a TPU."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_FORCE_KERNEL_ROUTE", True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def run_cell(cell, seed=5):
    spec = small_spec(cell)
    return harness.run(spec, seed, 0.3, False, cpu_devices(spec["cell"]["chips"]), FAKE_PEAK,
                       time.perf_counter())


def failing(result):
    return sorted(k for k, c in result["checks"].items() if c["value"] > c["limit"])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = run_cell(cell, seed=2**33 + 17)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell):
    spec = small_spec(cell)
    out = calibrate.readings(spec, [1, 2, 3], [1, 2, 3], cpu_devices(spec["cell"]["chips"]),
                             emit=lambda s: None)
    limits = spec["limits"]
    assert all(out["program"][k] <= v for k, v in limits.items()), out["program"]
    for control in ("program_bf16", "reference_bf16"):
        assert any(out[control][k] > v for k, v in limits.items()), out[control]


def _stream_target(cell):
    import repro.stream as stream

    name = "mesh_sharded_stream" if cell.endswith("4chip") else "stream_panels"
    return stream, name


def _finalize_target(cell):
    if cell.startswith("spsd"):
        import repro.spsd.streaming as mod

        return mod, "streaming_spsd_finalize"
    import repro.stream.adaptive as mod

    return mod, "adaptive_cur_finalize"


@pytest.mark.parametrize("cell", CELLS)
def test_fault_state_unchanged(cell, monkeypatch):
    mod, name = _stream_target(cell)
    monkeypatch.setattr(mod, name, lambda state, *a, **k: state)
    assert not run_cell(cell)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_fault_half_the_panels(cell, monkeypatch):
    if cell.endswith("4chip"):
        import repro.stream.distributed as dist

        scan = dist.scan_chunk
        monkeypatch.setattr(dist, "scan_chunk",
                            lambda st, A, panel, **k: scan(st, A[:, : A.shape[1] // 2], panel, **k))
    else:
        mod, name = _stream_target(cell)
        orig = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda state, A, panel, **k: orig(
            state, A, panel, stop=A.shape[1] // 2, **k))
    result = run_cell(cell)
    assert not result["correct"] and failing(result)


def test_fault_exchange_left_out(monkeypatch):
    """The four-chip cell with the psum of C, R and M left out."""
    import repro.stream.distributed as dist

    lax = types.SimpleNamespace(**{k: getattr(jax.lax, k) for k in dir(jax.lax)
                                   if not k.startswith("__")})
    lax.psum = lambda x, axis: x
    monkeypatch.setattr(dist, "jax", types.SimpleNamespace(
        **{k: getattr(jax, k) for k in dir(jax) if not k.startswith("__")}, ))
    monkeypatch.setattr(dist.jax, "lax", lax)
    result = run_cell("cur_128k.countsketch.4chip")
    assert not result["correct"] and failing(result)


@pytest.mark.parametrize("cell", CELLS)
def test_fault_answer_altered(cell, monkeypatch):
    mod, name = _finalize_target(cell)
    orig = getattr(mod, name)

    def altered(state):
        res = orig(state)
        return dataclasses.replace(res, C=res.C.at[0, 0].add(1.0))

    monkeypatch.setattr(mod, name, altered)
    result = run_cell(cell)
    assert not result["correct"] and "C_err" in failing(result)
