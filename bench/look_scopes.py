#!/usr/bin/env python3
"""Trace jobs of a cell and read the program's own stages from the trace.

  python3 bench/look_scopes.py --workload <cell> --seed <n> [--runs 3] [--jobs J]
      [--small] [--raw <dir>] [--save <trace.json.gz>]

Set-up is a ``--trace 1`` run's (data from the seed, one warm-up job). Then
each of ``--runs`` runs makes the cell's data anew from its own seed
(``--seed``, ``--seed`` + 1, ...) and traces ``--jobs`` jobs (the traffic's
``traced_jobs`` by default) exactly as a ``--trace 1`` run does. The trace is
reduced by ``xscope.load_dir`` and each run prints one JSON line: the
benchmark's ``stream_ms``, ``finalize_ms`` and ``device_idle_pct`` read by
their own readers, and what the program's scopes and spans show, per
factorization on the chip where each reads most:

- ``<scope>_ms``: device time of the innermost operations in each scope of
  the engine (``sketch_ms`` for ``stream.sketch``, ``mfold_ms``,
  ``admit_ms``, ``chunk_fold_ms``, ``rows_ms``, ``panel_kernel_ms``,
  ``psum_scope_ms``, ``solve_ms`` for ``finalize.solve``);
- ``host_idle_ms``: device idle time while the host was inside a program
  span (``stream/...``);
- ``stream_by_scope``: the stream stage's innermost operations by scope, in
  milliseconds per factorization, and ``coverage``, the share of the stream
  stage's busy time that the ``stream.*`` scopes hold;
- the idle gaps named by the benchmark's span and by the program's.

``--workload`` may be given more than once: one traced window then holds
``--jobs`` jobs of each cell in turn. ``--small`` cuts every CountSketch
cell to a 2048 x 2048 matrix (the fixture of ``bench/tests``). ``--raw``
keeps the ``.xplane.pb`` of each run, ``--save`` writes the reduced trace of
the first in the form ``xscope.ScopedTrace.from_json`` reads.
"""

import argparse
import importlib
import json
import os
import random
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime writes its logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import xscope  # noqa: E402
import xtrace  # noqa: E402

# metric name -> scope it reads
SCOPE_METRICS = {"sketch_ms": "stream.sketch", "mfold_ms": "stream.mfold",
                 "admit_ms": "stream.admit", "chunk_fold_ms": "stream.chunk_fold",
                 "rows_ms": "stream.rows", "panel_kernel_ms": "stream.panel_kernel",
                 "psum_scope_ms": "stream.psum", "solve_ms": "finalize.solve"}
# a CountSketch cell cut to a fixture's size; every other key is the cell's own
SMALL = {"data": {"m": 2048, "n": 2048}, "panel": 128, "c": 32, "r": 32,
         "s_c": 960, "s_r": 960, "panel_cap": 4}


def readings(tr: xscope.ScopedTrace, jobs: int, run: harness.RunRecord) -> dict:
    """What one traced window shows, per factorization."""
    out = {}
    for name in ("stream_ms", "finalize_ms", "device_idle_pct"):
        out[name] = importlib.import_module(f"metrics.{name}").read(run)
    if not tr.chips:
        return out
    for name, scope in SCOPE_METRICS.items():
        out[name] = max(1e3 * tr.scope_s(c, scope) for c in tr.chips) / jobs
    out["host_idle_ms"] = max(1e3 * tr.idle_in_spans_s(c, xscope.PROGRAM_SPAN_PREFIX)
                              for c in tr.chips) / jobs
    chip = max(tr.chips, key=lambda c: tr.busy_s(c, "stream"))
    shares = tr.scope_shares(chip, "stream")
    busy = tr.busy_s(chip, "stream")
    out["stream_by_scope"] = {str(k): 1e3 * v / jobs for k, v in shares.items()}
    out["coverage"] = sum(v for k, v in shares.items()
                          if k and k.startswith("stream.")) / busy if busy else None
    out["idle_gaps"] = tr.idle_gaps(6)
    out["span_gaps"] = tr.span_gaps(6)
    out["top_ops"] = tr.top_ops(8)
    return out


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--jobs", type=int, default=None)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--raw", default=None)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    harness.enable_compile_cache()
    peaks = harness._read_json(os.path.join(BENCH, "peaks.json"))
    cells = []
    for name in args.workload:
        spec = harness.load_cell(name)
        if args.small and spec["traffic"]["sketch"] == "countsketch":
            for k, v in SMALL.items():
                if k == "data":
                    spec["config"]["data"].update(v)
                else:
                    spec["config"][k] = v
        try:
            devices = harness.chips_for(spec["cell"]["chips"], peaks)
        except harness.RefusedRun as e:
            print(f"look_scopes: {e}", file=sys.stderr)
            return 2
        job = harness.make_job(spec, devices)
        jobs = args.jobs or spec["traffic"]["traced_jobs"]
        cells.append((name, spec, devices, job, harness.stage_tick(devices), jobs))

    for r in range(args.runs):
        seed = args.seed + r
        key = harness.seed_key(seed)
        jobs_key = jax.random.fold_in(key, 1)
        for name, spec, devices, job, tick, jobs in cells:
            job.A = None
            jax.block_until_ready(job.make_data(jax.random.fold_in(key, 0)))
            if r == 0:
                harness._job_once(job, jax.random.fold_in(jobs_key, harness.WARMUP_JOB), tick)
        trace_dir = tempfile.mkdtemp(prefix="bench-scopes-")
        times = {}
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                for name, spec, devices, job, tick, jobs in cells:
                    times[name] = harness._window(job, jobs_key, 0.0, tick, random.Random(seed),
                                                  1, jobs)[0]
        finally:
            jax.profiler.stop_trace()
        tr = xscope.load_dir(trace_dir)
        line = {"run": r, "seed": seed, "workloads": args.workload,
                "chips": tr.chips, "window_s": tr.window_s()}
        if len(cells) == 1:
            name, spec, devices, job, tick, jobs = cells[0]
            run = harness.RunRecord(
                config=spec["config"], chips=len(devices), peak=None, setup_s=0.0,
                job_s=times[name], window_s=tr.window_s(), cols_per_job=job.cols_per_job,
                work={}, trace=tr)
            line.update(readings(tr, jobs, run))
        print(json.dumps(line), flush=True)
        if args.save and r == 0:
            tr.to_json(args.save)
        if args.raw:
            shutil.copytree(trace_dir, os.path.join(args.raw, f"run{r}"), dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
