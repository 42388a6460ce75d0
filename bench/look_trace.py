#!/usr/bin/env python3
"""Trace a few jobs of a cell and print what the profiler recorded, by hand.

  python3 bench/look_trace.py --workload <cell> --seed <n> --jobs 2 [--save <events.json.gz>]

Runs set-up and ``--jobs`` traced jobs as a ``--trace 1`` run does, then
prints every plane and line of the ``.xplane.pb`` with its event count and
its most frequent and longest event names, so that a reader can see how the
device planes, the kernels and the collectives are named before trusting
``xtrace.py``. ``--save`` writes the events ``xtrace`` keeps, in the form
``bench/tests`` reads.
"""

import argparse
import collections
import glob
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
import xtrace  # noqa: E402


def describe(trace_dir: str, top: int = 8) -> list:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            count = collections.Counter(e.name for e in events)
            dur = collections.Counter()
            for e in events:
                dur[e.name] += e.duration_ns
            out.append({"plane": plane.name, "line": line.name, "events": len(events),
                        "most": count.most_common(top),
                        "longest_ns": dur.most_common(top),
                        "first": [(e.name, e.start_ns, e.duration_ns) for e in events[:3]]})
    return out


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    spec = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    peaks = harness._read_json(os.path.join(BENCH, "peaks.json"))
    try:
        devices = harness.chips_for(spec["cell"]["chips"], peaks)
    except harness.RefusedRun as e:
        print(f"look_trace: {e}", file=sys.stderr)
        return 2
    job = harness.make_job(spec, devices)
    key = harness.seed_key(args.seed)
    jobs_key = jax.random.fold_in(key, 1)
    jax.block_until_ready(job.make_data(jax.random.fold_in(key, 0)))
    tick = harness.stage_tick(devices)
    harness._job_once(job, jax.random.fold_in(jobs_key, harness.WARMUP_JOB), tick)
    trace_dir = tempfile.mkdtemp(prefix="bench-look-")
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            harness._window(job, jobs_key, 0.0, tick, random.Random(0), 1, args.jobs)
    finally:
        jax.profiler.stop_trace()
    for row in describe(trace_dir):
        print(json.dumps(row))
    tr = xtrace.load_dir(trace_dir)
    print(json.dumps({"chips": tr.chips, "window_s": tr.window_s(),
                      "busy_s": [tr.busy_s(c) for c in tr.chips],
                      "stages_s": {s: [tr.busy_s(c, s) for c in tr.chips]
                                   for s in harness.STAGES},
                      "top_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}))
    if args.save:
        tr.to_json(args.save)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
