#!/usr/bin/env python3
"""Readings that a cell's limits are set from (``limits/<cell>.json``).

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,...,12 --control-seeds 1,2,3

For each seed, in one process: the cell's data from the seed, then the first
job of the window (job 0, through the same entry points and at the same
sizes as a run) with its numbers against the plain reference; for each
control seed also the numbers of the two controls: the program's own
bfloat16 path (the job with bfloat16 accumulators, ``dtype=bfloat16`` of its
init) and the reference computed in bfloat16 put in the job's place. Prints
one JSON line per reading and a last line with, per number, the largest
sound reading and the smallest reading of each control. The benchmark's own
runs never run a control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime writes its logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def readings(spec: dict, seeds, control_seeds, devices, emit=print) -> dict:
    """{"program": {number: max}, "program_bf16": {number: min},
    "reference_bf16": {number: min}} over the seeds."""
    import jax
    import jax.numpy as jnp

    job = harness.make_job(spec, devices)
    job_bf16 = harness.make_job(spec, devices, dtype=jnp.bfloat16)
    worst, least = {}, {"program_bf16": {}, "reference_bf16": {}}
    for seed in sorted(set(seeds) | set(control_seeds)):
        key = harness.seed_key(seed)
        jobs_key = jax.random.fold_in(key, 1)
        jax.block_until_ready(job.make_data(jax.random.fold_in(key, 0)))
        job_key = jax.random.fold_in(jobs_key, 0)
        t0 = time.perf_counter()
        state, res = harness._job_once(job, job_key)
        job_s = time.perf_counter() - t0
        kept = job.keep(state, res)
        del state, res
        if seed in seeds:
            nums = job.compare(job_key, kept)
            emit(json.dumps({"seed": seed, "side": "program", "job_s": job_s, **nums}))
            for k, v in nums.items():
                worst[k] = max(worst.get(k, v), v)
        if seed in control_seeds:
            job_bf16.A = job.A
            kept_bf16 = job_bf16.keep(*harness._job_once(job_bf16, job_key))
            for side, nums in (("program_bf16", job.compare(job_key, kept_bf16)),
                               ("reference_bf16", job.compare(job_key, kept, control=True))):
                emit(json.dumps({"seed": seed, "side": side, **nums}))
                for k, v in nums.items():
                    least[side][k] = min(least[side].get(k, v), v)
            del kept_bf16
        del kept
        job.A = job_bf16.A = None
    return {"program": worst, **least}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds of the program")
    ap.add_argument("--control-seeds", default="", help="comma-separated seeds of the control")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]

    spec = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    peaks = harness._read_json(os.path.join(BENCH, "peaks.json"))
    try:
        devices = harness.chips_for(spec["cell"]["chips"], peaks)
    except harness.RefusedRun as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    out = readings(spec, seeds, control, devices, emit=lambda s: print(s, flush=True))
    out["wall_s"] = time.perf_counter() - T_PROCESS
    print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
