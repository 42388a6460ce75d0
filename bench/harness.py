"""One run of one benchmark cell: set-up, measured window, check, result.

Everything particular to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration file, ``traffic/<traffic>.json``,
``limits/<cell>.json``, the job of ``algorithms/<algorithm>.py``, the data of
``datagen/<kind>.py``, the sketches of ``sketches/<family>.py`` and one reader
``metrics/<metric>.py`` per metric. A new cell, configuration or metric is new
files and entries; no file here changes.

A traffic file holds the sketch family, ``checked_jobs`` and
``traced_jobs``; the loop is always closed, with one job in flight.

A run:

1. set-up: makes the cell's data on the device from ``--seed`` and warms up
   one whole factorization (every program the window runs compiles here);
   ``setup_s`` runs from process start to the end of this, and the result's
   ``setup`` key splits it;
2. window: a closed loop of whole factorizations, one in flight, job ``j``
   drawing its sketches and indices from ``fold_in(seed key, j)``, for
   ``--seconds`` (the last job that started in time is waited for). A job
   dispatches init, stream and finalize and then waits once for its
   factors. With ``--trace 1`` the window is ``traced_jobs`` jobs (the
   traffic's) of the same shape under the profiler (a CountSketch job puts
   about 1.4e5 operations in a TPU trace, and the profiler drops events of a
   window that holds twenty); before each stage the job also dispatches a
   tiny marker program (``jit_bench_<stage>`` on the device), so that the
   trace splits the device's time into stages without the host waiting
   between them;
3. check: once the window has closed and the peak memory is read, every
   job's core is checked for finite values and valid indices, and
   ``checked_jobs`` jobs sampled from the seed are compared with the plain
   reference of the algorithm; each number must be at most its limit.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib
import json
import os
import random
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import work
import xtrace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
STAGES = ("init", "stream", "finalize")
# the warm-up job's index: never one the window reaches
WARMUP_JOB = 2**31 - 1


class RefusedRun(RuntimeError):
    """The run cannot be measured here (no chip, unknown chip, too few)."""


class CompileCount:
    """What JAX does to get programs while the ``with`` block runs: ``n``
    programs compiled or loaded from its persistent cache (a measured window
    must count none), cache hits and misses, and the seconds spent tracing,
    lowering, compiling or loading, and reading the cache."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    SECONDS = {"trace_s": "/jax/core/compile/jaxpr_trace_duration",
               "lower_s": "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "compile_or_load_s": COMPILE,
               "cache_read_s": "/jax/compilation_cache/cache_retrieval_time_sec"}
    COUNTS = {"cache_hits": "/jax/compilation_cache/cache_hits",
              "cache_misses": "/jax/compilation_cache/cache_misses"}

    def __init__(self):
        self.n = 0
        self.seconds = dict.fromkeys(self.SECONDS, 0.0)
        self.counts = dict.fromkeys(self.COUNTS, 0)

    def _duration(self, event: str, duration: float, **kwargs) -> None:
        if event == self.COMPILE:
            self.n += 1
        for name, ev in self.SECONDS.items():
            if event == ev:
                self.seconds[name] += duration

    def _event(self, event: str, **kwargs) -> None:
        for name, ev in self.COUNTS.items():
            if event == ev:
                self.counts[name] += 1

    def summary(self) -> dict:
        return {"programs": self.n, **self.counts, **self.seconds}

    def __enter__(self) -> "CompileCount":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and metrics."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "config": _read_json(os.path.join(root, entry["file"])),
        "traffic": _read_json(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")),
        "limits": _read_json(os.path.join(BENCH, "limits", f"{name}.json"))["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
    }


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    for programs of any size and compile time."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def seed_key(seed: int):
    """A PRNG key from all 64 bits of ``seed`` (``jax.random.key`` alone keeps
    only the low 32 of a larger seed under 32-bit mode)."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed >> 32)


def chips_for(chips: int, peaks: dict) -> list:
    """The first ``chips`` TPUs; refuses a backend that is not a TPU, a
    device kind without peaks, or too few chips."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RefusedRun(f"no TPU: JAX's backend is {devices[0].platform}")
    if devices[0].device_kind not in peaks["devices"]:
        raise RefusedRun(f"no peaks for device kind {devices[0].device_kind!r} in peaks.json")
    if len(devices) < chips:
        raise RefusedRun(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


@dataclasses.dataclass
class RunRecord:
    """What the metric readers of ``metrics/`` read of one run."""

    config: dict
    chips: int
    peak: dict  # peaks.json entry of the device kind
    setup_s: float
    job_s: List[float]  # every job of the window, from its init to its factors
    window_s: float  # from the window's start to the end of its last job
    cols_per_job: int
    work: dict  # work.factorization of one job: flops, bytes
    trace: Optional[object] = None  # xtrace.Trace of a traced run


def make_job(spec: dict, devices, **kwargs):
    """The job of the cell's algorithm (``algorithms/<algorithm>.py``) on
    ``devices``, over a mesh of them when there are several."""
    mesh = Mesh(np.array(devices), ("data",)) if len(devices) > 1 else None
    module = importlib.import_module(f"algorithms.{spec['config']['algorithm']}")
    return module.Job(spec["config"], spec["traffic"], mesh, **kwargs)


def _marker(stage: str, k: int):
    """A jitted program that does next to nothing, named ``bench_<stage>``:
    the device runs programs in the order they were dispatched, so its run
    marks where the stage's programs begin on the device. Each marker adds
    its own constant ``k``: programs that differ in name alone share one
    entry of the compile cache, and the device would show every marker
    under the first one's name."""

    def mark(x):
        return x + k

    mark.__name__ = mark.__qualname__ = f"{xtrace.MARK_PREFIX}{stage}"
    return jax.jit(mark)


MARKERS = {stage: _marker(stage, k) for k, stage in enumerate(STAGES + (xtrace.END,), 1)}


def _job_once(job, key, tick=None):
    """One factorization, ending with its factors ready. With ``tick`` (an
    array on the job's chips) a traced job: the same dispatches, each stage
    preceded by its marker and dispatched inside its host span."""
    if tick is None:
        state = job.stream(job.init(key))
        res = jax.block_until_ready(job.finalize(state))
        return state, res
    with jax.profiler.TraceAnnotation("bench/init"):
        MARKERS["init"](tick)
        state = job.init(key)
    with jax.profiler.TraceAnnotation("bench/stream"):
        MARKERS["stream"](tick)
        state = job.stream(state)
    with jax.profiler.TraceAnnotation("bench/finalize"):
        MARKERS["finalize"](tick)
        res = job.finalize(state)
    MARKERS[xtrace.END](tick)
    with jax.profiler.TraceAnnotation("bench/wait"):
        res = jax.block_until_ready(res)
    return state, res


def stage_tick(devices):
    """The input of the stage markers: a scalar on every one of ``devices``."""
    sharding = NamedSharding(Mesh(np.array(devices), ("data",)), P())
    return jax.device_put(np.int32(0), sharding)


def _window(job, jobs_key, seconds: float, tick, sample: random.Random, k: int,
            max_jobs: Optional[int] = None):
    """The closed loop, for ``seconds`` or ``max_jobs`` jobs (traced with
    ``tick``, see :func:`_job_once`); returns per-job seconds, the window's
    length, the summaries of every job and a reservoir sample of ``k`` kept
    jobs."""
    times, summaries, kept = [], [], []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    j = 0
    while j == 0 or (j < max_jobs if max_jobs else time.perf_counter() < deadline):
        t0 = time.perf_counter()
        state, res = _job_once(job, jax.random.fold_in(jobs_key, j), tick)
        times.append(time.perf_counter() - t0)
        summaries.append(job.summary(res))
        # reservoir sampling: every job of the window is equally likely kept
        slot = j if j < k else sample.randrange(j + 1)
        if slot < k:
            entry = (j, job.keep(state, res))
            if slot < len(kept):
                kept[slot] = entry
            else:
                kept.append(entry)
        del state, res
        j += 1
    return times, time.perf_counter() - t_start, summaries, kept


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(specs: list, run: RunRecord) -> dict:
    """Each metric's reader ``metrics/<name>.py`` on ``run``; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for spec in specs:
        value = importlib.import_module(f"metrics.{spec['name']}").read(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def check(job, jobs_key, summaries, kept, limits: dict) -> tuple:
    """(failed jobs, {number: {value, limit}}): the health of every job and
    the worst of each number over the sampled jobs against its limit."""
    failed = sum(not job.healthy(s) for s in summaries)
    worst: dict = {}
    for j, outputs in kept:
        for name, value in job.compare(jax.random.fold_in(jobs_key, j), outputs).items():
            worst[name] = max(worst.get(name, value), value)
    missing = set(limits) - set(worst)
    if missing:
        raise KeyError(f"limits name numbers the job does not compare: {sorted(missing)}")
    checks = {name: {"value": worst[name], "limit": limits[name]} for name in sorted(limits)}
    return failed, checks


def run(spec: dict, seed: int, seconds: float, trace: bool, devices, peak: dict,
        t_process: float, setup: Optional[dict] = None) -> dict:
    """One run of the cell ``spec`` (from :func:`load_cell`) on ``devices``,
    whose peaks are ``peak``; returns the result object of the run's last
    output line. ``setup`` holds the seconds of set-up spent before the
    call, by part."""
    cfg, traffic = spec["config"], spec["traffic"]
    setup = dict(setup or {})
    job = make_job(spec, devices)
    tick = stage_tick(devices) if trace else None

    key = seed_key(seed)
    jobs_key = jax.random.fold_in(key, 1)
    with CompileCount() as setup_programs:
        t0 = time.perf_counter()
        jax.block_until_ready(job.make_data(jax.random.fold_in(key, 0)))
        t1 = time.perf_counter()
        _job_once(job, jax.random.fold_in(jobs_key, WARMUP_JOB), tick)
        t2 = time.perf_counter()
    # what set-up left behind (JAX's caches of traced and compiled programs)
    # is never garbage: out of the collector's way, a full collection cannot
    # stall a job of the window for a few hundred milliseconds
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    setup.update(data_s=t1 - t0, warmup_s=t2 - t1, **setup_programs.summary())

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        with CompileCount() as compiles, (jax.profiler.TraceAnnotation(xtrace.WINDOW)
                                          if trace else contextlib.nullcontext()):
            window = _window(job, jobs_key, seconds, tick, random.Random(seed),
                             traffic["checked_jobs"], traffic["traced_jobs"] if trace else None)
    finally:
        if trace:
            jax.profiler.stop_trace()
    times, window_s, summaries, kept = window
    peak_bytes = memory_peak_bytes(devices)

    reduced = None
    if trace:
        reduced = xtrace.load_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    failed, checks = check(job, jobs_key, summaries, kept, spec["limits"])
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())

    record = RunRecord(config=cfg, chips=len(devices),
                       peak=peak, setup_s=setup_s, job_s=times,
                       window_s=window_s, cols_per_job=job.cols_per_job,
                       work=work.factorization(cfg, traffic), trace=reduced)
    metrics = read_metrics(spec["per_layer"] if trace else spec["end_to_end"], record)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(times), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        busy = [reduced.busy_s(c) for c in reduced.chips]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = reduced.window_s()
        if busy:
            result["breakdown"] = {"device_ops": reduced.top_ops(10),
                                   "idle_gaps": reduced.idle_gaps(10)}
    # the driver ignores these three keys; "checks" must come last
    result["setup"] = setup
    result["window"] = {"compiles": compiles.n, "job_s": times}
    result["checks"] = checks
    return result


def main(argv=None, t_process: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_process = time.perf_counter() if t_process is None else t_process
    t_start = time.perf_counter()

    spec = load_cell(args.workload)
    enable_compile_cache()
    peaks = _read_json(os.path.join(BENCH, "peaks.json"))
    try:
        devices = chips_for(spec["cell"]["chips"], peaks)
    except RefusedRun as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    peak = peaks["devices"][devices[0].device_kind]
    setup = {"import_s": t_start - t_process, "backend_s": time.perf_counter() - t_start}
    result = run(spec, args.seed, args.seconds, bool(args.trace), devices, peak, t_process,
                 setup)
    if result["window"]["compiles"]:
        print(f"bench: {result['window']['compiles']} programs compiled inside the window",
              file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(f"check correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
