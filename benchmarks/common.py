"""Shared utilities of the paper's accuracy experiments: CPU timing, the
synthetic inputs of ``repro.data.synthetic`` (re-exported), and the
standard ``BENCH_<module>.json`` artifact writer that every table/figure
module — ``gmr_error``, ``cur_decomp``, ``spsd_approx``,
``single_pass_svd``, ``stream_bench`` — writes through."""

from __future__ import annotations

import json
import os
import platform
import time

import jax
import numpy as np

from repro.data.synthetic import (  # noqa: F401 — re-export
    clustered_points,
    drifting_spectrum_matrix,
    late_spike_matrix,
    lowrank_plus_noise,
    powerlaw_matrix,
    sparse_matrix,
    spiked_decay_matrix,
    spiked_rows_matrix,
    tune_rbf_sigma,
)


def write_bench_json(module: str, rows: list, meta: dict | None = None, out_dir: str | None = None) -> str:
    """Write the standard ``BENCH_<module>.json`` artifact and return its path.

    Shape: ``{"bench", "schema", "meta", "rows"}`` where each row keeps the
    CSV contract keys (``name``, ``us_per_call``, ``derived``); private
    ``_``-prefixed keys are stripped.
    """
    clean = [{k: v for k, v in row.items() if not k.startswith("_")} for row in rows]
    artifact = {
        "bench": module,
        "schema": 1,
        "meta": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
            **(meta or {}),
        },
        "rows": clean,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir or os.getcwd(), f"BENCH_{module}.json")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=2)
    return path


def time_call(fn, *args, warmup: int = 1, iters: int = 3, **kw) -> float:
    """Median wall-time in microseconds of fn(*args) (jit-compiled callers)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def time_calls_interleaved(fns: dict, warmup: int = 1, rounds: int = 7) -> dict:
    """Best (min) wall-time in µs per named thunk, interleaved in a
    **randomized order per round** (seeded — reproducible).

    Timing the configurations of a comparison back-to-back (all iterations
    of A, then all of B) folds ambient drift — CPU frequency, container
    neighbours, allocator state — into the *difference* being measured.
    Interleaving one iteration of every configuration per round exposes
    each to the same drift. The per-round order is a fresh seeded
    permutation rather than a fixed cycle: a fixed cycle gives every
    config a *constant predecessor*, and the tail of the predecessor's
    call (async deallocation, cache displacement) lands on the successor's
    timer — a persistent few-percent adjacency bias that min-of-rounds
    cannot remove because it is systematic, not noise (observed as
    byte-identical programs timing 2–4% apart). Random permutations make
    predecessors uniform, so the per-config min over enough rounds is
    order-unbiased and identical workloads measure equal.
    """
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    items = list(fns.items())
    rounds = max(rounds, 2 * len(items))
    rng = np.random.default_rng(0)
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for j in rng.permutation(len(items)):
            name, fn = items[j]
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[name] = min(best[name], (time.perf_counter() - t0) * 1e6)
    return best
