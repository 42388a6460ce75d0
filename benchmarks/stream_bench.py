"""Panel-streaming engine benchmark: adaptive vs fixed-uniform streaming CUR,
eviction vs admission-only, adaptive vs fixed rows, and DP-sharded
ingestion, on spiked / late-spike / drifting-spectrum matrices.

Rows (→ ``BENCH_stream.json`` via ``benchmarks.common.write_bench_json``):

* ``stream/cur/<m>x<n>/fixed-uniform/w<W>``  — pre-pass uniform col_idx
* ``stream/cur/<m>x<n>/adaptive/w<W>``       — residual-driven in-stream
  admission (same column budget c, same row_idx) on 1/2/4 simulated DP
  workers; ``derived`` records the relative Frobenius error so the
  adaptive-beats-uniform claim is auditable from the artifact.
* ``stream/cur/<scenario>/<m>x<n>/admit-only|evict`` — the v2 replacement
  policy on streams where admission-only *provably* loses: ``late-spike``
  (heavy columns arriving after the budget fills) and ``drift`` (dominant
  subspace drifting stronger block by block). ``evict_win`` rows record the
  admission-only/evict error ratio with PASS/FAIL at equal (c, r) budget.
* ``stream/cur/rows/<m>x<n>/fixed|adaptive`` — fixed pre-pass uniform rows
  vs in-stream row admission (equal r budget, identical adaptive columns)
  on spiked-rows matrices, plus a ``row_win`` PASS/FAIL row.
* ``stream/obs/est/<family>/<m>x<n>`` — the a-posteriori error estimator
  (``repro.obs.estimate_rel_error``) vs the true relative Frobenius error
  on each stream family; ``ratio`` must sit inside the 2× band.
* ``stream/spsvd/<m>x<n>/parity/w<W>``       — max |Δ| between DP-sharded
  and single-host SP-SVD accumulators (exactness evidence).

The ``us_per_call`` of these rows is CPU wall-clock, a rehearsal and not
a speed claim: speed is measured on the chip by ``bench/``.

When ``--out-dir`` is given the run's host metrics (stream telemetry
summaries + profiling spans, via :mod:`repro.obs.metrics`) are dumped as
``BENCH_stream.metrics.jsonl`` next to the artifact.

  PYTHONPATH=src python -m benchmarks.stream_bench [--smoke]
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.svd import sp_svd_init
from repro.cur import cur_relative_error, select_rows, streaming_cur_finalize, streaming_cur_init
from repro.obs import MetricsRegistry, default_registry, estimate_rel_error, set_registry
from repro.stream import (
    adaptive_cur_finalize,
    adaptive_cur_init,
    simulate_sharded_stream,
    stream_panels,
)

from .common import (
    drifting_spectrum_matrix,
    late_spike_matrix,
    spiked_decay_matrix,
    spiked_rows_matrix,
    time_calls_interleaved,
    write_bench_json,
)


def _stream(state, A, panel, workers):
    """Every worker count — including w1 — runs the same sharded driver
    (one fused program either way), so the w-scaling rows measure what the
    driver actually costs per worker count. Note what that means per
    method: for *fixed-uniform* (hook-less ops) the fused driver provably
    chains the contiguous worker partition into the single-host scan, so
    its w1/w2/w4 rows execute the same program — equal rows are the
    *result* of that optimization (the pre-PR4 per-worker dispatch loop is
    what made w2/w4 ≥ 2× slower), not evidence of parallel speedup. The
    *adaptive* rows keep genuinely divergent per-worker admission state +
    in-program merge. Real multi-device execution (`mesh_sharded_stream`)
    is exercised for parity in the slow test lane, not timed here."""
    return simulate_sharded_stream(state, A, panel, workers)


def _win_row(name: str, lose_err: float, win_err: float, label: str) -> dict:
    ratio = lose_err / max(win_err, 1e-12)
    return {
        "name": name,
        "us_per_call": 0.0,
        "derived": f"{label}={ratio:.2f}x({'PASS' if ratio > 1.0 else 'FAIL'}@equal-budget)",
    }


def run_adaptive_vs_uniform(shapes, trials: int, quick: bool) -> list:
    """PR-2 scenario kept intact: admission vs fixed-uniform at equal c.

    Timing methodology (CPU wall-clock): every (method, workers)
    configuration of a shape is timed **interleaved** (one call per config
    per round, min over rounds — see
    :func:`benchmarks.common.time_calls_interleaved`) so the
    adaptive-vs-fixed and w4-vs-w1 comparisons are not polluted by ambient
    drift between sequentially-timed rows.
    """
    rows = []
    c = r = 16
    for m, n, panel in shapes:
        # Wider panels than the adversarial-stream scenarios: the adaptive
        # policy pays a per-panel constant (whitened-basis solve + admission
        # chain), so fewer, larger panels amortize it; panel_cap scales up
        # so the admission budget per column stays the same.
        panel, panel_cap = 2 * panel, 4
        A, pos = spiked_decay_matrix(jax.random.key(m + n), m, n)
        ri = select_rows(jax.random.key(1), A, r, "uniform").idx
        errs = {}
        stats = {}
        for workers in (1, 2, 4):
            for method in ("fixed-uniform", "adaptive"):
                per_trial = []
                admitted_spikes = []
                for t in range(trials):
                    if method == "fixed-uniform":
                        ci = jax.random.choice(jax.random.key(100 + t), n, (c,), replace=False)
                        st = streaming_cur_init(
                            jax.random.key(200 + t), m, n, ci, ri,
                            sketch="countsketch", panel=panel,
                        )
                        res = streaming_cur_finalize(_stream(st, A, panel, workers))
                    else:
                        st = adaptive_cur_init(
                            jax.random.key(200 + t), m, n, c, ri,
                            sketch="countsketch", panel=panel, panel_cap=panel_cap,
                        )
                        res = adaptive_cur_finalize(_stream(st, A, panel, workers))
                        admitted_spikes.append(
                            len(set(np.asarray(pos).tolist()) & set(np.asarray(res.col_idx).tolist()))
                        )
                    per_trial.append(float(cur_relative_error(A, res)))
                errs[(method, workers)] = float(np.mean(per_trial))
                stats[(method, workers)] = admitted_spikes

        # Timed calls are end-to-end (init + stream + finalize) with the init
        # compiled in the closure: fewer host dispatches per call → a much
        # tighter min-floor on a noisy shared-CPU container.
        ci0 = jax.random.choice(jax.random.key(100), n, (c,), replace=False)
        fixed_init = jax.jit(lambda key: streaming_cur_init(
            key, m, n, ci0, ri, sketch="countsketch", panel=panel))
        adapt_init = jax.jit(lambda key: adaptive_cur_init(
            key, m, n, c, ri, sketch="countsketch", panel=panel, panel_cap=panel_cap))

        def once(method, workers):
            if method == "fixed-uniform":
                st = fixed_init(jax.random.key(200))
                return streaming_cur_finalize(_stream(st, A, panel, workers)).U
            st = adapt_init(jax.random.key(200))
            return adaptive_cur_finalize(_stream(st, A, panel, workers)).U

        # Cyclic measurement order keeps each w's fixed/adaptive pair and the
        # w4/w1 fixed pair adjacent, so sustained contention windows hit both
        # sides of every compared pair; rotation + min handles the rest.
        fns = {
            (method, workers): (lambda method=method, workers=workers: once(method, workers))
            for workers in (4, 1, 2)
            for method in ("fixed-uniform", "adaptive")
        }
        # rounds stretch the session across several contention cycles of the
        # shared container, so every config touches its true floor
        times = time_calls_interleaved(fns, warmup=1, rounds=40 if quick else 100)
        for workers in (1, 2, 4):
            for method in ("fixed-uniform", "adaptive"):
                rel = errs[(method, workers)]
                derived = f"rel_err={rel:.4f};c={c};panel={panel}"
                if method == "adaptive":
                    derived += f";spikes_admitted={np.mean(stats[(method, workers)]):.1f}/{len(pos)}"
                rows.append({
                    "name": f"stream/cur/{m}x{n}/{method}/w{workers}",
                    "us_per_call": round(times[(method, workers)], 1),
                    "derived": derived,
                    "_rel_err": rel,
                })
        for workers in (1, 2, 4):
            win = errs[("fixed-uniform", workers)] / max(errs[("adaptive", workers)], 1e-12)
            rows.append({
                "name": f"stream/cur/{m}x{n}/adaptive_win/w{workers}",
                "us_per_call": 0.0,
                "derived": f"uniform_over_adaptive={win:.2f}x"
                           f"({'PASS' if win > 1.0 else 'FAIL'}@equal-c)",
            })
    return rows


def run_eviction(shapes, trials: int) -> list:
    """v2 acceptance scenario: admission-only vs eviction at equal (c, r)
    budget on streams engineered so admission-only loses (the budget fills
    on early/weaker columns before the heavy ones arrive)."""
    rows = []
    c, r = 8, 16
    for m, n, panel in shapes:
        for scenario in ("late-spike", "drift"):
            errs = {"admit-only": [], "evict": []}
            evictions = []
            for t in range(trials):
                if scenario == "late-spike":
                    A, _early, _late = late_spike_matrix(jax.random.key(m + n + 7 * t), m, n)
                else:
                    A, _bounds = drifting_spectrum_matrix(jax.random.key(m + n + 7 * t), m, n)
                ri = select_rows(jax.random.key(11 + t), A, r, "uniform").idx
                for method, sg in (("admit-only", None), ("evict", 2.0)):
                    # panel_cap = c//2 so the early/weak columns genuinely fill
                    # the budget before the heavy ones arrive — the failure
                    # mode eviction exists for
                    st = adaptive_cur_init(
                        jax.random.key(300 + t), m, n, c, ri,
                        sketch="countsketch", panel=panel, panel_cap=c // 2, swap_gain=sg,
                    )
                    st = stream_panels(st, A, panel)
                    if method == "evict":
                        evictions.append(int(st.ctx.n_evicted))
                    errs[method].append(
                        float(cur_relative_error(A, adaptive_cur_finalize(st)))
                    )
            e_admit = float(np.mean(errs["admit-only"]))
            e_evict = float(np.mean(errs["evict"]))
            rows.append({
                "name": f"stream/cur/{scenario}/{m}x{n}/admit-only",
                "us_per_call": 0.0,
                "derived": f"rel_err={e_admit:.4f};c={c};panel={panel}",
                "_rel_err": e_admit,
            })
            rows.append({
                "name": f"stream/cur/{scenario}/{m}x{n}/evict",
                "us_per_call": 0.0,
                "derived": f"rel_err={e_evict:.4f};c={c};panel={panel}"
                           f";evictions={np.mean(evictions):.1f};swap_gain=2.0",
                "_rel_err": e_evict,
            })
            rows.append(_win_row(
                f"stream/cur/{scenario}/{m}x{n}/evict_win",
                e_admit, e_evict, "admit_only_over_evict",
            ))
    return rows


def run_row_admission(shapes, trials: int) -> list:
    """v2 acceptance scenario: fixed pre-pass uniform rows vs in-stream row
    admission at equal r budget (identical adaptive-column settings), on
    matrices with planted heavy rows."""
    rows = []
    c, r = 12, 8
    for m, n, panel in shapes:
        errs = {"fixed": [], "adaptive": []}
        captured = []
        for t in range(trials):
            A, rpos = spiked_rows_matrix(jax.random.key(m + 3 * n + 13 * t), m, n)
            for method in ("fixed", "adaptive"):
                kw = (
                    dict(row_idx=select_rows(jax.random.key(21 + t), A, r, "uniform").idx)
                    if method == "fixed"
                    else dict(row_idx=None, r=r, panel_cap_rows=2)
                )
                st = adaptive_cur_init(
                    jax.random.key(400 + t), m, n, c,
                    sketch="countsketch", panel=panel, panel_cap=2, **kw,
                )
                st = stream_panels(st, A, panel)
                res = adaptive_cur_finalize(st)
                if method == "adaptive":
                    captured.append(
                        len(set(np.asarray(rpos).tolist()) & set(np.asarray(res.row_idx).tolist()))
                    )
                errs[method].append(float(cur_relative_error(A, res)))
        e_fixed = float(np.mean(errs["fixed"]))
        e_adapt = float(np.mean(errs["adaptive"]))
        rows.append({
            "name": f"stream/cur/rows/{m}x{n}/fixed",
            "us_per_call": 0.0,
            "derived": f"rel_err={e_fixed:.4f};r={r};panel={panel}",
            "_rel_err": e_fixed,
        })
        rows.append({
            "name": f"stream/cur/rows/{m}x{n}/adaptive",
            "us_per_call": 0.0,
            "derived": f"rel_err={e_adapt:.4f};r={r};panel={panel}"
                       f";spiked_rows_admitted={np.mean(captured):.1f}/6",
            "_rel_err": e_adapt,
        })
        rows.append(_win_row(
            f"stream/cur/rows/{m}x{n}/row_win", e_fixed, e_adapt, "fixed_over_adaptive"
        ))
    return rows


def run_error_estimator(shapes, trials: int) -> list:
    """A-posteriori estimator audit rows: ``estimate_rel_error`` (the
    single-pass Ψ-vs-ÂΩ estimate) against the true relative Frobenius error
    on each stream family. Acceptance: ``ratio`` inside the 2× band in both
    directions. The final telemetry frame of each family is folded into the
    process metrics registry (→ ``BENCH_stream.metrics.jsonl``) so the
    per-panel admission/eviction audit ships with the artifact."""
    rows = []
    c = r = 16
    reg = default_registry()
    for m, n, panel in shapes:
        for family in ("spiked", "late-spike", "drift"):
            ests, trues = [], []
            for t in range(trials):
                key = jax.random.key(m + n + 17 * t)
                if family == "spiked":
                    A, _pos = spiked_decay_matrix(key, m, n)
                elif family == "late-spike":
                    A, _e, _l = late_spike_matrix(key, m, n)
                else:
                    A, _b = drifting_spectrum_matrix(key, m, n)
                st = adaptive_cur_init(
                    jax.random.key(500 + t), m, n, c, None, r=r,
                    sketch="countsketch", panel=panel, panel_cap=2,
                    panel_cap_rows=2, swap_gain=2.0, telemetry=True,
                )
                st = stream_panels(st, A, panel)
                ests.append(float(estimate_rel_error(st)))
                trues.append(float(cur_relative_error(A, adaptive_cur_finalize(st))))
                if t == 0:
                    reg.record_stream_telemetry(st, prefix=f"stream/{family}/{m}x{n}")
            est, true = float(np.mean(ests)), float(np.mean(trues))
            ratio = est / max(true, 1e-12)
            rows.append({
                "name": f"stream/obs/est/{family}/{m}x{n}",
                "us_per_call": 0.0,
                "derived": f"est={est:.4f};true={true:.4f};ratio={ratio:.2f}"
                           f"({'PASS' if 0.5 <= ratio <= 2.0 else 'FAIL'}@2x-band)",
            })
    return rows


def run_spsvd_parity(shapes) -> list:
    """SP-SVD DP-sharded parity evidence (exactness, not speed)."""
    rows = []
    c = r = 16
    for m, n, panel in shapes:
        A, _pos = spiked_decay_matrix(jax.random.key(m + n), m, n)
        sizes = dict(c=2 * c, r=2 * r, c0=6 * c, r0=6 * r, s_c=6 * c, s_r=6 * r)
        single = stream_panels(
            sp_svd_init(jax.random.key(3), m, n, sizes=sizes, panel=panel), A, panel
        )
        for workers in (2, 4):
            shard = simulate_sharded_stream(
                sp_svd_init(jax.random.key(3), m, n, sizes=sizes, panel=panel), A, panel, workers
            )
            delta = max(
                float(jnp.max(jnp.abs(shard.C - single.C))),
                float(jnp.max(jnp.abs(shard.R - single.R))),
                float(jnp.max(jnp.abs(shard.M - single.M))),
            )
            rows.append({
                "name": f"stream/spsvd/{m}x{n}/parity/w{workers}",
                "us_per_call": 0.0,
                "derived": f"max_abs_delta={delta:.2e}",
            })
    return rows


def run(trials: int = 3, quick: bool = False) -> list:
    shapes = [(384, 320, 64)] if quick else [(1024, 768, 128), (2048, 1024, 128)]
    rows = run_adaptive_vs_uniform(shapes, trials, quick)
    rows += run_eviction(shapes, trials)
    rows += run_row_admission(shapes, trials)
    rows += run_error_estimator(shapes, trials)
    rows += run_spsvd_parity(shapes)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="single small shape, 1 trial (CI)")
    ap.add_argument("--out-dir", default=None, help="where to write BENCH_stream.json")
    args = ap.parse_args()
    # enabled registry for the run: captures the engine's profiling spans and
    # the estimator scenario's telemetry summaries alongside the artifact
    prev = set_registry(MetricsRegistry())
    try:
        rows = run(trials=1 if args.smoke else 3, quick=args.smoke)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']},{str(row['derived']).replace(',', ';')}")
        path = write_bench_json("stream", rows, meta={"smoke": args.smoke}, out_dir=args.out_dir)
        print(f"wrote {path}")
        metrics_path = os.path.join(
            os.path.dirname(path) or os.getcwd(), "BENCH_stream.metrics.jsonl"
        )
        default_registry().dump_jsonl(metrics_path)
        print(f"wrote {metrics_path}")
    finally:
        set_registry(prev)


if __name__ == "__main__":
    main()
