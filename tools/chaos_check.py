#!/usr/bin/env python
"""Chaos lane: drive the streaming suite under a seeded ``FaultPlan`` and
assert zero factor divergence.

For each streaming config (fixed CUR, adaptive CUR, symmetric SPSD) a
seed-derived fault schedule — one injected crash, NaN-corrupted panels, a
straggler delay, plus a dropped and a duplicated delivery — is applied at
the source boundary while the production driver handles it: retry/dedup for
deliveries, checkpoint-resume for the crash, in-scan quarantine for the
NaN panels. The run must produce **bitwise-identical** C/R/M (and integer
telemetry counters) to the reference run on a clean source with the
corrupted panels zeroed (the quarantine contract: a quarantined panel ≡ an
all-zero panel). A sharded variant kills one worker at 2 and 4 workers and
asserts the re-merged result against the all-healthy sharded run.

Usage:  PYTHONPATH=src python tools/chaos_check.py [--seed N]
Exit 0 == no divergence anywhere. Wired as ``make chaos-check`` and a CI
step.
"""

from __future__ import annotations

import argparse
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np


def _fault_schedule(rng: np.random.RandomState, num_panels: int):
    """Seed-derived deterministic fault plan over ``num_panels`` panels."""
    panels = rng.permutation(num_panels)
    return dict(
        crash_at_panel=int(panels[0]),
        corrupt_panels=tuple(sorted(int(p) for p in panels[1:3])),
        drop_panels=(int(panels[3]),),
        duplicate_panels=(int(panels[4]),),
        straggler_panels=(int(panels[5]),),
    )


def _assert_equal(ref, st, which: str):
    for f in ("C", "R", "M"):
        a, b = np.asarray(getattr(ref, f)), np.asarray(getattr(st, f))
        if not np.array_equal(a, b):
            raise AssertionError(
                f"{which}: factor {f} diverged "
                f"(max |Δ| = {np.max(np.abs(a - b)):.3e})"
            )
    for leaf in ("admitted", "evicted", "rows_admitted", "occupancy", "panels_seen"):
        a = np.asarray(getattr(ref.tel, leaf))
        b = np.asarray(getattr(st.tel, leaf))
        if not np.array_equal(a, b):
            raise AssertionError(f"{which}: telemetry counter {leaf} diverged")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="fault-schedule seed")
    args = ap.parse_args(argv)

    from repro.cur.streaming import streaming_cur_init
    from repro.data.synthetic import powerlaw_matrix
    from repro.spsd.streaming import streaming_spsd_init
    from repro.stream import (
        ArrayPanelSource,
        FaultInjector,
        FaultPlan,
        InjectedCrash,
        adaptive_cur_init,
        run_resilient_sharded_stream,
        run_resilient_stream,
    )

    m, n, panel = 128, 192, 16
    num_panels = n // panel
    A = powerlaw_matrix(jax.random.key(0), m, n, 1.0)
    G = powerlaw_matrix(jax.random.key(8), n, 32, 1.0)
    K = G @ G.T + 0.01 * jnp.eye(n)
    ci = jnp.asarray([3, 40, 99, 120, 7, 31], jnp.int32)
    ri = jnp.asarray([5, 17, 40, 77, 90, 60], jnp.int32)

    configs = {
        "fixed_cur": (
            lambda: streaming_cur_init(jax.random.key(1), m, n, ci, ri, panel=panel, telemetry=True),
            A,
        ),
        "adaptive_cur": (
            lambda: adaptive_cur_init(jax.random.key(5), m, n, 8, ri[:4], panel=panel, panel_cap=2, telemetry=True),
            A,
        ),
        "spsd": (
            lambda: streaming_spsd_init(jax.random.key(9), n, ci[:4], s=48, panel=panel, telemetry=True),
            K,
        ),
    }

    rng = np.random.RandomState(args.seed)
    failures = 0
    for name, (init, op) in configs.items():
        sched = _fault_schedule(rng, num_panels)
        plan = FaultPlan(straggler_delay_s=0.002, **sched)
        print(f"[chaos] {name}: {sched}")

        # reference: clean source with the to-be-corrupted panels zeroed
        # (quarantine contract: a quarantined panel ≡ an all-zero panel)
        op_zero = op
        for t in plan.corrupt_panels:
            op_zero = op_zero.at[:, t * panel : (t + 1) * panel].set(0.0)
        ref, _ = run_resilient_stream(
            init(), ArrayPanelSource(op_zero, panel), chunk_panels=2, quarantine=True
        )

        inj = FaultInjector(ArrayPanelSource(op, panel), plan)
        with tempfile.TemporaryDirectory() as d:
            try:
                run_resilient_stream(
                    init(), inj, chunk_panels=2, ckpt_dir=d, ckpt_every=1,
                    quarantine=True,
                )
                print(f"[chaos] {name}: FAIL — injected crash never fired")
                failures += 1
                continue
            except InjectedCrash:
                pass
            st, rep = run_resilient_stream(
                init(), inj, chunk_panels=2, ckpt_dir=d, ckpt_every=1,
                quarantine=True,
            )
        try:
            _assert_equal(ref, st, name)
        except AssertionError as e:
            print(f"[chaos] FAIL: {e}")
            failures += 1
            continue
        if rep.quarantined != len(plan.corrupt_panels):
            print(
                f"[chaos] {name}: FAIL — quarantined {rep.quarantined} "
                f"!= {len(plan.corrupt_panels)} corrupted"
            )
            failures += 1
            continue
        print(
            f"[chaos] {name}: OK (resumed from panel {rep.resumed_from}, "
            f"retries={rep.retries}, quarantined={rep.quarantined})"
        )

    # sharded: kill one worker, resume from its per-worker checkpoints
    init, op = configs["fixed_cur"]
    src = ArrayPanelSource(op, panel)
    for W in (2, 4):
        healthy, _ = run_resilient_sharded_stream(init(), src, W, chunk_panels=2)
        inj = FaultInjector(src, FaultPlan(crash_at_panel=int(rng.randint(num_panels))))
        with tempfile.TemporaryDirectory() as d:
            try:
                run_resilient_sharded_stream(
                    init(), inj, W, ckpt_dir=d, chunk_panels=2, ckpt_every=1
                )
                print(f"[chaos] sharded w{W}: FAIL — injected crash never fired")
                failures += 1
                continue
            except InjectedCrash:
                pass
            st, reps = run_resilient_sharded_stream(
                init(), inj, W, ckpt_dir=d, chunk_panels=2, ckpt_every=1
            )
        try:
            _assert_equal(healthy, st, f"sharded w{W}")
        except AssertionError as e:
            print(f"[chaos] FAIL: {e}")
            failures += 1
            continue
        print(f"[chaos] sharded w{W}: OK (resumed={[r.resumed_from for r in reps]})")

    if failures:
        print(f"[chaos] {failures} divergence(s) — FAIL")
        return 1
    print("[chaos] zero factor divergence under seeded faults — PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
