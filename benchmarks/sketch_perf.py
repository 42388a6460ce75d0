"""Kernel-layer benchmark: Pallas kernels vs pure-jnp oracles.

On this CPU container the Pallas bodies execute in interpret mode (Python)
— wall-time there is meaningless, so we report (i) correctness deltas vs
the ref oracle, (ii) XLA wall-time of the oracle path (the deployable CPU
fallback), and (iii) the *structural* HBM-traffic model of the fused
kernel vs the sequential evaluation — the quantity that decides TPU perf
(memory-bound regime; see kernels/twoside_sketch.py docstring).

  PYTHONPATH=src python -m benchmarks.sketch_perf [--smoke]
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    countsketch_apply,
    countsketch_ref,
    panel_score,
    panel_score_ref,
    panel_update,
    panel_update_ref,
    twoside_sketch,
    twoside_sketch_ref,
)

from .common import time_call, write_bench_json


def _traffic_model(m, n, s_c, s_r, dtype_bytes=2):
    fused = (m * n + m * s_c + n * s_r + s_c * s_r) * dtype_bytes
    sequential = (m * n + m * s_c + 2 * s_c * n + n * s_r + s_c * s_r) * dtype_bytes
    return fused, sequential


def _panel_score_traffic(s_c, m, L, c, block_l=128, dtype_bytes=4):
    """HBM bytes: fused kernel vs the unfused three-op evaluation.

    Unfused: sc_a = S_C·A_L is written to HBM once and read back twice (the
    energy reduction and the Qᵀ·sc_a projection). Fused: the (s_c, bl) tile
    never leaves VMEM between the matmul and the two reductions — sc_a is
    written exactly once as an output and the extra traffic is just the
    (8, L) stats row. The fused side does re-fetch the S_C stripe once per
    L-block (its block index varies along the m-reduction, so it cannot
    stay resident across j sweeps — ``s_c·m·ceil(L/bl)`` bytes, matching
    the kernel docstring's traffic formula); A_L tiles and Q are read once.
    """
    l_sweeps = -(-L // block_l)
    fused = (m * L + s_c * m * l_sweeps + s_c * c + s_c * L + 8 * L) * dtype_bytes
    unfused = (m * L + s_c * m + s_c * c + 3 * s_c * L + c * L + 2 * L) * dtype_bytes
    return fused, unfused


def _panel_update_traffic(s_c, m, L, c, s_r, block_m=256, dtype_bytes=4):
    """HBM bytes: fused megakernel vs the unfused five-op panel update.

    Unfused: ``sc_a`` is written once and read back three times (energy,
    Qᵀ projection, M fold), the candidate columns of ``A_L`` are gathered a
    second time for the C scatter, and C/M each make a full read+write
    round-trip through XLA's scatter. Fused: ``sc_a`` is written once and
    read back once by the XLA ``M`` fold, ``A_L`` tiles are read at most
    twice (sketch reduction + the C write of admitted row blocks), and C is
    aliased in place — C traffic is the admitted row-blocks' read+write,
    counted here at the full ``m·c`` worst case.
    """
    fused = (2 * m * L + s_c * m + s_c * c + L * s_r + 2 * s_c * s_r
             + 2 * m * c + 2 * s_c * L + 2 * 8 * L) * dtype_bytes
    unfused = (2 * m * L + s_c * m + s_c * c + L * s_r + 2 * s_c * s_r
               + 2 * m * c + 4 * s_c * L + c * L + 2 * L) * dtype_bytes
    return fused, unfused


def run(trials: int = 3, quick: bool = False) -> list:
    rows = []
    shapes = [(256, 2048, 2048, 256)] if quick else [
        (128, 1024, 1024, 128),
        (256, 2048, 2048, 256),
        (256, 4096, 8192, 256),
    ]
    for s_c, m, n, s_r in shapes:
        ks = jax.random.split(jax.random.key(0), 3)
        Sc = jax.random.normal(ks[0], (s_c, m), jnp.float32)
        A = jax.random.normal(ks[1], (m, n), jnp.float32)
        SrT = jax.random.normal(ks[2], (n, s_r), jnp.float32)
        out = twoside_sketch(Sc, A, SrT)
        ref = twoside_sketch_ref(Sc, A, SrT)
        rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        us_ref = time_call(jax.jit(twoside_sketch_ref), Sc, A, SrT)
        fused, seq = _traffic_model(m, n, s_c, s_r)
        rows.append({
            "name": f"kernel/twoside/{s_c}x{m}x{n}x{s_r}",
            "us_per_call": round(us_ref, 1),
            "derived": f"pallas_rel_err={rel:.2e};hbm_fused={fused/1e6:.1f}MB;"
                       f"hbm_seq={seq/1e6:.1f}MB;traffic_save={seq/fused:.2f}x",
        })

    # Fused panel-scoring kernel (adaptive streaming CUR hot path): interpret
    # mode executes the kernel body for correctness; the XLA wall-time of the
    # unfused three-op reference is the deployable CPU fallback, and the
    # traffic model is what decides the TPU win (memory-bound regime).
    ps_shapes = [(240, 2048, 128, 16)] if quick else [
        (240, 1024, 128, 16),
        (240, 2048, 128, 16),
        (512, 4096, 256, 32),
    ]
    for s_c, m, L, c in ps_shapes:
        ks = jax.random.split(jax.random.key(2), 3)
        Sc = jax.random.normal(ks[0], (s_c, m), jnp.float32)
        A_L = jax.random.normal(ks[1], (m, L), jnp.float32)
        Q, _ = jnp.linalg.qr(jax.random.normal(ks[2], (s_c, c), jnp.float32))
        Qm = Q * (jnp.arange(c) < max(1, c // 2))  # half-filled admitted basis
        sc_a, r2, en = panel_score(Sc, A_L, Qm, interpret=True)
        sc_ref, r2_ref, en_ref = panel_score_ref(Sc, A_L, Qm)
        scale = float(jnp.max(jnp.abs(en_ref)))
        rel = max(
            float(jnp.max(jnp.abs(sc_a - sc_ref)) / jnp.max(jnp.abs(sc_ref))),
            float(jnp.max(jnp.abs(r2 - r2_ref))) / scale,
            float(jnp.max(jnp.abs(en - en_ref))) / scale,
        )
        us_ref = time_call(jax.jit(panel_score_ref), Sc, A_L, Qm)
        fused, unfused = _panel_score_traffic(s_c, m, L, c)
        rows.append({
            "name": f"kernel/panel_score/{s_c}x{m}x{L}_c{c}",
            "us_per_call": round(us_ref, 1),
            "derived": f"pallas_rel_err={rel:.2e};hbm_fused={fused/1e6:.1f}MB;"
                       f"hbm_unfused={unfused/1e6:.1f}MB;traffic_save={unfused/fused:.2f}x;"
                       f"sc_a_hbm_roundtrips=0vs2",
        })

    # Fused panel-update megakernel (sketch + score + admission + C scatter
    # + M fold in one launch, C/M aliased in place). Interpret mode executes
    # the kernel body against the unfused XLA oracle; the oracle wall-time
    # is the CPU fallback and the traffic model the TPU-decisive number.
    pu_shapes = [(240, 2048, 256, 16, 240)] if quick else [
        (240, 1024, 128, 16, 240),
        (240, 2048, 256, 16, 240),
        (512, 4096, 256, 32, 512),
    ]
    for s_c, m, L, c, s_r in pu_shapes:
        ks = jax.random.split(jax.random.key(3), 6)
        Sc = jax.random.normal(ks[0], (s_c, m), jnp.float32)
        A_L = jax.random.normal(ks[1], (m, L), jnp.float32)
        SrT = jax.random.normal(ks[2], (L, s_r), jnp.float32)
        Q, _ = jnp.linalg.qr(jax.random.normal(ks[3], (s_c, c), jnp.float32))
        Qm = Q * (jnp.arange(c) < max(1, c // 2))
        C = jax.random.normal(ks[4], (m, c), jnp.float32)
        M = jax.random.normal(ks[5], (s_c, s_r), jnp.float32)
        kw = dict(min_gain=0.5, run_mean=0.0, true_cols=float(L),
                  n_filled=c // 2, free=c - c // 2, panel_cap=4)
        out = panel_update(Sc, A_L, SrT, Qm, C, M, interpret=True, **kw)
        ref = panel_update_ref(Sc, A_L, SrT, Qm, C, M, **kw)
        rel = 0.0
        for o, rf in zip(out[:5], ref[:5]):  # C, M, sc_a, resid2, energy
            scale = float(jnp.max(jnp.abs(rf))) + 1e-30
            rel = max(rel, float(jnp.max(jnp.abs(o - rf))) / scale)
        slots_equal = bool(jnp.array_equal(out[5], ref[5]))
        us_ref = time_call(
            jax.jit(lambda *a: panel_update_ref(*a, **kw)), Sc, A_L, SrT, Qm, C, M
        )
        fused, unfused = _panel_update_traffic(s_c, m, L, c, s_r)
        rows.append({
            "name": f"kernel/panel_update/{s_c}x{m}x{L}_c{c}",
            "us_per_call": round(us_ref, 1),
            "derived": f"pallas_rel_err={rel:.2e};slots_exact={slots_equal};"
                       f"hbm_fused={fused/1e6:.1f}MB;hbm_unfused={unfused/1e6:.1f}MB;"
                       f"traffic_save={unfused/fused:.2f}x;sc_a_hbm_readbacks=1vs3",
        })

    cs_shapes = [(256, 4096, 1024)] if quick else [(128, 2048, 512), (256, 4096, 1024), (512, 8192, 2048)]
    for s, m, n in cs_shapes:
        ks = jax.random.split(jax.random.key(1), 3)
        h = jax.random.randint(ks[0], (m,), 0, s)
        sg = jax.random.rademacher(ks[1], (m,), jnp.float32)
        A = jax.random.normal(ks[2], (m, n), jnp.float32)
        out = countsketch_apply(h, sg, A, s)
        ref = countsketch_ref(h, sg, A, s)
        rel = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-30))
        us_ref = time_call(jax.jit(countsketch_ref, static_argnums=3), h, sg, A, s)
        rows.append({
            "name": f"kernel/countsketch/s{s}_{m}x{n}",
            "us_per_call": round(us_ref, 1),
            "derived": f"pallas_rel_err={rel:.2e};hbm_passes_over_A=1",
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="single shape per kernel (CI)")
    ap.add_argument("--out-dir", default=None, help="where to write BENCH_kernels.json")
    args = ap.parse_args()
    rows = run(trials=1 if args.smoke else 3, quick=args.smoke)
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row['name']},{row['us_per_call']},{str(row['derived']).replace(',', ';')}")
    path = write_bench_json("kernels", rows, meta={"smoke": args.smoke}, out_dir=args.out_dir)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
