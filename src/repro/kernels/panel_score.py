"""Pallas TPU kernel: fused streaming panel scoring for adaptive CUR.

Per panel, the adaptive admission policy (``repro.stream.adaptive``) needs
three quantities from the same data:

* ``sc_a = S_C · A_L``                       — the panel sketch (also feeds
  the engine's shared ``M`` update);
* ``energy_j = ‖sc_a[:, j]‖²``               — per-column sketch energies
  (the admission threshold's denominator);
* ``resid2_j = energy_j − ‖Qᵀ sc_a[:, j]‖²`` — residual energy outside the
  admitted basis, with ``Q`` an (s_c × c) whitened (or orthonormal) basis
  of the admitted columns' sketches; unfilled slots' all-zero columns are
  inert (see ``repro.stream.adaptive._whitened_basis``).

Evaluated as three separate XLA ops this is three HBM round-trips per
panel: write ``sc_a``, read it back for the energies, read it again for the
projection. The fused kernel keeps the ``(s_c × bl)`` panel-sketch tile in
VMEM scratch across the whole m-reduction (the accumulator pattern of
``twoside_sketch.py``) and computes both scores from the still-resident
tile on the last reduction step — each ``A_L`` tile is read exactly once
and ``sc_a`` never makes an HBM round-trip:

    HBM traffic:  m·L + s_c·m·(L/bl) + s_c·c + s_c·L + 8·L
    vs unfused:   m·L + s_c·m·(L/bl) + s_c·c + 3·s_c·L + … (sc_a written
                  once and re-read twice)

Grid (j, l) = (L blocks, m blocks), reduction over l; scores land in rows
0 (resid2) / 1 (energy) of an (8, L) stats output (sublane-padded for the
f32 (8, 128) tile floor). All dims are pre-padded to block multiples by
``ops.panel_score`` — zero rows/columns contribute nothing to any of the
three outputs. fp32 accumulation regardless of input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM budget of the scoring kernels. Mosaic's 16 MiB default cannot
# hold the (s_c, ·) blocks at the Table-2 sketch sizes of c = 256 (s_c =
# 3840 needs ~17 MiB here and ~41 MiB in panel_update); a v5e core has
# 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 96 * 2**20


def _kernel(sc_ref, a_ref, q_ref, sca_ref, stats_ref, acc_ref):
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (s_c, bm) @ (bm, bl) → (s_c, bl), fp32 accumulate on the MXU
    acc_ref[...] += jnp.dot(sc_ref[...], a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(l == pl.num_programs(1) - 1)
    def _():
        y = acc_ref[...]  # (s_c, bl) — the finished panel-sketch tile
        sca_ref[...] = y.astype(sca_ref.dtype)
        # t = Qᵀ y without materializing the transpose: contract dim 0 ⊗ dim 0
        t = jax.lax.dot_general(
            q_ref[...], y, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (c, bl)
        energy = jnp.sum(y * y, axis=0, keepdims=True)  # (1, bl)
        resid2 = jnp.maximum(energy - jnp.sum(t * t, axis=0, keepdims=True), 0.0)
        pad = jnp.zeros((stats_ref.shape[0] - 2, y.shape[1]), jnp.float32)
        stats_ref[...] = jnp.concatenate([resid2, energy, pad], axis=0)


def panel_score_kernel(
    sc: jax.Array,  # (s_c, m) dense column sketch
    a_l: jax.Array,  # (m, L) panel
    q: jax.Array,  # (s_c, c) zero-masked orthonormal basis of admitted sketches
    *,
    block_m: int = 256,
    block_l: int = 128,
    interpret: bool = False,
) -> tuple:
    """All dims must already be padded to their block multiples (see ops.py).

    Returns ``(sc_a (s_c, L) f32, stats (8, L) f32)`` with ``stats[0] =
    resid2`` and ``stats[1] = energy``.
    """
    s_c, m = sc.shape
    _, L = a_l.shape
    c = q.shape[1]
    assert a_l.shape[0] == m and q.shape[0] == s_c
    assert s_c % 8 == 0 and c % 128 == 0
    assert m % block_m == 0 and L % block_l == 0

    grid = (L // block_l, m // block_m)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s_c, block_m), lambda j, l: (0, l)),
            pl.BlockSpec((block_m, block_l), lambda j, l: (l, j)),
            pl.BlockSpec((s_c, c), lambda j, l: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_c, block_l), lambda j, l: (0, j)),
            pl.BlockSpec((8, block_l), lambda j, l: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_c, L), jnp.float32),
            jax.ShapeDtypeStruct((8, L), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s_c, block_l), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(sc, a_l, q)
