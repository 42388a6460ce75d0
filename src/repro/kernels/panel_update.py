"""Pallas TPU megakernel: fused streaming panel *update* for adaptive CUR.

``panel_score.py`` fused the three scoring reads of a panel into one VMEM
pass but still returned ``sc_a`` to HBM for XLA to finish the panel: the
admission decision and the scatter of the admitted columns into ``C``
each re-read data the kernel just held in registers. This kernel extends
the same accumulator pattern to the admission-only panel update
(:mod:`repro.stream.adaptive`):

* ``sc_a = S_C · A_L`` accumulated in VMEM scratch across the m-reduction
  (never an HBM round-trip between its producers and consumers);
* scores ``(resid2, energy)`` from the still-resident tile (the
  ``panel_score`` math);
* the admission decision itself — eligibility threshold + slot assignment
  — resolved in-kernel by a pairwise rank over the L panel columns:

      rank_j = #{i eligible : resid2_i > resid2_j
                              or (resid2_i = resid2_j and i < j)}
      admit_j ⇔ eligible_j and rank_j < min(free, panel_cap)
      slot_j  = n_filled + rank_j   (else the c_total sentinel)

  For eligible columns ``resid2 > thresh ≥ 0 > −1``, so this is exactly
  the selection of the XLA route's stable ``top_k`` over the −1-masked
  residuals followed by ``cumsum`` ranking (``top_k`` breaks ties by
  lower index — the same tie-break the rank formula encodes), at O(L²)
  vector ops instead of a sort;
* the admitted columns scattered into ``C`` as a one-hot matmul
  ``C ← C·keep + A_L·P`` with ``P[j, s] = [slot_j = s]`` (the
  ``countsketch.py`` slab idiom — a scatter the MXU can execute), ``C``
  aliased in/out.

The ``M += sc_a · S_Rᵀ|window`` fold stays with XLA in
``ops.panel_update``: an ``(s_c, s_r)`` block resident in VMEM, in and out,
does not fit the chip's 128 MiB once ``s_c = s_r = 3840`` (the Table-2
sizes for ``c = r = 256``), and the fold reads and writes ``M`` once per
panel either way — outside the kernel it re-reads only the ``(s_c, L)``
``sc_a``.

Grid ``(2, m/block_m)`` — phase-major, m-blocks fastest. Phase 0 runs the
m-reduction and, on its last step, scores + admission + the sc_a/stats
writes, parking the slot map in scratch; phase 1 revisits the m-blocks to
apply the C scatter row-block by row-block (``A_L`` is read once per
phase — the second read is the unavoidable one: ``C``'s row blocks need
the admitted columns' full m extent, which the phase-0 reduction has
already retired block by block). Phase 0 writes ``C`` through unchanged:
an aliased output block that is visited but never written would flush
whatever the window buffer holds.

All dims are pre-padded to block multiples by ``ops.panel_update``; zero
padding is inert everywhere (zero columns have zero energy and are never
eligible — the threshold comparison is strict — and the ``c_total``
sentinel lands either in a sliced-off padded C column or out of bounds).
fp32 accumulation regardless of input dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .panel_score import VMEM_LIMIT_BYTES


def _kernel(
    sc_ref, a_ref, q_ref, cin_ref, sf_ref, si_ref,
    cout_ref, sca_ref, stats_ref, slots_ref,
    acc_ref, slot_ref, *, c_total: int, panel_cap: int, L: int,
):
    p = pl.program_id(0)
    k = pl.program_id(1)
    nm = pl.num_programs(1)
    Lp = acc_ref.shape[1]

    @pl.when(p == 0)
    def _phase0():
        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # (s_c, bm) @ (bm, Lp) → (s_c, Lp), fp32 accumulate on the MXU
        acc_ref[...] += jnp.dot(
            sc_ref[...], a_ref[...], preferred_element_type=jnp.float32
        )
        # write-through: this C row block is revisited (and really written)
        # in phase 1; an aliased output block left unwritten flushes garbage
        cout_ref[...] = cin_ref[...]

        @pl.when(k == nm - 1)
        def _():
            y = acc_ref[...]  # (s_c, Lp) — the finished panel-sketch tile
            sca_ref[...] = y
            # t = Qᵀ y without materializing the transpose
            t = jax.lax.dot_general(
                q_ref[...], y, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (c_local, Lp)
            energy = jnp.sum(y * y, axis=0, keepdims=True)  # (1, Lp)
            resid2 = jnp.maximum(
                energy - jnp.sum(t * t, axis=0, keepdims=True), 0.0
            )
            # admission threshold (repro.stream.adaptive._update_c): the
            # panel mean is over *true* columns; padded columns have zero
            # energy so the in-kernel sum needs no mask
            panel_mean = jnp.sum(energy) / sf_ref[2]
            thresh = sf_ref[0] * jnp.maximum(sf_ref[1], panel_mean)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, Lp), 1)
            eligible = (resid2 > thresh) & (lane < L)
            # pairwise rank ≡ stable-top_k order (ties broken by lower index).
            # The (Lp, 1) column forms are read off the diagonal of the
            # (Lp, Lp) broadcast — one nonzero term per row, so exact — in
            # place of a transpose, which Mosaic cannot lower for these
            # vector shapes.
            ii = jax.lax.broadcasted_iota(jnp.int32, (Lp, Lp), 0)
            jj = jax.lax.broadcasted_iota(jnp.int32, (Lp, Lp), 1)
            diag = ii == jj
            ri = jnp.sum(jnp.where(diag, resid2, 0.0), axis=1, keepdims=True)
            ei = jnp.sum(
                jnp.where(diag, eligible.astype(jnp.float32), 0.0),
                axis=1, keepdims=True,
            ) > 0.0
            better = ei & ((ri > resid2) | ((ri == resid2) & (ii < jj)))
            rank = jnp.sum(better.astype(jnp.int32), axis=0, keepdims=True)
            limit = jnp.minimum(si_ref[1], panel_cap)  # min(free, cap)
            admit = eligible & (rank < limit)
            slot = jnp.where(admit, si_ref[0] + rank, c_total)  # (1, Lp)
            slot_ref[...] = jnp.broadcast_to(slot, slot_ref.shape)
            slots_ref[...] = jnp.broadcast_to(slot, slots_ref.shape)
            pad = jnp.zeros((stats_ref.shape[0] - 2, Lp), jnp.float32)
            stats_ref[...] = jnp.concatenate([resid2, energy, pad], axis=0)

    @pl.when(p == 1)
    def _phase1():
        # scatter-as-matmul (the countsketch slab idiom), built transposed
        # from the row-form slot map so no transpose is needed:
        # PT[s, j] = [slot_j = s]
        slot = slot_ref[0:1, :]  # (1, Lp)
        c_pad = cin_ref.shape[1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (c_pad, Lp), 0)
        PT = (slot == rows).astype(jnp.float32)  # (c_pad, Lp)
        nt = (((1,), (1,)), ((), ()))  # contract both operands' lane dims
        hits = jax.lax.dot_general(
            jnp.ones((8, Lp), jnp.float32), PT, nt,
            preferred_element_type=jnp.float32,
        )  # (8, c_pad) — per-slot admission counts (0/1, exact)
        keep = (hits[0:1, :] == 0.0).astype(jnp.float32)
        # exact copies: one-hot rows select single A entries, and fp32
        # contract precision keeps every mantissa bit on the MXU
        newc = jax.lax.dot_general(
            a_ref[...].astype(jnp.float32), PT, nt,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (bm, c_pad)
        cout_ref[...] = (
            cin_ref[...].astype(jnp.float32) * keep + newc
        ).astype(cout_ref.dtype)


@partial(
    jax.jit, static_argnames=("L", "c_total", "panel_cap", "block_m", "interpret")
)
def panel_update_kernel(
    sc: jax.Array,  # (s_c, m) dense column sketch
    a_l: jax.Array,  # (m, Lp) panel
    q: jax.Array,  # (s_c, c_q) zero-masked whitened basis of admitted sketches
    C: jax.Array,  # (m, c_pad) column factor — aliased to the first output
    scal_f: jax.Array,  # (8,) f32 [min_gain, run_mean, true_cols, …]
    scal_i: jax.Array,  # (8,) i32 [n_filled, free, …]
    *,
    L: int,  # true (unpadded) panel width
    c_total: int,  # true C column count — the not-admitted slot sentinel
    panel_cap: int,
    block_m: int = 256,
    interpret: bool = False,
) -> tuple:
    """All dims must already be padded to their block multiples (see ops.py).

    Returns ``(C', sc_a (s_c, Lp) f32, stats (8, Lp) f32, slots (8, Lp)
    i32)`` with ``stats[0] = resid2``, ``stats[1] = energy`` and
    ``slots[0]`` the per-column admission slot (``c_total`` sentinel).
    """
    s_c, m = sc.shape
    _, Lp = a_l.shape
    c_pad = C.shape[1]
    assert a_l.shape[0] == m and q.shape[0] == s_c and C.shape[0] == m
    assert s_c % 8 == 0 and Lp % 128 == 0
    assert q.shape[1] % 128 == 0 and c_pad % 128 == 0 and m % block_m == 0

    grid = (2, m // block_m)
    kernel = partial(_kernel, c_total=c_total, panel_cap=panel_cap, L=L)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s_c, block_m), lambda p, k: (0, k)),
            pl.BlockSpec((block_m, Lp), lambda p, k: (k, 0)),
            pl.BlockSpec((s_c, q.shape[1]), lambda p, k: (0, 0)),
            pl.BlockSpec((block_m, c_pad), lambda p, k: (k, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, c_pad), lambda p, k: (k, 0)),
            pl.BlockSpec((s_c, Lp), lambda p, k: (0, 0)),
            pl.BlockSpec((8, Lp), lambda p, k: (0, 0)),
            pl.BlockSpec((8, Lp), lambda p, k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(C.shape, C.dtype),
            jax.ShapeDtypeStruct((s_c, Lp), jnp.float32),
            jax.ShapeDtypeStruct((8, Lp), jnp.float32),
            jax.ShapeDtypeStruct((8, Lp), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((s_c, Lp), jnp.float32),
            pltpu.VMEM((8, Lp), jnp.int32),
        ],
        input_output_aliases={3: 0},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(sc, a_l, q, C, scal_f, scal_i)
