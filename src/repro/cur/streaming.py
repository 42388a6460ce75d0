"""Single-pass streaming CUR over L-column panels.

Same streaming contract as ``repro.core.svd.sp_svd_update`` (Algorithm 3) —
both now ride the shared :mod:`repro.stream.engine`: ``A`` arrives as column
panels ``A_L`` and is never retained. Per panel:

* ``C``: the panel's selected columns land in their slots (selected column
  j with ``offset ≤ col_idx[j] < offset+L`` is copied out of the panel);
* ``R[:, cols] = A_L[row_idx, :]`` — selected rows accumulate left→right;
* ``M += (S_C A_L) · S_R[:, cols]ᵀ`` via the ``cols()`` sketch-window
  primitive of ``repro.core.sketching`` (column-sliceable families only:
  gaussian / countsketch / osnap / sampling).

Memory: C (m·c) + R (r·n) + M (s_c·s_r) — the factors themselves plus a
constant-size core sketch; ``finalize`` then runs the Fast-GMR core solve.
Because ``Σ_L S_C A_L S_R[:,cols]ᵀ = S_C A S_Rᵀ`` exactly, the finalized
factors match one-shot :func:`repro.cur.fast_cur` on identical sketches up
to fp32 summation order (tested in ``tests/test_cur.py``). Drive the state
with :func:`repro.stream.stream_panels` — scan-compiled by default (one
program per chunk, donated buffers), with the per-panel jitted step behind
``jit="per-panel"``.

This module keeps *fixed* pre-pass indices (uniform, or scores from a prior
epoch / sketched estimate). For residual-driven in-stream column
admission/eviction and adaptive row admission (the v2 replacement policy)
see :mod:`repro.stream.adaptive`; for DP-sharded ingestion of either
variant see :mod:`repro.stream.distributed`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.gmr import fast_gmr_core
from ..core.sketching import draw_sketch
from ..obs.spans import spanned
from ..obs.telemetry import fixed_stream_telemetry, init_telemetry
from ..stream.engine import (
    SCOPE_SOLVE,
    PanelOps,
    PanelState,
    copy_selected_columns,
    fresh_pytree,
    padded_n,
    panel_update,
    truncated_R,
)
from .cur import CURResult, cur_sketch_sizes

__all__ = [
    "StreamingCURState",
    "CURStreamCtx",
    "STREAMING_CUR_OPS",
    "STREAMING_CUR_TEL_OPS",
    "streaming_cur_init",
    "streaming_cur_update",
    "streaming_cur_finalize",
]


@dataclasses.dataclass(frozen=True)
class CURStreamCtx:
    """Fixed selection indices + the shared core sketching operators."""

    col_idx: jax.Array  # (c,)
    row_idx: jax.Array  # (r,)
    S_C: object  # column-sliceable sketch, (s_c, m)
    S_R: object  # column-sliceable sketch, (s_r, n_pad)


jax.tree_util.register_dataclass(
    CURStreamCtx, data_fields=["col_idx", "row_idx", "S_C", "S_R"], meta_fields=[]
)


def _cur_core_sketches(ctx: CURStreamCtx):
    return ctx.S_C, ctx.S_R


def _cur_update_c(ctx: CURStreamCtx, C, A_L, sc_a, off):
    # selected columns that live in this panel → their C slots
    return ctx, copy_selected_columns(ctx.col_idx, C, A_L, off)


def _cur_r_block(ctx: CURStreamCtx, A_L, off):
    # selected rows of the panel → R[:, off:off+L]
    return jnp.take(A_L, ctx.row_idx, axis=0)  # (r, L)


def _cur_chunk_fold(ctx: CURStreamCtx, C, R, block, bcol0, start, width):
    """Fused-scan hook: the whole chunk's C/R writes in one pass.

    Fixed indices make every panel's factor write a pure copy of ``A``
    entries, so the per-panel loop is unnecessary: the selected columns
    falling inside ``[start, start+width)`` are gathered once into their C
    slots, and the selected rows' chunk stripe lands in ``R`` with one
    window write — bitwise the values the per-panel path copies.
    """
    rel = ctx.col_idx - start
    in_chunk = (rel >= 0) & (rel < width)
    picked = jnp.take(block, bcol0 + jnp.clip(rel, 0, width - 1), axis=1)
    C = jnp.where(in_chunk[None, :], picked.astype(C.dtype), C)
    stripe = jax.lax.dynamic_slice_in_dim(
        jnp.take(block, ctx.row_idx, axis=0), bcol0, width, axis=1
    )
    R = jax.lax.dynamic_update_slice_in_dim(
        R, stripe.astype(R.dtype), start, axis=1
    )
    return ctx, C, R


STREAMING_CUR_OPS = PanelOps(
    name="streaming_cur",
    core_sketches=_cur_core_sketches,
    update_c=_cur_update_c,
    r_block=_cur_r_block,
    chunk_fold=_cur_chunk_fold,
)

# Telemetered twin — same hooks plus the fixed-index diagnostics fold; one
# module-level instance so telemetered inits share jit caches.
STREAMING_CUR_TEL_OPS = dataclasses.replace(
    STREAMING_CUR_OPS, telemetry=fixed_stream_telemetry
)

# Streaming state: the generic engine state with ctx = CURStreamCtx
# (``state.S_C`` etc. resolve through to ctx for back-compat).
StreamingCURState = PanelState


@spanned("stream/streaming_cur/init")
def streaming_cur_init(
    key,
    m: int,
    n: int,
    col_idx: jax.Array,
    row_idx: jax.Array,
    *,
    s_c: Optional[int] = None,
    s_r: Optional[int] = None,
    eps: float = 0.05,
    rho_est: float = 2.0,
    sketch: str = "countsketch",
    osnap_p: int = 2,
    dtype=jnp.float32,
    sketches=None,
    panel: Optional[int] = None,
    telemetry: bool = False,
) -> StreamingCURState:
    """Draw column-sliceable core sketches and allocate zero accumulators.

    Args:
        key: PRNG key for the core sketches (ignored when ``sketches`` given).
        m, n: stream shape — ``A`` is (m, n), arriving as column panels.
        col_idx, row_idx: fixed pre-pass selections, (c,) / (r,) int32.
        s_c, s_r: core sketch sizes; default to the Table-2
            :func:`cur_sketch_sizes` for ``(c, r, eps, rho_est)``.
        eps, rho_est: Table-2 sketch-size parameters (ε target, ρ estimate).
        sketch: column-sliceable family (``countsketch``/``osnap``/``gaussian``).
        osnap_p: nonzeros per column for the OSNAP family.
        dtype: accumulator dtype.
        sketches: optional pre-drawn ``(S_C, S_R)`` pair (shared randomness
            with a one-shot :func:`repro.cur.fast_cur` for parity tests).
        panel: fixed streaming width — pre-pads ``R``/``S_R`` to a whole
            number of panels so ragged tails can be zero-padded (exact; see
            :mod:`repro.stream.engine`).
        telemetry: attach an in-scan diagnostics frame
            (:class:`repro.obs.telemetry.TelemetryFrame`) + the a-posteriori
            error estimator's test sketch (:func:`repro.obs.estimate_rel_error`).
            Requires ``panel=``; factors are bit-identical with it on or off.

    Returns:
        A fresh :class:`StreamingCURState` with zero (m,c)/(r,n_pad)/(s_c,s_r)
        accumulators, ready for :func:`streaming_cur_update` /
        :func:`repro.stream.stream_panels`.
    """
    # Copies, not views: the scan path donates the state's buffers, and a
    # zero-copy asarray would hand the caller's own arrays to the donor.
    col_idx = jnp.array(col_idx, jnp.int32)
    row_idx = jnp.array(row_idx, jnp.int32)
    c, r = col_idx.shape[0], row_idx.shape[0]
    if sketches is None:
        sizes = cur_sketch_sizes(c, r, eps=eps, rho=rho_est)
        s_c = min(s_c or sizes["s_c"], m)
        s_r = min(s_r or sizes["s_r"], n)
        k_sc, k_sr = jax.random.split(key)
        S_C = draw_sketch(k_sc, sketch, s_c, m, p=osnap_p, dtype=dtype)
        S_R = draw_sketch(k_sr, sketch, s_r, n, p=osnap_p, dtype=dtype)
    else:
        S_C, S_R = fresh_pytree(sketches)  # donation-safe copies
        s_c, s_r = S_C.s, S_R.s
    S_R.cols(0, 1)  # fail fast on non-sliceable families (srht)
    n_pad = padded_n(n, panel) if panel else n
    ctx = CURStreamCtx(col_idx=col_idx, row_idx=row_idx, S_C=S_C, S_R=S_R.pad_cols(n_pad))
    tel = None
    ops = STREAMING_CUR_OPS
    if telemetry:
        if panel is None:
            raise ValueError(
                "telemetry=True requires a fixed panel= width (the diagnostics "
                "frame is indexed by global panel id)"
            )
        # Held-out estimator sketch: fold a constant so the draw is disjoint
        # from the split(key) core-sketch draws but reproducible from one seed.
        tel = init_telemetry(jax.random.fold_in(key, 7), m, n, panel)
        ops = STREAMING_CUR_TEL_OPS
    return StreamingCURState(
        C=jnp.zeros((m, c), dtype),
        R=jnp.zeros((r, n_pad), dtype),
        M=jnp.zeros((s_c, s_r), dtype),
        offset=jnp.zeros((), jnp.int32),
        ctx=ctx,
        ops=ops,
        n=n,
        tel=tel,
    )


def streaming_cur_update(state: StreamingCURState, A_L: jax.Array) -> StreamingCURState:
    """Consume one (m, L) column panel ``A_L`` at the state's current offset.

    jit-compatible (L static per panel width); thin alias of the shared
    :func:`repro.stream.engine.panel_update`.
    """
    return panel_update(state, A_L)


def streaming_cur_finalize(state: StreamingCURState) -> CURResult:
    """Fast-GMR core solve on the accumulated pieces (Algorithm 1 step 11).

    Computes ``U = (S_C C)† M (R S_Rᵀ)†`` from the streamed (m,c)/(r,n)
    factors and the (s_c, s_r) core sketch ``M = S_C A S_Rᵀ``; returns a
    :class:`~repro.cur.cur.CURResult` matching one-shot
    :func:`repro.cur.fast_cur` on identical sketches up to fp32 summation
    order.
    """
    ctx = state.ctx
    with jax.named_scope(SCOPE_SOLVE):
        R = truncated_R(state)
        ScC = ctx.S_C.apply(state.C)  # (s_c, c)
        RSr = ctx.S_R.apply_t(R)  # (r, s_r)
        U = fast_gmr_core(ScC, state.M, RSr)
    return CURResult(C=state.C, U=U, R=R, col_idx=ctx.col_idx, row_idx=ctx.row_idx)


# Compiled at module scope (one trace per shape) and dispatched inside a host
# span; the state is NOT donated — callers inspect it after finalizing.
streaming_cur_finalize = spanned("stream/streaming_cur/finalize")(jax.jit(streaming_cur_finalize))
