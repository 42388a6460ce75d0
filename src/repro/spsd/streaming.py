"""Single-pass streaming SPSD approximation (Algorithm 2 over a kernel
*stream*), as a symmetric plug-in of the panel engine.

The batch path (:mod:`repro.spsd.batch`) assumes an entry oracle it can
query at will. At serving scale the kernel often arrives instead as column
panels ``K_L`` that are produced once and never retained — exactly the
streaming contract of :mod:`repro.stream.engine`, with one structural
difference: the operand is **symmetric**, so the row factor is *tied* to
the column factor (``R = Cᵀ``) and accumulating it would be redundant.
This module plugs SPSD into the engine's ``symmetric=True`` mode:

* ``C``: the selected kernel columns land in their slots as their panels
  stream by (fixed ``col_idx``), or are *admitted in-stream* by the
  adaptive residual-scoring policy of :mod:`repro.stream.adaptive` applied
  to kernel columns (:func:`adaptive_spsd_init` — same fused
  ``sketch_panel`` scoring, admission/eviction knobs and disjoint-slot
  sharding hooks, reused verbatim with ``rows=None``);
* ``M += S₁ K_L S₂[:, cols]ᵀ`` — the engine's shared core-sketch update;
  both sketches live on the same n-dimensional index space (one family,
  two independent draws — Algorithm 2 requires ``S₁ ⊥ S₂``);
* no R half at all: the engine skips it, and ``truncated_R`` derives
  ``R = Cᵀ``.

Finalize solves ``X̃ = (S₁C)† M (Cᵀ S₂ᵀ)†`` and projects onto the PSD cone
(Theorem 2), returning the same :class:`~repro.spsd.batch.SPSDResult`
contract as the batch paths. With the *same* ``col_idx`` and the same
:class:`~repro.core.sketching.RowSampling` pair
(:func:`repro.spsd.batch.leverage_sampling_sketches`), the streamed result
matches batch :func:`~repro.spsd.batch.faster_spsd` exactly up to fp32
order — each ``M`` entry receives exactly one nonzero panel contribution —
the parity contract of ``tests/test_spsd_stream.py``, which holds under
DP-sharded ingestion too (:mod:`repro.stream.distributed`; tied-operand
states shard with one psum and a mirrored merge, no R traffic).

Memory: C (n·c) + M (s²) — the stream itself is never retained. Every
kernel entry flows through the update once, so ``entries_observed`` is n²
by construction; the streaming win is *memory and passes*, not queries.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.gmr import fast_gmr_core
from ..core.projections import psd_project
from ..core.sketching import draw_sketch
from ..obs.spans import spanned
from ..obs.telemetry import (
    adaptive_stream_telemetry,
    fixed_stream_telemetry,
    init_telemetry,
)
from ..stream.adaptive import (
    AdaptiveCURCtx,
    _bind_shard,
    _collective_ctx,
    _chunk_fold,
    _core_sketches,
    _fused_step,
    _merge_ctx,
    _panel_kernel,
    _prep_shard,
    _sketch_panel,
    _supports_fused,
    _update_c,
)
from ..stream.engine import (
    SCOPE_SOLVE,
    PanelOps,
    PanelState,
    copy_selected_columns,
    fresh_pytree,
    padded_n,
)
from .batch import SPSDResult

__all__ = [
    "SPSDStreamCtx",
    "STREAMING_SPSD_OPS",
    "STREAMING_SPSD_TEL_OPS",
    "ADAPTIVE_SPSD_OPS",
    "ADAPTIVE_SPSD_TEL_OPS",
    "streaming_spsd_init",
    "streaming_spsd_finalize",
    "adaptive_spsd_init",
    "adaptive_spsd_finalize",
]


@dataclasses.dataclass(frozen=True)
class SPSDStreamCtx:
    """Fixed column selection + the tied-operand core sketch pair.

    Both sketches are (s, n) operators over the *same* index space (the
    stream is square); ``S2`` is the column-sliceable one driving the
    ``M`` window updates and is padded to ``n_pad`` at init.
    """

    col_idx: jax.Array  # (c,)
    S1: object  # (s, n) left core sketch
    S2: object  # (s, n_pad) right core sketch (column-sliceable)


jax.tree_util.register_dataclass(
    SPSDStreamCtx, data_fields=["col_idx", "S1", "S2"], meta_fields=[]
)


def _spsd_core_sketches(ctx: SPSDStreamCtx):
    return ctx.S1, ctx.S2


def _spsd_update_c(ctx: SPSDStreamCtx, C, K_L, sc_a, off):
    # selected kernel columns that live in this panel → their C slots
    return ctx, copy_selected_columns(ctx.col_idx, C, K_L, off)


def _spsd_chunk_fold(ctx: SPSDStreamCtx, C, R, block, bcol0, start, width):
    """Fused-scan hook: the whole chunk's fixed-index C copies in one gather
    (the symmetric half of :func:`repro.cur.streaming._cur_chunk_fold` — no
    R side, ``R = Cᵀ`` is derived)."""
    rel = ctx.col_idx - start
    in_chunk = (rel >= 0) & (rel < width)
    picked = jnp.take(block, bcol0 + jnp.clip(rel, 0, width - 1), axis=1)
    C = jnp.where(in_chunk[None, :], picked.astype(C.dtype), C)
    return ctx, C, R


STREAMING_SPSD_OPS = PanelOps(
    name="streaming_spsd",
    core_sketches=_spsd_core_sketches,
    update_c=_spsd_update_c,
    chunk_fold=_spsd_chunk_fold,
    symmetric=True,
)


# Adaptive in-stream column admission over kernel columns: the column half
# of the adaptive-CUR policy applies verbatim (scores are computed from the
# sketches alone; ``rows=None`` disables the row machinery), with the
# symmetric engine skipping the R half. The disjoint-slot sharding hooks —
# and both fused routes (the hoisted-sketch scan body and the Route-B
# panel-update megakernel; the (0,)-row ``row_idx`` makes the R stripe of
# the shared ``_chunk_fold`` a no-op) — come along for free.
ADAPTIVE_SPSD_OPS = PanelOps(
    name="adaptive_spsd",
    core_sketches=_core_sketches,
    sketch_panel=_sketch_panel,
    update_c=_update_c,
    prep_shard=_prep_shard,
    bind_shard=_bind_shard,
    merge_ctx=_merge_ctx,
    collective_ctx=_collective_ctx,
    chunk_fold=_chunk_fold,
    fused_step=_fused_step,
    supports_fused=_supports_fused,
    panel_kernel=_panel_kernel,
    symmetric=True,
)

# Telemetered twins — same hooks plus the per-panel diagnostics folds; one
# module-level instance each so telemetered inits share jit caches.
STREAMING_SPSD_TEL_OPS = dataclasses.replace(
    STREAMING_SPSD_OPS, telemetry=fixed_stream_telemetry
)
ADAPTIVE_SPSD_TEL_OPS = dataclasses.replace(
    ADAPTIVE_SPSD_OPS, telemetry=adaptive_stream_telemetry
)


def _draw_pair(key, sketch: str, s: int, n: int, osnap_p: int, dtype):
    k1, k2 = jax.random.split(key)
    S1 = draw_sketch(k1, sketch, s, n, p=osnap_p, dtype=dtype)
    S2 = draw_sketch(k2, sketch, s, n, p=osnap_p, dtype=dtype)
    return S1, S2


def _resolve_sketch_pair(key, n, c, s, sketch, osnap_p, dtype, sketches, panel):
    """Shared init plumbing for both streaming-SPSD variants: validate the
    budget sizes (matching the batch paths' ``_validate_sizes`` convention),
    draw or donation-copy the ``(S₁, S₂)`` pair, fail fast on
    non-sliceable families, and pad ``S₂`` to the panel-aligned width.

    Returns ``(S1, S2_padded, n_pad)``.
    """
    if not 0 < c <= n:
        raise ValueError(f"need 0 < c <= n column slots, got c={c}, n={n}")
    if sketches is None:
        if s is not None and s <= 0:
            raise ValueError(f"need s > 0 sketch rows, got s={s} (n={n})")
        s = min(s or 10 * c, n)
        S1, S2 = _draw_pair(key, sketch, s, n, osnap_p, dtype)
    else:
        S1, S2 = fresh_pytree(sketches)  # donation-safe copies
    S2.cols(0, 1)  # fail fast on non-sliceable families (srht)
    n_pad = padded_n(n, panel) if panel else n
    return S1, S2.pad_cols(n_pad), n_pad


def _maybe_telemetry(telemetry: bool, key, n: int, panel, base_ops, tel_ops):
    """Shared telemetry plumbing for the SPSD inits: allocate the diagnostics
    frame (``m = n`` — the stream is square) on an estimator key folded off
    the init key, and swap in the telemetered ops twin."""
    if not telemetry:
        return None, base_ops
    if panel is None:
        raise ValueError(
            "telemetry=True requires a fixed panel= width (the diagnostics "
            "frame is indexed by global panel id)"
        )
    return init_telemetry(jax.random.fold_in(key, 7), n, n, panel), tel_ops


@spanned("stream/streaming_spsd/init")
def streaming_spsd_init(
    key,
    n: int,
    col_idx: jax.Array,
    *,
    s: Optional[int] = None,
    sketch: str = "countsketch",
    osnap_p: int = 2,
    dtype=jnp.float32,
    sketches: Optional[Tuple] = None,
    panel: Optional[int] = None,
    telemetry: bool = False,
) -> PanelState:
    """Allocate a fixed-index streaming-SPSD state (symmetric engine plug-in).

    Args:
        key: PRNG key for the core sketch pair (ignored when ``sketches``
            given).
        n: stream size — ``K`` is (n, n), arriving as column panels.
        col_idx: selected kernel columns, (c,) int32 (uniform pre-pass, or
            any :func:`repro.cur.select_columns` policy via a prior
            epoch / sketch — see ``repro.cur.symmetric_cur`` for the batch
            equivalent).
        s: core sketch size; defaults to the paper's §6.2 "≈ optimal"
            operating point ``min(10·c, n)``.
        sketch: sketch family for both draws (``countsketch`` / ``osnap`` /
            ``gaussian``; any column-sliceable family).
        osnap_p: nonzeros per column for the OSNAP family.
        dtype: accumulator dtype.
        sketches: optional pre-drawn ``(S₁, S₂)`` pair — e.g. the
            leverage-sampling pair of
            :func:`repro.spsd.batch.leverage_sampling_sketches` for exact
            batch parity.
        panel: fixed streaming panel width — pre-pads ``S₂`` so ragged
            tails are zero-padded exactly (see :mod:`repro.stream.engine`).
        telemetry: attach an in-scan diagnostics frame + the a-posteriori
            error estimator's test sketch (:func:`repro.obs.estimate_rel_error`
            — call it after the stream is fully consumed; the symmetric
            ``C X Cᵀ`` acts on all rows, so the mid-stream estimate is
            biased). Requires ``panel=``; factors are bit-identical with it
            on or off.

    Returns:
        A :class:`~repro.stream.engine.PanelState` wired to
        :data:`STREAMING_SPSD_OPS` (note the ``(0, n_pad)`` R placeholder —
        R is derived as ``Cᵀ``). Drive it with ``stream_panels`` /
        ``simulate_sharded_stream`` / ``mesh_sharded_stream`` and finish
        with :func:`streaming_spsd_finalize`.
    """
    # Copy, not view: the scan path donates the state's buffers.
    col_idx = jnp.array(col_idx, jnp.int32)
    c = col_idx.shape[0]
    if c and not (0 <= int(jnp.min(col_idx)) and int(jnp.max(col_idx)) < n):
        raise ValueError(
            f"col_idx entries must lie in [0, {n}), got range "
            f"[{int(jnp.min(col_idx))}, {int(jnp.max(col_idx))}] — an "
            "out-of-range index would leave its C slot permanently zero"
        )
    S1, S2, n_pad = _resolve_sketch_pair(
        key, n, c, s, sketch, osnap_p, dtype, sketches, panel
    )
    ctx = SPSDStreamCtx(col_idx=col_idx, S1=S1, S2=S2)
    tel, ops = _maybe_telemetry(telemetry, key, n, panel, STREAMING_SPSD_OPS,
                                STREAMING_SPSD_TEL_OPS)
    return PanelState(
        C=jnp.zeros((n, c), dtype),
        R=jnp.zeros((0, n_pad), dtype),  # tied operand: R = Cᵀ is derived
        M=jnp.zeros((S1.s, S2.s), dtype),
        offset=jnp.zeros((), jnp.int32),
        ctx=ctx,
        ops=ops,
        n=n,
        tel=tel,
    )


def streaming_spsd_finalize(state: PanelState) -> SPSDResult:
    """Algorithm 2 core solve on the streamed pieces + PSD projection.

    ``X̃ = (S₁C)† M (Cᵀ S₂ᵀ)†`` with ``M = S₁ K S₂ᵀ`` accumulated panel by
    panel; matches batch :func:`repro.spsd.batch.faster_spsd` exactly (up
    to fp32 order) on identical ``col_idx``/``sketches``.
    ``entries_observed`` is n² — every kernel entry flowed through the
    stream once (the streaming win is memory and single-pass access, not
    query count).
    """
    ctx = state.ctx
    with jax.named_scope(SCOPE_SOLVE):
        S1C = ctx.S1.apply(state.C)  # (s, c)
        CS2 = ctx.S2.apply(state.C).T  # (c, s)
        X = psd_project(fast_gmr_core(S1C, state.M, CS2))
    return SPSDResult(
        C=state.C, X=X, col_idx=ctx.col_idx, entries_observed=state.n * state.n
    )


@spanned("stream/adaptive_spsd/init")
def adaptive_spsd_init(
    key,
    n: int,
    c: int,
    *,
    s: Optional[int] = None,
    sketch: str = "countsketch",
    osnap_p: int = 2,
    min_gain: float = 2.0,
    panel_cap: Optional[int] = None,
    swap_gain: Optional[float] = None,
    dtype=jnp.float32,
    sketches: Optional[Tuple] = None,
    panel: Optional[int] = None,
    telemetry: bool = False,
) -> PanelState:
    """Adaptive streaming SPSD: kernel columns are *admitted in-stream*.

    Reuses the residual-scoring column policy of
    :mod:`repro.stream.adaptive` (fused ``sketch_panel`` scoring,
    ``min_gain`` admission, optional ``swap_gain`` eviction, per-worker
    disjoint slot ranges under sharding) on the symmetric engine — the row
    machinery is off (``rows=None``) because ``R = Cᵀ`` is derived.

    Args mirror :func:`repro.stream.adaptive.adaptive_cur_init` (columns
    only); ``s`` defaults to ``min(10·c, n)`` as in
    :func:`streaming_spsd_init`. Finish with
    :func:`adaptive_spsd_finalize`.
    """
    S1, S2, n_pad = _resolve_sketch_pair(
        key, n, c, s, sketch, osnap_p, dtype, sketches, panel
    )
    ctx = AdaptiveCURCtx(
        col_idx=jnp.full((c,), -1, jnp.int32),
        row_idx=jnp.zeros((0,), jnp.int32),  # tied operand: no row budget
        S_C=S1,
        S_R=S2,
        ScC=jnp.zeros((S1.s, c), dtype),
        slot_score=jnp.zeros((c,), jnp.float32),
        n_filled=jnp.zeros((), jnp.int32),
        slot_lo=jnp.zeros((), jnp.int32),
        energy=jnp.zeros((), jnp.float32),
        cols_seen=jnp.zeros((), jnp.float32),
        min_gain=jnp.asarray(min_gain, jnp.float32),
        swap_gain=jnp.asarray(jnp.inf if swap_gain is None else swap_gain, jnp.float32),
        n_evicted=jnp.zeros((), jnp.int32),
        rows=None,
        c_local=c,
        panel_cap=panel_cap if panel_cap is not None else max(1, c // 8),
        n=n,
        evict=swap_gain is not None,
    )
    tel, ops = _maybe_telemetry(telemetry, key, n, panel, ADAPTIVE_SPSD_OPS,
                                ADAPTIVE_SPSD_TEL_OPS)
    return PanelState(
        C=jnp.zeros((n, c), dtype),
        R=jnp.zeros((0, n_pad), dtype),  # tied operand: R = Cᵀ is derived
        M=jnp.zeros((S1.s, S2.s), dtype),
        offset=jnp.zeros((), jnp.int32),
        ctx=ctx,
        ops=ops,
        n=n,
        tel=tel,
    )


def adaptive_spsd_finalize(state: PanelState) -> SPSDResult:
    """Core solve on the admitted kernel columns + PSD projection.

    Unfilled slots (zero C columns) get their core rows *and* columns
    zeroed before the projection, so the floored solve's finite garbage
    cannot leak into ``C X Cᵀ`` (zeroing a symmetric row/col pair of a PSD
    matrix keeps it PSD, and zero C columns contribute nothing either way).
    """
    ctx = state.ctx
    with jax.named_scope(SCOPE_SOLVE):
        CS2 = ctx.S_R.apply(state.C).T  # (c, s)
        X = fast_gmr_core(ctx.ScC, state.M, CS2)  # ScC ≡ S₁ C by construction
        filled = ctx.col_idx >= 0
        X = jnp.where(filled[:, None] & filled[None, :], X, jnp.zeros((), X.dtype))
        X = psd_project(X)
    return SPSDResult(
        C=state.C,
        X=X,
        col_idx=ctx.col_idx,
        entries_observed=state.n * state.n,
    )


# Compiled at module scope (one trace per shape) and dispatched inside host
# spans; states are NOT donated — callers inspect them (col_idx, n_evicted,
# …) after finalizing.
streaming_spsd_finalize = spanned("stream/streaming_spsd/finalize")(
    jax.jit(streaming_spsd_finalize)
)
adaptive_spsd_finalize = spanned("stream/adaptive_spsd/finalize")(
    jax.jit(adaptive_spsd_finalize)
)
