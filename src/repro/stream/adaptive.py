"""Adaptive streaming CUR v2: column admission **and eviction**, plus
in-stream row admission.

Fixed-index streaming CUR must pick its ``col_idx``/``row_idx`` before the
pass — a single uniform pre-pass draw misses the heavy columns/rows of
spiked spectra. This module closes that gap (ROADMAP open items 1–2) with a
*residual-driven* replacement policy in the spirit of Wang & Zhang 2016's
adaptive sampling, computable **from the sketches alone** so the
single-pass contract is kept.

Column scoring (admission + eviction)
-------------------------------------
Scoring is fused with the panel sketch through the engine's
``sketch_panel`` hook: one pass computes ``sc_a = S_C A_L`` (shared with
the M update), the per-column energies, and for each panel column
``y = S_C a_j`` how much of it lies outside the span of the
already-admitted (sketched) columns ``S_C C``:

    ``score_j = ||y||² − ||Qᵀ y||²``

where ``Q`` is the Gram-whitened basis of the worker's admitted-slot
sketches (:func:`_whitened_basis` — unfilled slots' zero columns are
inert) — a λ-regularized projection residual, equal up to the tiny ridge
to the sketched least-squares residual ``||y − (S_C C)(S_C C)⁺ y||²``
(``S_C`` preserves these norms to (1±ε) by the subspace-embedding
property). On TPU the whole
triple runs as the fused ``repro.kernels.panel_score`` Pallas kernel (one
VMEM pass instead of three HBM round-trips); elsewhere the same math runs
as XLA ops on the structured sketch apply. A column is *admitted* into the
next free ``C`` slot when its score clears ``min_gain ×`` the mean column
energy — the larger of the running-stream mean and the current panel's mean,
so noise columns are never "eligible by default" on a cold start — with at
most ``panel_cap`` admissions per panel so the budget isn't exhausted early.

**Eviction** (v2): every admitted slot remembers the residual energy it
carried at admission time (``slot_score`` — its *retained energy*: how much
of the column lay outside the then-current basis). Once the budget is full,
an eligible candidate whose score clears ``swap_gain ×`` the weakest
admitted slot's retained energy *evicts* that slot: the victim's ``C``
column, ``ScC`` sketch, ``col_idx`` entry and score are overwritten in
place, inside the same jitted panel step. This is what admission-only
single-pass policies structurally cannot do: a heavy column arriving after
the budget fills (late-spike / drifting-spectrum streams) is no longer
lost. ``swap_gain=None`` (the default) disables eviction and reproduces the
v1 admission-only policy exactly.

Row admission (v2)
------------------
Rows are scored with the transposed sketch: each panel contributes
``A_L S_R[:, cols]ᵀ`` to a running accumulator ``row_sketch = A S_Rᵀ``
(m × s_r — the same order as the ``C`` factor), which after panel ``t``
holds every row's *exact* sketch over the columns seen so far. Rows are
scored by their residual against the span of the admitted rows' live
sketches and admitted into free ``R`` slots under the same
``min_gain``/``panel_cap`` knobs (``min_gain_rows``/``panel_cap_rows``).

Because ``R`` rows are gathered mid-stream, a row admitted at offset
``off`` has already missed columns ``[0, off)``. Those entries are
*backfilled* from the sketched reconstruction: with ``y`` the row's
accumulated sketch restricted to the missed prefix (kept per-slot in the
``backfill`` buffer at admission) and ``S`` the prefix window of ``S_R``,
the minimum-norm reconstruction ``x = Sᵀ(SSᵀ + λI)⁻¹ y`` is written into
``R[slot, :off]``. This needs writes *outside* the current panel window,
which is why :class:`~repro.stream.engine.PanelOps` grew the ``update_r``
hook. Row *eviction* is future work (backfill would have to be re-run for
the replacement row).

Bookkeeping is O(s_c·c + r·s_r) extra memory plus the O(m·s_r)
``row_sketch`` accumulator (adaptive rows only), and the scorers are one
(s_c × c_local) and one (s_r × r_local) QR per panel. Everything is
jit-compatible: admission/eviction use rank/slot scatters with
``mode='drop'`` so traced shapes stay static.

Distributed: each DP worker admits into its own ``c/W`` column-slot and
``r/W`` row-slot range (``prep_shard``/``bind_shard``), so merged states
never collide (disjoint-slot semantics); the merged result is a valid
admission outcome but — unlike the fixed-index paths — not bitwise equal to
single-host admission (workers score against their local basis only, and a
worker's backfill can only reconstruct the column range it has seen).
Because rows are global, two workers can admit the *same* heavy row; the
post-merge ``merge_state`` hook (:func:`_merge_state`) consolidates such
duplicates into the lowest-numbered slot (summing their disjoint-support
``R`` rows) and frees the rest, so duplicate admissions no longer waste
budget.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.gmr import fast_gmr_core
from ..core.sketching import GaussianSketch, draw_sketch
from ..kernels.ops import kernel_route_enabled, panel_score
from ..kernels.ops import panel_update as kernel_panel_update
from ..obs.spans import spanned
from ..obs.telemetry import adaptive_stream_telemetry, init_telemetry
from .engine import SCOPE_SOLVE, PanelOps, PanelState, fresh_pytree, padded_n, truncated_R

__all__ = [
    "AdaptiveCURCtx",
    "AdaptiveRowState",
    "ADAPTIVE_CUR_OPS",
    "ADAPTIVE_CUR_TEL_OPS",
    "adaptive_cur_init",
    "adaptive_cur_finalize",
    "allocate_shared_budget",
]


def allocate_shared_budget(
    scores: jax.Array, budget: int, *, floor: int = 0, cap: "int | None" = None
) -> jax.Array:
    """Split a shared rank ``budget`` across groups by greedy marginal gain.

    The streaming-CUR admission machinery above scores *columns* and spends
    a slot budget on the highest-residual ones; this is the same greedy at
    *group* granularity (the serving stack's groups are KV heads): each
    group ``g`` offers marginal gains ``scores[g, j]`` for its ``j``-th rank
    unit, and the budget is spent one unit at a time on the globally best
    remaining marginal — one fused :func:`jax.lax.top_k` over the flattened
    eligible window, exactly the admission kernel's selection primitive.

    Args:
        scores: ``(G, K)`` per-group marginal-gain ladders, **sorted
            descending along the last axis** (e.g. singular values or
            energies ``σ²``); with non-increasing ladders the global greedy
            is prefix-consistent, so the result is a valid per-group rank.
        budget: total units to allocate (static). Must satisfy
            ``budget >= G * floor``.
        floor: guaranteed minimum units per group (static).
        cap: per-group maximum (static; default ``K``). Units beyond ``cap``
            are never allocated even if budget remains.

    Returns:
        ``(G,)`` int32 allocation with ``floor <= out[g] <= cap`` and
        ``out.sum() <= budget``. Non-positive marginals are never bought
        (a group with a dead spectrum tail keeps its floor), so the sum can
        undershoot the budget.
    """
    G, K = scores.shape
    cap = K if cap is None else min(int(cap), K)
    if floor < 0 or cap < floor:
        raise ValueError(f"need 0 <= floor <= cap, got floor={floor} cap={cap}")
    extra = int(budget) - G * floor
    if extra < 0:
        raise ValueError(f"budget {budget} cannot cover floor {floor} x {G} groups")
    W = cap - floor
    if W == 0 or extra == 0:
        return jnp.full((G,), floor, jnp.int32)
    window = scores[:, floor:cap].reshape(-1)  # (G*W,) marginal gains
    k = min(extra, G * W)
    vals, idx = jax.lax.top_k(window, k)
    picks = (vals > 0).astype(jnp.int32)  # dead marginals are never bought
    counts = jnp.zeros((G,), jnp.int32).at[idx // W].add(picks)
    return floor + counts


@dataclasses.dataclass(frozen=True)
class AdaptiveRowState:
    """Adaptive row-admission state (present only when rows are adaptive).

    ``row_sketch`` accumulates ``A S_Rᵀ`` panel-by-panel, so row ``i``'s
    sketch is exact over the columns this worker has seen; ``backfill``
    holds, for slots admitted in the *current* panel, the pre-panel sketch
    of the admitted row (the sketched image of exactly the missed column
    prefix) consumed by the ``update_r`` backfill; ``admit_off`` records
    the admission offset per slot (−1 = unfilled) and doubles as the
    "freshly admitted this panel" marker; ``seen_lo`` is the global column
    offset where this worker's stream started (−1 until the first panel),
    bounding the backfillable range. ``gram`` accumulates the prefix Gram
    ``S_pre S_preᵀ`` of the sketch windows *before* the current panel —
    the backfill solve's left-hand side — at O(s_r²·L) per panel instead
    of an O(s_r²·n_pad) rebuild per admission; ``gram_pending`` holds the
    current panel's window Gram, folded into ``gram`` at the next panel so
    ``gram`` stays strictly pre-panel when ``_update_r`` consumes it.
    ``sr_dense`` is the dense ``S_R`` (s_r × n_pad), materialized **once at
    init** and threaded through the stream — the per-panel window Gram and
    the backfill's prefix map are dynamic slices of it, replacing the
    per-panel ``materialize()`` rebuilds that dominated the adaptive-row
    hot path (a full (s_r, L) scatter every panel plus an (s_r, n_pad)
    scatter per admission).
    """

    row_sketch: jax.Array  # (m, s_r) running A S_Rᵀ over seen columns
    backfill: jax.Array  # (r, s_r) pre-panel sketches of this panel's admits
    admit_off: jax.Array  # (r,) int32 admission offset per slot, −1 = unfilled
    gram: jax.Array  # (s_r, s_r) Gram of the S_R windows over [seen_lo, off)
    gram_pending: jax.Array  # (s_r, s_r) current panel's window Gram
    sr_dense: jax.Array  # (s_r, n_pad) dense S_R, precomputed once at init
    n_filled: jax.Array  # () int32 — next free row slot (worker-local range)
    slot_lo: jax.Array  # () int32 — first row slot this worker may fill
    min_gain: jax.Array  # () f32 — row admission threshold multiplier
    seen_lo: jax.Array  # () int32 — first column offset this worker saw, −1 = none
    r_local: int  # static: number of row slots this worker owns
    panel_cap: int  # static: max row admissions per panel


jax.tree_util.register_dataclass(
    AdaptiveRowState,
    data_fields=[
        "row_sketch", "backfill", "admit_off", "gram", "gram_pending",
        "sr_dense", "n_filled", "slot_lo", "min_gain", "seen_lo",
    ],
    meta_fields=["r_local", "panel_cap"],
)


@dataclasses.dataclass(frozen=True)
class AdaptiveCURCtx:
    """Admission/eviction state threaded through the panel stream."""

    col_idx: jax.Array  # (c,) int32, −1 = unfilled slot
    row_idx: jax.Array  # (r,) int32, −1 = unfilled (fixed pre-pass when rows=None)
    S_C: object  # (s_c, m) column-sliceable core sketch
    S_R: object  # (s_r, n_pad)
    ScC: jax.Array  # (s_c, c) — sketches of the admitted columns, by slot
    slot_score: jax.Array  # (c,) f32 — residual energy at admission (retained energy)
    n_filled: jax.Array  # () int32 — next free slot (within this worker's range)
    slot_lo: jax.Array  # () int32 — first slot this worker may fill
    energy: jax.Array  # () f32 — running Σ ||S_C a_j||² over seen columns
    cols_seen: jax.Array  # () f32 — true (unpadded) columns seen
    min_gain: jax.Array  # () f32 — admission threshold multiplier
    swap_gain: jax.Array  # () f32 — eviction threshold multiplier (+inf = off)
    n_evicted: jax.Array  # () int32 — total evictions performed
    rows: Optional[AdaptiveRowState]  # adaptive row admission state, or None
    c_local: int  # static: number of column slots this worker owns
    panel_cap: int  # static: max column admissions per panel
    n: int  # static: true column count of the stream
    # static: eviction enabled (swap_gain was given)? Statically known so the
    # admission-only compile path can use one vectorized scatter per panel
    # instead of the sequential admit-or-evict chain.
    evict: bool = False


jax.tree_util.register_dataclass(
    AdaptiveCURCtx,
    data_fields=[
        "col_idx", "row_idx", "S_C", "S_R", "ScC", "slot_score",
        "n_filled", "slot_lo", "energy", "cols_seen", "min_gain",
        "swap_gain", "n_evicted", "rows",
    ],
    meta_fields=["c_local", "panel_cap", "n", "evict"],
)


def _core_sketches(ctx):
    """Engine hook: the (S_C, S_R) pair driving the shared M update."""
    return ctx.S_C, ctx.S_R


def _whitened_basis(mat: jax.Array) -> jax.Array:
    """Gram-whitened basis ``Q = mat·L⁻ᵀ`` with ``LLᵀ = matᵀmat + λI``.

    ``‖Qᵀy‖² = yᵀ mat (matᵀmat + λI)⁻¹ matᵀ y`` is the (λ-regularized)
    energy of ``y`` inside ``span(mat)``, so ``‖y‖² − ‖Qᵀy‖²`` is the
    projection residual the admission policy scores with. Two properties
    make this the right streaming primitive:

    * all-zero columns of ``mat`` (unfilled slots — the zero-suffixed
      prefix invariant) produce all-zero columns of ``Q``, contributing
      nothing: no fill-count masking needed, cold start included
      (``mat = 0`` ⇒ residual = energy exactly);
    * the factorization is a ``c×c`` Gram + Cholesky + triangular solve —
      O(s_c·c²) like QR but without the tall-matrix Householder pass,
      which dominated the per-panel serial latency of the scoring step.

    ``λ = c·eps·tr(G) + tiny`` is sized so the factorization **cannot** go
    numerically indefinite — the fp32 rounding perturbation of ``G`` is
    bounded by ``eps·tr(G)`` and LAPACK's potrf needs ≈``c×`` that in
    min-eigenvalue headroom — so near-duplicate admitted columns (a true
    rank-deficient Gram) still produce a finite, NaN-free scorer: the
    no-NaN guarantee the floored-QR path of
    :func:`repro.core.gmr._solve_least_squares` gave, restated for the
    Cholesky route. The ridge stays O(1e-6) relative, far below the
    subspace-embedding noise the scores already carry, and the regularized
    projection energy is ≤ the exact one, so residuals stay ≥ 0.
    """
    dt = jnp.float32
    M = mat.astype(dt)
    G = M.T @ M
    lam = G.shape[0] * jnp.finfo(dt).eps * jnp.trace(G) + jnp.finfo(dt).tiny
    L = jnp.linalg.cholesky(G + lam * jnp.eye(G.shape[0], dtype=dt))
    return jax.scipy.linalg.solve_triangular(L, M.T, lower=True).T


def _admitted_basis(ctx: AdaptiveCURCtx) -> jax.Array:
    """Whitened basis of this worker's admitted-slot sketches (per panel —
    every admission changes the span the next panel scores against)."""
    ScC_local = jax.lax.dynamic_slice_in_dim(ctx.ScC, ctx.slot_lo, ctx.c_local, axis=1)
    return _whitened_basis(ScC_local)


def _score_columns(Qm: jax.Array, sc_a: jax.Array) -> tuple:
    """Per-column ``(resid2, energy)`` of the panel sketches against the
    whitened admitted basis ``Qm`` — the XLA half of the scoring triple."""
    y = sc_a.astype(jnp.float32)
    energy = jnp.sum(y * y, axis=0)  # (L,)
    t = Qm.T @ y  # (c_local, L)
    resid2 = jnp.maximum(energy - jnp.sum(t * t, axis=0), 0.0)
    return resid2, energy


def _sketch_panel(ctx: AdaptiveCURCtx, A_L, off):
    """Engine ``sketch_panel`` hook: panel sketch + column scores, fused.

    Computes ``sc_a = S_C A_L`` together with the per-column energies and
    the residual energies against the worker's admitted basis. On TPU with a
    dense ``S_C`` the triple is one VMEM pass of the
    :func:`repro.kernels.ops.panel_score` Pallas kernel (each ``A_L`` tile
    read once, ``sc_a`` never round-trips through HBM); elsewhere the same
    math runs as XLA ops over the structured sketch apply. The whitening of
    the (s_c × c_local) admitted-sketch slice happens outside the kernel —
    it is O(s_c·c²), independent of the panel.
    """
    Qm = _admitted_basis(ctx)
    if jax.default_backend() == "tpu" and isinstance(ctx.S_C, GaussianSketch):
        sc_a, resid2, energy = panel_score(ctx.S_C.mat[:, : A_L.shape[0]], A_L, Qm)
    else:
        sc_a = ctx.S_C.apply(A_L)  # (s_c, L)
        resid2, energy = _score_columns(Qm, sc_a)
    return ctx, sc_a, (resid2, energy)


# ---------------------------------------------------------------------------
# column admission + eviction
# ---------------------------------------------------------------------------


def _admit_or_evict_columns(ctx: AdaptiveCURCtx, C, block, col0, sc_a, resid2, eligible, off):
    """Greedy per-candidate pass over the top-``panel_cap`` residual columns:
    admit into the next free slot while the worker's range has one, else
    evict the weakest admitted slot when the candidate clears ``swap_gain ×``
    its retained-energy score. With eviction enabled the pass is sequential
    but statically unrolled (``panel_cap`` scatter chains — each decision
    changes the slot table the next one sees); admission-only
    (``ctx.evict`` False) is order-independent within a panel, so it
    compiles to **one** batched scatter per buffer, identical outcome. All
    shapes stay static via ``mode='drop'`` OOB scatters.

    The panel's columns live at ``block[:, col0 + j]`` (``col0`` may be
    traced) — the per-panel driver passes ``(A_L, 0)``, the fused scan body
    the un-copied chunk operand, so candidate gathers never materialize the
    (m × L) panel slice."""
    L = sc_a.shape[1]
    c_total = C.shape[1]
    K = min(ctx.panel_cap, L)

    # top-K eligible residual columns, best first (resid2 ≥ 0 > −1 mask)
    cand_res, cand = jax.lax.top_k(jnp.where(eligible, resid2, -1.0), K)
    cand_ok = jnp.take(eligible, cand)
    cand_A = jnp.take(block, col0 + cand, axis=1)  # (m, K)
    cand_sc = jnp.take(sc_a, cand, axis=1)  # (s_c, K)

    if not ctx.evict:
        # Vectorized admission: candidate k (already best-first) lands in
        # slot n_filled + (its rank among the eligible), budget permitting.
        ranks = jnp.cumsum(cand_ok.astype(jnp.int32)) - 1
        free = ctx.slot_lo + ctx.c_local - ctx.n_filled
        admit = cand_ok & (ranks < free)
        slots = jnp.where(admit, ctx.n_filled + ranks, c_total)  # OOB → drop
        C = C.at[:, slots].set(cand_A.astype(C.dtype), mode="drop")
        ctx = dataclasses.replace(
            ctx,
            ScC=ctx.ScC.at[:, slots].set(cand_sc.astype(ctx.ScC.dtype), mode="drop"),
            col_idx=ctx.col_idx.at[slots].set((off + cand).astype(jnp.int32), mode="drop"),
            slot_score=ctx.slot_score.at[slots].set(
                cand_res.astype(ctx.slot_score.dtype), mode="drop"
            ),
            n_filled=ctx.n_filled + jnp.sum(admit).astype(jnp.int32),
        )
        return ctx, C

    slot_ids = jnp.arange(c_total)
    in_range = (slot_ids >= ctx.slot_lo) & (slot_ids < ctx.slot_lo + ctx.c_local)

    def step(k, carry):
        C, ScC, col_idx, slot_score, n_filled, n_evicted = carry
        res, ok = cand_res[k], cand_ok[k]
        has_free = n_filled < ctx.slot_lo + ctx.c_local
        # weakest admitted slot of this worker's range (+inf elsewhere, so an
        # all-masked argmin picks slot 0 but swap_ok is then provably False)
        scores = jnp.where(in_range & (col_idx >= 0), slot_score, jnp.inf)
        victim = jnp.argmin(scores).astype(jnp.int32)
        admit = ok & has_free
        swap = ok & (~has_free) & (res > ctx.swap_gain * scores[victim])
        # slot = free slot | victim | c_total (OOB → scatter dropped)
        slot = jnp.where(admit, n_filled, jnp.where(swap, victim, c_total))
        C = C.at[:, slot].set(cand_A[:, k].astype(C.dtype), mode="drop")
        ScC = ScC.at[:, slot].set(cand_sc[:, k].astype(ScC.dtype), mode="drop")
        col_idx = col_idx.at[slot].set((off + cand[k]).astype(jnp.int32), mode="drop")
        slot_score = slot_score.at[slot].set(res.astype(slot_score.dtype), mode="drop")
        return (
            C, ScC, col_idx, slot_score,
            n_filled + admit.astype(jnp.int32),
            n_evicted + swap.astype(jnp.int32),
        )

    # Sequential because each decision changes the slot table the next one
    # sees; K = panel_cap is a small static constant, so the loop is
    # UNROLLED into the surrounding scan body (no inner fori_loop) and XLA
    # fuses the K scatter chains.
    carry = (C, ctx.ScC, ctx.col_idx, ctx.slot_score, ctx.n_filled, ctx.n_evicted)
    for k in range(K):
        carry = step(k, carry)
    C, ScC, col_idx, slot_score, n_filled, n_evicted = carry
    ctx = dataclasses.replace(
        ctx, ScC=ScC, col_idx=col_idx, slot_score=slot_score,
        n_filled=n_filled, n_evicted=n_evicted,
    )
    return ctx, C


def _admit_rows(ctx: AdaptiveCURCtx, A_L, off):
    """Score every matrix row's accumulated ``A S_Rᵀ`` sketch against the
    admitted rows' live sketches and admit the top residual rows into free
    slots of this worker's row range. Returns the updated ctx (row_idx +
    AdaptiveRowState); the R-side writes happen in ``_update_r``."""
    rows = ctx.rows
    L = A_L.shape[1]
    m = A_L.shape[0]
    r_total = ctx.row_idx.shape[0]

    window = ctx.S_R.cols(off, L)
    a_sr = window.apply_t(A_L)  # (m, s_r) this panel's row sketches
    prev = rows.row_sketch
    row_sketch = prev + a_sr.astype(prev.dtype)
    seen_lo = jnp.where(rows.seen_lo < 0, off.astype(jnp.int32), rows.seen_lo)
    # Rotate the prefix Gram: fold the previous panel's window in, stash the
    # current one — ``gram`` must cover exactly [seen_lo, off) when the
    # update_r backfill consumes it later this panel. The window is a
    # dynamic slice of the init-time dense S_R — no per-panel scatter.
    Sw = jax.lax.dynamic_slice_in_dim(rows.sr_dense, off, L, axis=1)  # (s_r, L)
    gram = rows.gram + rows.gram_pending
    gram_pending = Sw @ Sw.T

    # Residual of every row's sketch against the admitted-row span, with the
    # basis gathered *live* from the accumulator (always-fresh sketches).
    # Like the column path, the basis is restricted to this worker's slot
    # range and projected through a zero-masked orthonormal basis: the range
    # is filled as a zero-suffixed prefix, so ``Q[:, :filled]`` spans it
    # exactly (a full-table gather would interleave other ranges' leading
    # zero columns and break that invariant under sharding).
    row_idx_local = jax.lax.dynamic_slice_in_dim(
        ctx.row_idx, rows.slot_lo, rows.r_local, axis=0
    )
    filled = row_idx_local >= 0
    basis = jnp.take(row_sketch, jnp.clip(row_idx_local, 0), axis=0)  # (r_local, s_r)
    basis = jnp.where(filled[:, None], basis, jnp.zeros((), basis.dtype))
    Qm = _whitened_basis(basis.T)  # (s_r, r_local); unfilled rows self-mask
    t = row_sketch.astype(jnp.float32) @ Qm  # (m, r_local)
    row_energy = jnp.sum(row_sketch * row_sketch, axis=1)  # (m,)
    resid2 = jnp.maximum(row_energy - jnp.sum(t * t, axis=1), 0.0)  # (m,)

    # Threshold: min_gain_rows × the current mean per-row sketch energy.
    # Already-admitted rows are excluded outright (their residual is fp
    # noise, but −1-free bookkeeping is cheaper than trusting that).
    taken = jnp.zeros((m,), bool).at[jnp.where(filled, row_idx_local, m)].set(
        True, mode="drop"
    )
    mean_energy = jnp.sum(row_energy) / m
    eligible = (resid2 > rows.min_gain * mean_energy) & ~taken

    K = min(rows.panel_cap, m)
    _, top = jax.lax.top_k(jnp.where(eligible, resid2, -1.0), K)  # best first
    free = rows.slot_lo + rows.r_local - rows.n_filled
    cap = jnp.minimum(jnp.minimum(free, jnp.sum(eligible)), rows.panel_cap)
    slots = jnp.where(jnp.arange(K) < cap, rows.n_filled + jnp.arange(K), r_total)

    row_idx = ctx.row_idx.at[slots].set(top.astype(jnp.int32), mode="drop")
    admit_off = rows.admit_off.at[slots].set(off.astype(jnp.int32), mode="drop")
    # pre-panel sketches of the fresh admits = sketched image of exactly the
    # missed prefix [seen_lo, off) — the update_r backfill's right-hand side
    backfill = jnp.zeros_like(rows.backfill).at[slots].set(
        jnp.take(prev, top, axis=0).astype(rows.backfill.dtype), mode="drop"
    )
    rows = dataclasses.replace(
        rows,
        row_sketch=row_sketch,
        backfill=backfill,
        admit_off=admit_off,
        gram=gram,
        gram_pending=gram_pending,
        n_filled=rows.n_filled + cap.astype(jnp.int32),
        seen_lo=seen_lo,
    )
    return dataclasses.replace(ctx, row_idx=row_idx, rows=rows)


def _score_and_admit(ctx: AdaptiveCURCtx, C, block, col0, sc_a, resid2, col_energy, off):
    """Shared per-panel column policy: threshold, admit/evict, fold the
    energy bookkeeping — the core of ``_update_c`` and ``_fused_step``.

    Admission threshold: min_gain × the mean column energy, where the mean
    is the larger of the running stream mean and the current panel's mean
    (over true, unpadded columns). The panel term matters on each worker's
    first panels — with a 0 running mean every noise column would otherwise
    be "eligible" and greedily exhaust the slot budget before any heavy
    column arrives.
    """
    L = sc_a.shape[1]
    true_cols = jnp.clip(ctx.n - off, 1, L).astype(jnp.float32)
    panel_mean = jnp.sum(col_energy) / true_cols
    run_mean = ctx.energy / jnp.maximum(ctx.cols_seen, 1.0)
    thresh = ctx.min_gain * jnp.maximum(run_mean, panel_mean)
    eligible = resid2 > thresh  # strict: zero-padded tail columns never pass

    ctx, C = _admit_or_evict_columns(ctx, C, block, col0, sc_a, resid2, eligible, off)
    ctx = dataclasses.replace(
        ctx,
        energy=ctx.energy + jnp.sum(col_energy),
        cols_seen=ctx.cols_seen + jnp.clip(ctx.n - off, 0, L).astype(ctx.cols_seen.dtype),
    )
    return ctx, C


def _update_c(ctx: AdaptiveCURCtx, C, A_L, sc_a, off, scores):
    """Engine hook: admit/evict this panel's columns within this worker's
    slot range using the scores pre-computed by the fused ``sketch_panel``
    pass; when rows are adaptive, fold the panel into the row accumulator
    and admit rows too."""
    resid2, col_energy = scores  # (L,), (L,) — see _sketch_panel
    ctx, C = _score_and_admit(ctx, C, A_L, 0, sc_a, resid2, col_energy, off)
    if ctx.rows is not None:
        ctx = _admit_rows(ctx, A_L, off)
    return ctx, C


def _update_r(ctx: AdaptiveCURCtx, R, A_L, off):
    """Engine ``update_r`` hook: write the panel block for the current
    (post-admission) ``row_idx`` — unfilled slots stay zero — then backfill
    the missed column prefix of any row admitted *this* panel from its
    sketched reconstruction ``x = S_preᵀ (S_pre S_preᵀ + λI)⁻¹ y``, where
    ``S_pre`` is ``S_R`` masked to the columns this worker has already
    consumed and ``y`` the per-slot pre-panel sketch kept in
    ``rows.backfill``."""
    blk = jnp.take(A_L, jnp.clip(ctx.row_idx, 0), axis=0)
    blk = jnp.where((ctx.row_idx >= 0)[:, None], blk, jnp.zeros((), blk.dtype))
    R = jax.lax.dynamic_update_slice_in_dim(R, blk.astype(R.dtype), off, axis=1)
    rows = ctx.rows
    if rows is None:
        return R

    fresh = (rows.admit_off == off) & (ctx.row_idx >= 0)  # admitted this panel

    def do_backfill(R):
        # G = S_pre S_preᵀ is pre-accumulated window-by-window (rows.gram);
        # only the map back to columns needs the materialized prefix window.
        G = rows.gram  # (s_r, s_r) PSD Gram of the prefix [seen_lo, off)
        lam = 1e-6 * jnp.trace(G) / G.shape[0] + jnp.finfo(jnp.float32).tiny
        Z = jnp.linalg.solve(G + lam * jnp.eye(G.shape[0], dtype=G.dtype),
                             rows.backfill.T.astype(jnp.float32))  # (s_r, r)
        col_ids = jnp.arange(R.shape[1])
        mask = (col_ids >= rows.seen_lo) & (col_ids < off)  # backfillable prefix
        Sm = rows.sr_dense * mask[None, :]  # dense S_R precomputed at init
        Xb = (Sm.T @ Z).T  # (r, n_pad) min-norm row reconstructions
        keep = fresh[:, None] & mask[None, :]
        return jnp.where(keep, Xb.astype(R.dtype), R)

    return jax.lax.cond(jnp.any(fresh), do_backfill, lambda R: R, R)


# ---------------------------------------------------------------------------
# fused-scan hooks (Route A) and the panel-update megakernel (Route B)
# ---------------------------------------------------------------------------


def _chunk_fold(ctx: AdaptiveCURCtx, C, R, block, bcol0, start, width):
    """Fused-scan hook: the whole chunk's fixed-row ``R`` stripe in one pass.

    Adaptive *columns* are inherently per-panel (each admission changes the
    basis the next panel scores against) and stay in ``_fused_step``; the
    fixed ``row_idx`` side is panel-invariant, so the chunk's row stripe is
    gathered once — bitwise the values the per-panel ``_update_r`` copies.
    Adaptive rows never reach here (``_supports_fused`` keeps them on the
    legacy body).
    """
    stripe = jnp.take(block, jnp.clip(ctx.row_idx, 0), axis=0)
    stripe = jnp.where((ctx.row_idx >= 0)[:, None], stripe, jnp.zeros((), stripe.dtype))
    stripe = jax.lax.dynamic_slice_in_dim(stripe, bcol0, width, axis=1)
    R = jax.lax.dynamic_update_slice_in_dim(R, stripe.astype(R.dtype), start, axis=1)
    return ctx, C, R


def _fused_step(ctx: AdaptiveCURCtx, C, block, bcol, sc_a, off):
    """Engine ``fused_step`` hook: score the pre-sliced panel sketch against
    the current admitted basis and run the admission/eviction policy,
    gathering candidate columns straight from the un-copied chunk operand
    (``block[:, bcol + j]``) — the per-panel (m × L) ``A_L`` slice the fused
    body exists to remove. Decision-for-decision (and bitwise, for
    column-independent sketch families) equal to the per-panel oracle."""
    Qm = _admitted_basis(ctx)
    resid2, col_energy = _score_columns(Qm, sc_a)
    ctx, C = _score_and_admit(ctx, C, block, bcol, sc_a, resid2, col_energy, off)
    return ctx, C, (resid2, col_energy)


def _kernel_ok(ctx: AdaptiveCURCtx) -> bool:
    """Static (trace-time) gate for the Route-B megakernel: TPU backend (or
    the forced test route), admission-only columns, fixed rows, and dense
    gaussian core sketches on both sides (the kernel contracts ``S_C.mat``
    and a dynamic window of ``S_R.mat`` directly)."""
    return (
        kernel_route_enabled()
        and not ctx.evict
        and ctx.rows is None
        and isinstance(ctx.S_C, GaussianSketch)
        and isinstance(ctx.S_R, GaussianSketch)
    )


def _supports_fused(ctx: AdaptiveCURCtx) -> bool:
    """Route-A gate: adaptive rows are per-panel by construction (the row
    accumulator + backfill chain can't be hoisted), and when the megakernel
    route is live the scan keeps the legacy per-panel body so Route B fires
    every panel instead."""
    return ctx.rows is None and not _kernel_ok(ctx)


def _panel_kernel(ctx: AdaptiveCURCtx, C, M, A_L, off):
    """Engine ``panel_kernel`` hook (Route B): one fused Pallas launch for
    the sketch, scoring, admission decision, ``C`` scatter and ``M`` fold
    (:func:`repro.kernels.ops.panel_update` — C/M aliased in place, ``sc_a``
    never round-trips HBM). Returns ``None`` at trace time when the config
    is outside the kernel's contract; the engine then runs the standard
    path. The whitening and the ctx slot-table scatters stay outside — they
    are O(s_c·c²) / O(s_c·L), independent of ``m``."""
    if not _kernel_ok(ctx):
        return None
    L = A_L.shape[1]
    c_total = C.shape[1]
    Qm = _admitted_basis(ctx)
    # S_R window for the M fold: M += sc_a @ S_R[:, off:off+L]ᵀ
    srt = jax.lax.dynamic_slice_in_dim(ctx.S_R.mat, off, L, axis=1).T  # (L, s_r)
    run_mean = ctx.energy / jnp.maximum(ctx.cols_seen, 1.0)
    true_cols = jnp.clip(ctx.n - off, 1, L).astype(jnp.float32)
    free = ctx.slot_lo + ctx.c_local - ctx.n_filled
    C, M, sc_a, resid2, energy, slots = kernel_panel_update(
        ctx.S_C.mat[:, : A_L.shape[0]], A_L, srt, Qm, C, M,
        min_gain=ctx.min_gain, run_mean=run_mean, true_cols=true_cols,
        n_filled=ctx.n_filled, free=free, panel_cap=ctx.panel_cap,
    )
    # slot-table bookkeeping: slots[j] is the C slot column j was admitted
    # into, or the c_total sentinel (OOB → scatter dropped)
    ctx = dataclasses.replace(
        ctx,
        ScC=ctx.ScC.at[:, slots].set(sc_a.astype(ctx.ScC.dtype), mode="drop"),
        col_idx=ctx.col_idx.at[slots].set(
            (off + jnp.arange(L)).astype(jnp.int32), mode="drop"
        ),
        slot_score=ctx.slot_score.at[slots].set(
            resid2.astype(ctx.slot_score.dtype), mode="drop"
        ),
        n_filled=ctx.n_filled + jnp.sum(slots < c_total).astype(jnp.int32),
        energy=ctx.energy + jnp.sum(energy),
        cols_seen=ctx.cols_seen + jnp.clip(ctx.n - off, 0, L).astype(ctx.cols_seen.dtype),
    )
    return ctx, C, M, sc_a, (resid2, energy)


# ---------------------------------------------------------------------------
# distributed hooks (disjoint-slot semantics; see repro.stream.distributed)
# ---------------------------------------------------------------------------


def _prep_shard(ctx: AdaptiveCURCtx, num_workers: int) -> AdaptiveCURCtx:
    """Static per-run shard prep: split the column (and row) slot budgets
    into ``/W`` per-worker ranges; raises when a budget doesn't divide."""
    if ctx.c_local % num_workers:
        raise ValueError(
            f"column budget c={ctx.c_local} must divide across {num_workers} workers"
        )
    rows = ctx.rows
    if rows is not None:
        if rows.r_local % num_workers:
            raise ValueError(
                f"row budget r={rows.r_local} must divide across {num_workers} workers"
            )
        rows = dataclasses.replace(rows, r_local=rows.r_local // num_workers)
    return dataclasses.replace(ctx, c_local=ctx.c_local // num_workers, rows=rows)


def _bind_shard(ctx: AdaptiveCURCtx, w) -> AdaptiveCURCtx:
    """Bind worker ``w`` (may be traced) to its disjoint slot ranges."""
    lo = (w * ctx.c_local).astype(jnp.int32)
    rows = ctx.rows
    if rows is not None:
        lo_r = (w * rows.r_local).astype(jnp.int32)
        rows = dataclasses.replace(rows, slot_lo=lo_r, n_filled=lo_r)
    return dataclasses.replace(ctx, slot_lo=lo, n_filled=lo, rows=rows)


def _merge_ctx(ctxs):
    """In-process merge of per-worker ctxs: slot ranges are disjoint, so the
    per-slot state sums exactly; ``row_sketch`` sums to the full-stream
    ``A S_Rᵀ`` because workers consumed disjoint column ranges."""
    base = ctxs[0]
    rows = None
    if base.rows is not None:
        rows = dataclasses.replace(
            base.rows,
            row_sketch=sum((c.rows.row_sketch for c in ctxs[1:]), base.rows.row_sketch),
            backfill=jnp.zeros_like(base.rows.backfill),  # per-panel scratch
            gram=jnp.zeros_like(base.rows.gram),  # worker-local prefix state
            gram_pending=jnp.zeros_like(base.rows.gram_pending),
            admit_off=jnp.max(jnp.stack([c.rows.admit_off for c in ctxs]), axis=0),
            n_filled=sum((c.rows.n_filled - c.rows.slot_lo) for c in ctxs).astype(jnp.int32),
            slot_lo=jnp.zeros((), jnp.int32),
            seen_lo=jnp.zeros((), jnp.int32),
            r_local=base.row_idx.shape[0],
        )
    return dataclasses.replace(
        base,
        ScC=sum((c.ScC for c in ctxs[1:]), base.ScC),  # slot ranges are disjoint
        col_idx=jnp.max(jnp.stack([c.col_idx for c in ctxs]), axis=0),  # −1 = unfilled
        row_idx=jnp.max(jnp.stack([c.row_idx for c in ctxs]), axis=0),
        slot_score=sum((c.slot_score for c in ctxs[1:]), base.slot_score),
        n_filled=sum((c.n_filled - c.slot_lo) for c in ctxs).astype(jnp.int32),
        slot_lo=jnp.zeros((), jnp.int32),
        energy=sum(c.energy for c in ctxs),
        cols_seen=sum(c.cols_seen for c in ctxs),
        n_evicted=sum(c.n_evicted for c in ctxs).astype(jnp.int32),
        rows=rows,
        c_local=base.col_idx.shape[0],
    )


def _merge_state(state: PanelState) -> PanelState:
    """Post-merge cross-worker **row dedup** (engine ``merge_state`` hook).

    Matrix rows are global — unlike the disjoint per-worker column ranges —
    so two workers can admit the *same* heavy row into different slots, and
    the merged state then spends two budget slots on one row (the
    rank-deficient core solve absorbs the duplication, but the budget is
    wasted). Reconciliation, entirely in the merged state:

    * every filled slot's **canonical** slot is the lowest-numbered slot
      holding the same row index;
    * each duplicate slot's ``R`` row is **added into** its canonical slot —
      workers consumed disjoint column ranges (and backfill only writes
      inside a worker's seen range), so the duplicates' column supports are
      disjoint and the sum is the union of what every admitting worker saw
      of that row;
    * the duplicate slots themselves are then zeroed and freed
      (``row_idx``/``admit_off`` → −1, ``n_filled`` decremented), restoring
      the unfilled-slot invariants the finalizer masks on.

    Canonical-slot selection is deterministic, so the scan and per-panel
    sharded drivers stay decision-for-decision equal. No-op when rows are
    fixed (duplicates are then the caller's explicit choice) and on
    single-host streams (in-stream admission already excludes admitted
    rows, so duplicates cannot arise without a merge).
    """
    ctx = state.ctx
    if ctx.rows is None:
        return state
    idx = ctx.row_idx
    r = idx.shape[0]
    filled = idx >= 0
    same = (idx[:, None] == idx[None, :]) & filled[:, None] & filled[None, :]
    canon = jnp.argmax(same, axis=0)  # lowest slot holding the same row
    dup = filled & (canon != jnp.arange(r))
    # T[i, j] = 1 ⇔ slot j's content lands in slot i. Duplicate slots are
    # never anyone's canonical slot, so T @ R consolidates *and* zeroes
    # them in one pass.
    T = (jnp.arange(r)[:, None] == jnp.where(filled, canon, r)[None, :])
    R = T.astype(state.R.dtype) @ state.R
    rows = ctx.rows
    # canonical slots keep the group's earliest admission offset
    admit_grp = jnp.min(
        jnp.where(same, rows.admit_off[None, :], jnp.iinfo(jnp.int32).max), axis=1
    )
    admit_off = jnp.where(dup, -1, jnp.where(filled, admit_grp, rows.admit_off))
    rows = dataclasses.replace(
        rows,
        admit_off=admit_off.astype(jnp.int32),
        n_filled=rows.n_filled - jnp.sum(dup).astype(jnp.int32),
    )
    ctx = dataclasses.replace(
        ctx, row_idx=jnp.where(dup, -1, idx).astype(jnp.int32), rows=rows
    )
    return dataclasses.replace(state, R=R, ctx=ctx)


def _collective_ctx(ctx: AdaptiveCURCtx, axis) -> AdaptiveCURCtx:
    """shard_map all-reduce mirror of :func:`_merge_ctx` (psum for the
    disjoint per-slot state, pmax for −1-sentinel index maps)."""
    rows = ctx.rows
    if rows is not None:
        rows = dataclasses.replace(
            rows,
            row_sketch=jax.lax.psum(rows.row_sketch, axis),
            backfill=jnp.zeros_like(rows.backfill),
            gram=jnp.zeros_like(rows.gram),  # worker-local prefix state
            gram_pending=jnp.zeros_like(rows.gram_pending),
            admit_off=jax.lax.pmax(rows.admit_off, axis),
            n_filled=jax.lax.psum(rows.n_filled - rows.slot_lo, axis).astype(jnp.int32),
            slot_lo=jnp.zeros((), jnp.int32),
            seen_lo=jnp.zeros((), jnp.int32),
        )
    return dataclasses.replace(
        ctx,
        ScC=jax.lax.psum(ctx.ScC, axis),
        col_idx=jax.lax.pmax(ctx.col_idx, axis),
        row_idx=jax.lax.pmax(ctx.row_idx, axis),
        slot_score=jax.lax.psum(ctx.slot_score, axis),
        n_filled=jax.lax.psum(ctx.n_filled - ctx.slot_lo, axis).astype(jnp.int32),
        slot_lo=jnp.zeros((), jnp.int32),
        energy=jax.lax.psum(ctx.energy, axis),
        cols_seen=jax.lax.psum(ctx.cols_seen, axis),
        n_evicted=jax.lax.psum(ctx.n_evicted, axis).astype(jnp.int32),
        rows=rows,
    )


ADAPTIVE_CUR_OPS = PanelOps(
    name="adaptive_cur",
    core_sketches=_core_sketches,
    sketch_panel=_sketch_panel,
    update_c=_update_c,
    update_r=_update_r,
    prep_shard=_prep_shard,
    bind_shard=_bind_shard,
    merge_ctx=_merge_ctx,
    collective_ctx=_collective_ctx,
    merge_state=_merge_state,
    chunk_fold=_chunk_fold,
    fused_step=_fused_step,
    supports_fused=_supports_fused,
    panel_kernel=_panel_kernel,
)

# Telemetered twin of ADAPTIVE_CUR_OPS — same hooks plus the per-panel
# diagnostics fold. A module-level instance (not a per-init replace) so every
# telemetered init shares one ops object and the engine's jit caches stay hot.
ADAPTIVE_CUR_TEL_OPS = dataclasses.replace(
    ADAPTIVE_CUR_OPS, telemetry=adaptive_stream_telemetry
)


@spanned("stream/adaptive_cur/init")
def adaptive_cur_init(
    key,
    m: int,
    n: int,
    c: int,
    row_idx: Optional[jax.Array] = None,
    *,
    r: Optional[int] = None,
    s_c: Optional[int] = None,
    s_r: Optional[int] = None,
    eps: float = 0.05,
    rho_est: float = 2.0,
    sketch: str = "countsketch",
    osnap_p: int = 2,
    min_gain: float = 2.0,
    panel_cap: Optional[int] = None,
    swap_gain: Optional[float] = None,
    min_gain_rows: Optional[float] = None,
    panel_cap_rows: Optional[int] = None,
    dtype=jnp.float32,
    sketches=None,
    panel: Optional[int] = None,
    telemetry: bool = False,
) -> PanelState:
    """Allocate an adaptive streaming-CUR state with an empty column budget.

    Args:
        key: PRNG key for the core sketches (ignored when ``sketches`` given).
        m, n: stream shape — ``A`` is (m, n), arriving as column panels.
        c: column budget; slots are filled in-stream by residual admission.
        row_idx: fixed pre-pass row indices (r,). Pass ``None`` together with
            ``r=`` to enable adaptive in-stream **row admission** instead.
        r: row budget when ``row_idx is None`` (adaptive rows).
        s_c, s_r: core sketch sizes; default to the Table-2
            :func:`repro.cur.cur.cur_sketch_sizes` for ``(c, r, eps, rho_est)``.
        eps, rho_est: Table-2 sketch-size parameters.
        sketch: column-sliceable core sketch family
            (``countsketch`` / ``osnap`` / ``gaussian``).
        osnap_p: nonzeros per column for the OSNAP family.
        min_gain: data-relative column admission threshold — a column must
            carry ``min_gain ×`` the mean column energy *outside* the current
            admitted basis.
        panel_cap: max column admissions (or evictions) per panel; defaults
            to ``max(1, c // 8)`` so the budget survives past the first panels.
        swap_gain: **eviction** threshold — once the budget is full, an
            eligible candidate evicts the weakest admitted slot when its
            residual clears ``swap_gain ×`` that slot's retained-energy
            score. ``None`` (default) disables eviction (v1 admission-only).
        min_gain_rows: row admission threshold (default: ``min_gain``) — a
            row must carry ``min_gain_rows ×`` the mean per-row sketch energy
            outside the admitted row span.
        panel_cap_rows: max row admissions per panel (default ``max(1, r//8)``).
        dtype: accumulator dtype.
        sketches: optional pre-drawn ``(S_C, S_R)`` pair (shared randomness).
        panel: fixed streaming panel width — pre-pads ``R``/``S_R`` so ragged
            tails can be zero-padded exactly (see :mod:`repro.stream.engine`).
        telemetry: attach an in-scan diagnostics frame
            (:class:`repro.obs.telemetry.TelemetryFrame` — admission/eviction
            counts, score quantiles, and the a-posteriori error estimator's
            test sketch; see :func:`repro.obs.estimate_rel_error`). Requires
            ``panel=``; factors are bit-identical with it on or off.

    Returns:
        A :class:`~repro.stream.engine.PanelState` wired to
        :data:`ADAPTIVE_CUR_OPS`; drive it with ``stream_panels`` /
        ``simulate_sharded_stream`` / ``mesh_sharded_stream`` and finish with
        :func:`adaptive_cur_finalize`.
    """
    from ..cur.cur import cur_sketch_sizes  # lazy: repro.cur imports repro.stream

    adaptive_rows = row_idx is None
    if adaptive_rows:
        if r is None:
            raise ValueError("pass `row_idx` (fixed rows) or `r=` (adaptive rows)")
        row_idx_arr = jnp.full((r,), -1, jnp.int32)
    else:
        if r is not None:
            raise ValueError(
                "`r=` is the adaptive-row budget and requires `row_idx=None`; "
                "with fixed `row_idx` the budget is its length"
            )
        # Copy, not view: the scan path donates the state's buffers, and a
        # zero-copy asarray would hand the caller's own array to the donor.
        row_idx_arr = jnp.array(row_idx, jnp.int32)
        r = row_idx_arr.shape[0]
    n_pad = padded_n(n, panel) if panel else n
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
    S_R.cols(0, 1)  # fail fast on non-sliceable families
    S_R = S_R.pad_cols(n_pad)
    rows = None
    if adaptive_rows:
        rows = AdaptiveRowState(
            row_sketch=jnp.zeros((m, s_r), jnp.float32),
            backfill=jnp.zeros((r, s_r), jnp.float32),
            admit_off=jnp.full((r,), -1, jnp.int32),
            gram=jnp.zeros((s_r, s_r), jnp.float32),
            gram_pending=jnp.zeros((s_r, s_r), jnp.float32),
            # dense S_R once, at init: every per-panel window Gram and every
            # backfill prefix map is a slice of this — the streaming loop
            # never materializes a sketch again
            sr_dense=S_R.materialize().astype(jnp.float32),
            n_filled=jnp.zeros((), jnp.int32),
            slot_lo=jnp.zeros((), jnp.int32),
            min_gain=jnp.asarray(
                min_gain if min_gain_rows is None else min_gain_rows, jnp.float32
            ),
            seen_lo=jnp.full((), -1, jnp.int32),
            r_local=r,
            panel_cap=panel_cap_rows if panel_cap_rows is not None else max(1, r // 8),
        )
    ctx = AdaptiveCURCtx(
        col_idx=jnp.full((c,), -1, jnp.int32),
        row_idx=row_idx_arr,
        S_C=S_C,
        S_R=S_R,
        ScC=jnp.zeros((s_c, c), dtype),
        slot_score=jnp.zeros((c,), jnp.float32),
        n_filled=jnp.zeros((), jnp.int32),
        slot_lo=jnp.zeros((), jnp.int32),
        energy=jnp.zeros((), jnp.float32),
        cols_seen=jnp.zeros((), jnp.float32),
        min_gain=jnp.asarray(min_gain, jnp.float32),
        swap_gain=jnp.asarray(
            jnp.inf if swap_gain is None else swap_gain, jnp.float32
        ),
        n_evicted=jnp.zeros((), jnp.int32),
        rows=rows,
        c_local=c,
        panel_cap=panel_cap if panel_cap is not None else max(1, c // 8),
        n=n,
        evict=swap_gain is not None,
    )
    tel = None
    ops = ADAPTIVE_CUR_OPS
    if telemetry:
        if panel is None:
            raise ValueError(
                "telemetry=True requires a fixed panel= width (the diagnostics "
                "frame is indexed by global panel id)"
            )
        # Independent key for the estimator's held-out test sketch: folding a
        # constant into the init key keeps it disjoint from the S_C/S_R draws
        # (which use split(key)) while staying reproducible from one seed.
        tel = init_telemetry(jax.random.fold_in(key, 7), m, n, panel)
        ops = ADAPTIVE_CUR_TEL_OPS
    return PanelState(
        C=jnp.zeros((m, c), dtype),
        R=jnp.zeros((r, n_pad), dtype),
        M=jnp.zeros((s_c, s_r), dtype),
        offset=jnp.zeros((), jnp.int32),
        ctx=ctx,
        ops=ops,
        n=n,
        tel=tel,
    )


def adaptive_cur_finalize(state: PanelState):
    """Fast-GMR core solve on the admitted columns/rows.

    Unfilled slots (zero columns of ``C`` / zero rows of ``R``) get zeroed
    core rows/columns so they cannot inject the floored solve's
    large-but-finite garbage into downstream consumers.

    Returns:
        A :class:`~repro.cur.cur.CURResult`; ``col_idx``/``row_idx`` hold
        the admitted (post-eviction) index sets with −1 in unfilled slots.
    """
    from ..cur.cur import CURResult  # lazy: repro.cur imports repro.stream

    ctx = state.ctx
    with jax.named_scope(SCOPE_SOLVE):
        R = truncated_R(state)
        RSr = ctx.S_R.apply_t(R)  # (r, s_r)
        U = fast_gmr_core(ctx.ScC, state.M, RSr)  # ScC ≡ S_C C by construction
        filled_c = ctx.col_idx >= 0
        U = jnp.where(filled_c[:, None], U, jnp.zeros((), U.dtype))
        if ctx.rows is not None:
            filled_r = ctx.row_idx >= 0
            U = jnp.where(filled_r[None, :], U, jnp.zeros((), U.dtype))
    return CURResult(C=state.C, U=U, R=R, col_idx=ctx.col_idx, row_idx=ctx.row_idx)


# Compiled at module scope (one trace per shape) and dispatched inside a host
# span; the state is NOT donated — callers inspect it (n_evicted, admit_off,
# …) after finalizing.
adaptive_cur_finalize = spanned("stream/adaptive_cur/finalize")(jax.jit(adaptive_cur_finalize))
