"""Unified panel-streaming engine.

The paper's two streaming applications — single-pass SVD (Algorithm 3,
``repro.core.svd``) and streaming CUR (``repro.cur.streaming``) — share one
contract: the input ``A`` arrives as L-column panels ``A_L`` that are never
retained, and three accumulators are maintained per panel

* ``C``  (m × c)   — a column factor (sketched columns for SP-SVD, actual
  selected columns for CUR);
* ``R``  (r × n)   — a row factor filled block-by-block at the panel's
  column offset;
* ``M``  (s_c × s_r) — the running core sketch
  ``M += (S_C A_L) · S_R[:, cols]ᵀ`` via the ``cols()`` sketch-window
  primitive of :mod:`repro.core.sketching`.

**Symmetric (tied-operand) streams.** A :class:`PanelOps` may declare
``symmetric=True`` for square streams where the row factor is *tied* to the
column factor — SPSD / kernel matrices with ``R = Cᵀ``
(:mod:`repro.spsd.streaming`, ``repro.cur.symmetric_cur``). The engine then
skips the redundant R half of every panel update entirely: the state's ``R``
is a zero-row placeholder ``(0, n_pad)`` (so the scan/donation/merge/psum
machinery is untouched), :func:`truncated_R` *derives* ``R = Cᵀ`` from the
column factor, and the per-panel work drops to the C update + the shared M
accumulation. Both sketches of ``core_sketches`` live on the same
``n``-dimensional operand space (one sketch family over one index set
instead of two); they may still be independent draws — Algorithm 2's
analysis requires ``S₁ ⊥ S₂``.

This module owns that contract once. Applications plug in a
:class:`PanelOps` — three pure functions describing how their ``C``
contribution and ``R`` block are computed from a panel — and get the shared
machinery for free: a scan-compiled whole-stream driver
(:func:`stream_panels`, the default — one ``lax.scan`` program per chunk
with the input state's buffers donated so C/R/M update in place), a
jit-cached per-panel step (:func:`panel_update` /
:data:`jitted_panel_update`, retained behind ``jit="per-panel"`` as the
parity oracle), zero-padded ragged-tail handling (exact because
``pad_cols()`` sketch windows past the true column count are zero-scaled),
and DP-sharded ingestion with exact psum/merge finalize
(:mod:`repro.stream.distributed`).

Panel width does not change the mathematics: ``Σ_L S_C A_L S_R[:, cols]ᵀ =
S_C A S_Rᵀ`` exactly, so any panel partition — including the per-worker
partitions of the distributed path — reproduces the one-shot accumulators up
to fp32 summation order.

**Donation contract:** the scan path donates the input state's buffers to
the output state (``donate_argnums``), so a caller must treat
``stream_panels(state, …)`` as *consuming* ``state`` — keep only the
returned state. Chunked ingestion (repeated calls on the same logical
stream) composes naturally: each call consumes the previous call's output.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..obs.spans import span
from ..obs.telemetry import EVENT_QUARANTINED, fold_psi_chunk

__all__ = [
    "PanelOps",
    "PanelState",
    "panel_update",
    "jitted_panel_update",
    "stream_panels",
    "scan_chunk",
    "scan_panels",
    "padded_n",
    "fresh_pytree",
    "copy_selected_columns",
    "truncated_R",
    "with_quarantine",
    "zero_nonfinite_panels",
    "SCOPES",
]

# Device scopes (``jax.named_scope``): every stage of a panel update carries
# one of these names in the ``op_name`` metadata of its HLO, so a device
# trace splits the scan's time by stage (docs/observability.md). A scope is
# metadata only: the optimized program is otherwise the same without it.
SCOPE_SKETCH = "stream.sketch"  # S_C applied to the chunk or panel, and its operand read
SCOPE_CHUNK_FOLD = "stream.chunk_fold"  # ops.chunk_fold
SCOPE_MFOLD = "stream.mfold"  # M += (S_C A_L) S_R[:, off:off+L]ᵀ
SCOPE_ADMIT = "stream.admit"  # scoring and admission: sketch_panel, update_c, fused_step
SCOPE_COLSKETCH = "stream.colsketch"  # a C update that sketches the panel (SP-SVD's A_L Ω̃_L)
SCOPE_ROWS = "stream.rows"  # update_r / r_block
SCOPE_PANEL_KERNEL = "stream.panel_kernel"  # Route B launch + slot-table bookkeeping
SCOPE_PSUM = "stream.psum"  # the mesh program's collectives
SCOPE_SOLVE = "finalize.solve"  # the finalizers' core solve
SCOPES = (
    SCOPE_SKETCH, SCOPE_CHUNK_FOLD, SCOPE_MFOLD, SCOPE_ADMIT, SCOPE_COLSKETCH, SCOPE_ROWS,
    SCOPE_PANEL_KERNEL, SCOPE_PSUM, SCOPE_SOLVE,
)


def copy_selected_columns(col_idx, C, A_L, off):
    """Slot-copy C update shared by the fixed-index plug-ins: every panel
    column whose global index appears in ``col_idx`` lands in that slot.

    ``off`` may be traced; out-of-panel (and −1-sentinel) slots pass
    through unchanged. Used by streaming CUR (``repro.cur.streaming``) and
    streaming SPSD (``repro.spsd.streaming``) so the panel window math
    lives in one place.
    """
    L = A_L.shape[1]
    rel = col_idx - off
    in_panel = (rel >= 0) & (rel < L)
    picked = jnp.take(A_L, jnp.clip(rel, 0, L - 1), axis=1)  # (m, c)
    return jnp.where(in_panel[None, :], picked.astype(C.dtype), C)


@dataclasses.dataclass(frozen=True)
class PanelOps:
    """The per-application slice of the streaming contract (static metadata).

    All callables must be jit-traceable. ``ctx`` is an application-defined
    pytree holding sketches / indices / adaptive state; the engine threads it
    through every update.
    """

    name: str
    # ctx -> (S_C-like, S_R-like): the core sketches driving the M update.
    core_sketches: Callable[[Any], tuple]
    # (ctx, C, A_L, sc_a, off) -> (ctx', C'): fold one panel into C.
    # ``sc_a = S_C @ A_L`` is pre-computed by the engine (shared with the M
    # update) so residual-scoring policies get it for free. When
    # ``sketch_panel`` (below) is set, update_c instead receives a sixth
    # positional argument — the scores tuple returned by that hook.
    update_c: Callable[..., tuple]
    # Optional fused panel-sketch hook: (ctx, A_L, off) -> (ctx', sc_a,
    # scores). When set it REPLACES the engine's own ``S_C.apply(A_L)`` so an
    # application can compute ``sc_a`` *and* per-column scores in one fused
    # pass (on TPU, one VMEM pass via the kernels.panel_score Pallas kernel
    # instead of three HBM round-trips); ``scores`` is forwarded verbatim to
    # ``update_c`` as its sixth argument. Must be jit-traceable and must
    # return ``sc_a`` bit-compatible with ``S_C.apply(A_L)``'s contract (it
    # also feeds the shared M update).
    sketch_panel: Optional[Callable] = None
    # (ctx, A_L, off) -> (r, L) block written into R[:, off:off+L]. May be
    # omitted when update_r (below) is provided instead.
    r_block: Optional[Callable[..., jax.Array]] = None
    # Optional full-control R update: (ctx, R, A_L, off) -> R'. When set it
    # REPLACES the r_block/dynamic_update_slice path, so applications that
    # must write outside the current panel window — e.g. adaptive row
    # admission backfilling a late-admitted row's column prefix from its
    # sketched reconstruction (repro.stream.adaptive) — can do so. The hook
    # receives the post-update_c ctx, so per-panel admission decisions made
    # in update_c are visible. Must be jit-traceable and must only *add*
    # information at columns < off + L (the single-pass contract: future
    # columns have not been seen).
    update_r: Optional[Callable] = None
    # Optional distributed hooks (see repro.stream.distributed):
    # prep_shard(ctx, num_workers) -> ctx   — static, once per run (meta edits)
    # bind_shard(ctx, w) -> ctx             — per worker, w may be traced
    # merge_ctx(ctxs) -> ctx                — in-process merge of worker ctxs
    # collective_ctx(ctx, axis_name) -> ctx — shard_map all-reduce of ctx state
    prep_shard: Optional[Callable] = None
    bind_shard: Optional[Callable] = None
    merge_ctx: Optional[Callable] = None
    collective_ctx: Optional[Callable] = None
    # merge_state(state) -> state — optional post-merge reconciliation run by
    # every distributed driver AFTER the accumulators and ctx are merged
    # (in-process merge, fused simulate, and the shard_map body alike). Unlike
    # merge_ctx it sees the full PanelState, so cross-worker repairs that
    # touch the accumulators — e.g. the adaptive row-admission dedup zeroing
    # duplicate R rows (repro.stream.adaptive) — live here. Must be
    # jit-traceable and deterministic (the mesh path evaluates it replicated
    # on every shard).
    merge_state: Optional[Callable] = None
    # Optional in-scan telemetry hook (repro.obs.telemetry):
    # (tel, ctx_pre, ctx_post, A_L, sc_a, scores, off) -> tel'. Runs AFTER
    # the C/R/M updates of a panel, only when the state actually carries a
    # telemetry frame (state.tel is not None), and may only derive
    # diagnostics — factors are bit-identical with telemetry on or off, and
    # an untelemetered state (tel=None contributes no pytree leaves)
    # compiles to the identical scan program. Contract: the hook may read
    # A_L's static shape only, never its values — the fused scan route
    # passes a (0, panel) placeholder so the panel is not re-sliced.
    telemetry: Optional[Callable] = None
    # --- fused scan-body hooks (Route A — see scan_chunk/scan_panels) -----
    # Declaring chunk_fold opts the ops into the fused scan body: the
    # engine hoists the chunk sketch sca = S_C.apply(window) out of the
    # scan, runs chunk_fold ONCE per chunk for all whole-chunk work, and
    # the per-panel body shrinks to slicing sc_a out of sca + the M fold +
    # fused_step. The per-panel driver (panel_update) stays the parity
    # oracle; a fused ops must produce factors matching it to the scan
    # parity tolerances (bitwise where those tests demand it).
    #
    # chunk_fold(ctx, C, R, block, bcol0, start, width) -> (ctx', C', R'):
    # fold everything panel-invariant over the whole chunk in one pass —
    # fixed-index C column copies, fixed-row R gather + one window write.
    # ``block`` columns [bcol0, bcol0+width) are the chunk's global columns
    # [start, start+width); bcol0/start may be traced.
    chunk_fold: Optional[Callable] = None
    # fused_step(ctx, C, block, bcol, sc_a, off) -> (ctx', C', scores):
    # the genuinely per-panel remainder (adaptive admission/eviction).
    # ``sc_a`` is the pre-sliced panel sketch; candidate columns must be
    # gathered from ``block`` at column ``bcol`` (+ the in-panel index)
    # instead of materializing A_L — that slice is the traffic the fused
    # body removes. None ⇒ no per-panel C/ctx work (fixed-index ops).
    fused_step: Optional[Callable] = None
    # supports_fused(ctx) -> bool — static (trace-time) predicate gating
    # the fused route per state; None ⇒ always. Used to keep configs whose
    # per-panel work cannot be hoisted (e.g. adaptive row admission) on the
    # legacy body.
    supports_fused: Optional[Callable] = None
    # --- Pallas megakernel hook (Route B — see kernels.panel_update) ------
    # panel_kernel(ctx, C, M, A_L, off) -> None | (ctx', C', M', sc_a,
    # scores). Tried FIRST by panel_update: when the hook accepts (TPU
    # backend or a forced test route, kernel-compatible sketches/config) it
    # replaces the sketch + M fold + update_c with one fused kernel launch;
    # returning None at trace time declines and the standard path runs.
    # R-side and telemetry handling are unchanged around it.
    panel_kernel: Optional[Callable] = None
    # Tied-operand (symmetric) stream: the row factor is R = Cᵀ by
    # definition (SPSD / kernel matrices), so the engine skips the R half of
    # every panel update and `truncated_R` derives R from C. Symmetric ops
    # must not declare r_block/update_r, and their state's R must be the
    # (0, n_pad) placeholder.
    symmetric: bool = False
    # Device scope of ``update_c`` in the per-panel body: admission, or
    # SCOPE_COLSKETCH where the C update is itself a sketch of the panel.
    c_scope: str = SCOPE_ADMIT

    def __post_init__(self):
        """Fail fast at construction: a symmetric (tied-operand) ops derives
        ``R = Cᵀ`` and must not declare an R hook; otherwise the R update
        must come from exactly one of ``r_block`` / ``update_r`` (a missing
        hook would surface as an opaque NoneType call inside the jitted
        step)."""
        if self.symmetric:
            if self.r_block is not None or self.update_r is not None:
                raise ValueError(
                    f"PanelOps {self.name!r} is symmetric (R = Cᵀ is derived); "
                    "it must not declare r_block / update_r"
                )
        elif (self.r_block is None) == (self.update_r is None):
            raise ValueError(
                f"PanelOps {self.name!r} needs exactly one of r_block / update_r"
            )


@dataclasses.dataclass
class PanelState:
    """Streaming accumulators + application context.

    ``R`` is allocated at the padded width ``ceil(n/panel)·panel`` when a
    fixed panel width is declared at init; ``n`` records the true column
    count so finalizers can truncate.

    ``tel`` is the optional in-scan diagnostics frame
    (:class:`repro.obs.telemetry.TelemetryFrame`): ``None`` — the default —
    contributes no pytree leaves, so untelemetered states keep their
    pre-telemetry treedef, jit cache keys and donation layout.

    ``quarantined`` is the optional graceful-degradation counter
    (:func:`with_quarantine`): ``None`` — the default — contributes no
    leaves and compiles to the exact pre-quarantine program; a ``()`` int32
    arms the in-scan non-finite panel guard, which zero-scales any panel
    carrying a NaN/Inf entry (its contribution to C/R/M becomes *exactly*
    that of an all-zero panel) and counts it here instead of letting one
    corrupt panel poison every accumulator downstream.
    """

    C: jax.Array  # (m, c)
    R: jax.Array  # (r, n_pad)
    M: jax.Array  # (s_c, s_r)
    offset: jax.Array  # () int32 — columns consumed so far (global)
    ctx: Any  # application pytree (sketches, indices, adaptive state)
    ops: PanelOps  # static
    n: int  # static: true column count
    tel: Any = None  # optional in-scan telemetry frame (repro.obs)
    quarantined: Any = None  # optional () int32 — non-finite panels zeroed in-scan

    def __getattr__(self, name):
        # Back-compat with the pre-engine SPSVDState / StreamingCURState
        # surfaces: delegate unknown attributes (S_C, col_idx, …) to ctx.
        ctx = object.__getattribute__(self, "ctx")
        try:
            return getattr(ctx, name)
        except AttributeError:
            raise AttributeError(
                f"{type(self).__name__} has no attribute {name!r} (nor does its ctx)"
            ) from None

    @property
    def sketches(self):
        """Legacy ``SPSVDState.sketches`` alias for the application ctx."""
        return self.ctx


jax.tree_util.register_dataclass(
    PanelState,
    data_fields=["C", "R", "M", "offset", "ctx", "tel", "quarantined"],
    meta_fields=["ops", "n"],
)


def with_quarantine(state: PanelState) -> PanelState:
    """Arm the in-scan non-finite panel guard on ``state``.

    Returns the state with a zeroed ``()`` int32 ``quarantined`` counter
    leaf. From then on every :func:`panel_update` checks the incoming panel
    for NaN/Inf: a bad panel is zero-scaled (contributing exactly what an
    all-zero panel would to C/R/M and the telemetry fold), the counter is
    incremented, and — when the state carries telemetry — the panel's
    ``EVENT_QUARANTINED`` bit is set in ``tel.events``. Idempotent; the
    default un-armed state compiles to the byte-identical pre-quarantine
    program because ``quarantined=None`` contributes no pytree leaves.
    """
    if state.quarantined is not None:
        return state
    return dataclasses.replace(state, quarantined=jnp.zeros((), jnp.int32))


def zero_nonfinite_panels(block, panel: int):
    """Zero every ``panel``-wide column group of ``block`` that carries a
    NaN/Inf entry.

    Host-callable *and* jit-traceable pre-filter matching the in-scan
    quarantine guard's semantics at block granularity: the engine's scan
    entry points run the estimator Ψ fold over the raw chunk *before* the
    per-panel guard executes, so a quarantine-armed state sanitizes the
    fold's input here — a quarantined panel must contribute zero to Ψ just
    as it contributes zero to C/R/M. ``block`` columns are assumed
    panel-aligned at column 0 (the engine always folds from a panel
    boundary); a ragged tail is treated as its own (partial) panel.
    """
    m, w = block.shape
    num_panels = padded_n(w, panel) // panel
    padded = jnp.pad(block, ((0, 0), (0, num_panels * panel - w)))
    fin = jnp.all(
        jnp.isfinite(padded.reshape(m, num_panels, panel)), axis=(0, 2)
    )  # (num_panels,) — per-panel finite flag
    mask = jnp.repeat(fin, panel)[:w]
    return jnp.where(mask[None, :], block, jnp.zeros((), block.dtype))


def padded_n(n: int, panel: int) -> int:
    """Column count rounded up to a whole number of panels."""
    return ((n + panel - 1) // panel) * panel


def fresh_pytree(tree):
    """Deep-copy every array leaf of a pytree.

    Init functions route caller-provided arrays (index sets, shared
    sketches) through this so the scan path's buffer donation can never
    invalidate an array the caller still holds."""
    return jax.tree_util.tree_map(
        lambda x: jnp.array(x) if isinstance(x, jax.Array) else x, tree
    )


def panel_update(state: PanelState, A_L: jax.Array) -> PanelState:
    """Consume one L-column panel. jit-compatible (L static per panel width).

    ``state.offset`` may be traced (the distributed path binds it to
    ``axis_index · shard_n``); all window arithmetic is dynamic-slice based.
    """
    L = A_L.shape[1]
    off = state.offset
    ops = state.ops

    quarantined = state.quarantined
    bad = None
    if quarantined is not None:
        # Graceful degradation (see with_quarantine): a NaN/Inf panel is
        # zero-scaled so its contribution to C/R/M is exactly an all-zero
        # panel's, and counted instead of poisoning the accumulators.
        bad = ~jnp.all(jnp.isfinite(A_L))
        A_L = jnp.where(bad, jnp.zeros((), A_L.dtype), A_L)
        quarantined = quarantined + bad.astype(jnp.int32)

    fast = None
    if ops.panel_kernel is not None:
        # Route B: one fused Pallas launch replaces the sketch, the M fold
        # and update_c when the hook accepts (None = trace-time decline).
        with jax.named_scope(SCOPE_PANEL_KERNEL):
            fast = ops.panel_kernel(state.ctx, state.C, state.M, A_L, off)
    if fast is not None:
        ctx, C, M, sc_a, scores = fast
    else:
        S_C, S_R = ops.core_sketches(state.ctx)
        if ops.sketch_panel is not None:
            # fused path: the application computes sc_a together with its
            # per-column scores (one pass; see kernels.panel_score on TPU)
            with jax.named_scope(SCOPE_ADMIT):
                ctx, sc_a, scores = ops.sketch_panel(state.ctx, A_L, off)
        else:
            with jax.named_scope(SCOPE_SKETCH):
                ctx, sc_a, scores = state.ctx, S_C.apply(A_L), None
        with jax.named_scope(SCOPE_MFOLD):
            M = state.M + S_R.cols(off, L).apply_t(sc_a).astype(state.M.dtype)

        with jax.named_scope(ops.c_scope):
            if scores is None:
                ctx, C = ops.update_c(ctx, state.C, A_L, sc_a, off)
            else:
                ctx, C = ops.update_c(ctx, state.C, A_L, sc_a, off, scores)
    if ops.symmetric:
        R = state.R  # tied operand: R = Cᵀ is derived, nothing to accumulate
    elif ops.update_r is not None:
        with jax.named_scope(SCOPE_ROWS):
            R = ops.update_r(ctx, state.R, A_L, off)
    else:
        with jax.named_scope(SCOPE_ROWS):
            r_blk = ops.r_block(ctx, A_L, off).astype(state.R.dtype)
            R = jax.lax.dynamic_update_slice_in_dim(state.R, r_blk, off, axis=1)

    # Telemetry fold runs last — it observes the panel's outcome (pre/post
    # ctx) and only writes the diagnostics frame, never the factors.
    tel = state.tel
    if ops.telemetry is not None and tel is not None:
        tel = ops.telemetry(tel, state.ctx, ctx, A_L, sc_a, scores, off)
    if bad is not None and tel is not None:
        # Flag the quarantine in the panel's event bitmask. `.add` composes
        # with the hook's `.set` above — the hook never writes this bit.
        t = off // tel.panel
        flag = jnp.where(bad, EVENT_QUARANTINED, 0).astype(jnp.int32)
        tel = dataclasses.replace(tel, events=tel.events.at[t].add(flag))

    return dataclasses.replace(
        state, C=C, R=R, M=M, offset=off + L, ctx=ctx, tel=tel,
        quarantined=quarantined,
    )


# Module-scope jit: one trace per (shapes, ops) pair for the whole process —
# callers that used to rebuild ``jax.jit(update)`` per invocation retraced on
# every call. Retained as the per-panel parity oracle for the scan path.
jitted_panel_update = jax.jit(panel_update)


def _fused_route_ok(state: PanelState) -> bool:
    """Static (trace-time) check: may this state take the fused scan body?

    Requires the ops to have opted in (``chunk_fold``), an un-armed
    quarantine guard (the in-scan NaN zero-scaling is inherently per-panel
    — chaos parity stays on the legacy body), and the ops' own
    ``supports_fused`` predicate to accept the ctx.
    """
    ops = state.ops
    return (
        ops.chunk_fold is not None
        and state.quarantined is None
        and (ops.supports_fused is None or ops.supports_fused(state.ctx))
    )


def _fused_scan(
    state: PanelState, block: jax.Array, bcol0, window: jax.Array,
    num_panels: int, panel: int,
) -> PanelState:
    """Fused scan body (Route A): chunk-hoisted sketch + thin per-panel loop.

    The legacy scan body re-slices the (m × L) panel out of the operand and
    re-applies ``S_C`` to it every step — O(m·L) HBM traffic per panel for
    data whose per-panel products are tiny. Here the chunk sketch
    ``sca = S_C.apply(window)`` is computed ONCE per chunk (exactly the
    per-panel sketches side by side: every supported sketch family's
    ``apply`` is column-independent), all panel-invariant factor writes are
    folded once by ``ops.chunk_fold``, and the scan body shrinks to an
    (s_c × L) slice of ``sca``, the per-panel ``M`` fold — kept per panel
    so the fp32 summation order matches the per-panel oracle — and the
    ops' ``fused_step`` (admission policies; None for fixed-index ops).

    ``window`` is the contiguous (m × num_panels·panel) column range being
    consumed (``block`` itself for chunk operands, a dynamic window slice
    for full-stream operands); ``block``/``bcol0`` are forwarded to the
    hooks so per-panel candidate gathers index the un-copied operand.
    """
    ops = state.ops
    start = state.offset
    S_C, S_R = ops.core_sketches(state.ctx)
    with jax.named_scope(SCOPE_SKETCH):
        sca = S_C.apply(window)  # (s_c, width) — all panel sketches, one pass
    with jax.named_scope(SCOPE_CHUNK_FOLD):
        ctx, C, R = ops.chunk_fold(
            state.ctx, state.C, state.R, block, bcol0, start, num_panels * panel
        )
    has_tel = ops.telemetry is not None and state.tel is not None
    # telemetry hooks read A_L's static shape only (see PanelOps.telemetry)
    placeholder = jnp.zeros((0, panel), block.dtype)

    def body(carry, t):
        ctx, C, M, tel = carry
        off = start + t * panel
        with jax.named_scope(SCOPE_SKETCH):
            sc_a = jax.lax.dynamic_slice_in_dim(sca, t * panel, panel, axis=1)
        with jax.named_scope(SCOPE_MFOLD):
            M = M + S_R.cols(off, panel).apply_t(sc_a).astype(M.dtype)
        ctx_pre, scores = ctx, None
        if ops.fused_step is not None:
            with jax.named_scope(SCOPE_ADMIT):
                ctx, C, scores = ops.fused_step(
                    ctx, C, block, bcol0 + t * panel, sc_a, off
                )
        if has_tel:
            tel = ops.telemetry(tel, ctx_pre, ctx, placeholder, sc_a, scores, off)
        return (ctx, C, M, tel), None

    (ctx, C, M, tel), _ = jax.lax.scan(
        body, (ctx, C, state.M, state.tel), jnp.arange(num_panels, dtype=jnp.int32)
    )
    return dataclasses.replace(
        state, C=C, R=R, M=M, offset=start + num_panels * panel, ctx=ctx, tel=tel
    )


def scan_chunk(state: PanelState, A_chunk: jax.Array, panel: int) -> PanelState:
    """Consume a pre-padded chunk (width = whole panels) via one ``lax.scan``.

    Traceable core of the compiled streaming path: the whole chunk becomes a
    single XLA loop whose carry is the :class:`PanelState`, so the C/R/M
    buffers update in place across panels instead of being re-materialized
    at every dispatch boundary. ``A_chunk.shape[1]`` must be a multiple of
    ``panel`` (callers zero-pad the ragged tail — exact, see
    :func:`stream_panels`); panels are consumed left-to-right at the state's
    running offset, bit-for-bit the same per-panel math as
    :func:`panel_update`. The chunk is indexed *relative* to its own first
    column — use :func:`scan_panels` when the operand is the full stream
    array (no chunk copy).

    The scan body is the fused one (:func:`_fused_scan`) when the state
    allows it (:func:`_fused_route_ok`), else the per-panel body.
    """
    num_panels = A_chunk.shape[1] // panel
    if state.ops.telemetry is not None and state.tel is not None:
        # estimator Ψ fold hoisted out of the scan body: one GEMM over the
        # whole chunk (inside the carry it costs ~3× standalone wall-time);
        # the chunk is consumed atomically by this program, so Ψ and the
        # factors agree at every program boundary
        psi_in = A_chunk
        if state.quarantined is not None:
            # the fold sees the raw chunk before the per-panel guard runs —
            # drop quarantined panels here too, or one NaN poisons Ψ
            psi_in = zero_nonfinite_panels(A_chunk, panel)
        state = dataclasses.replace(
            state, tel=fold_psi_chunk(state.tel, psi_in, state.offset)
        )

    if _fused_route_ok(state):
        return _fused_scan(state, A_chunk, 0, A_chunk, num_panels, panel)

    def body(st, t):
        with jax.named_scope(SCOPE_SKETCH):  # the panel is the sketch's operand
            A_L = jax.lax.dynamic_slice_in_dim(A_chunk, t * panel, panel, axis=1)
        return panel_update(st, A_L), None

    state, _ = jax.lax.scan(body, state, jnp.arange(num_panels, dtype=jnp.int32))
    return state


def scan_panels(
    state: PanelState, A: jax.Array, num_panels: int, panel: int
) -> PanelState:
    """Scan ``num_panels`` panels of the *full* ``A`` at the state's offset.

    Same loop as :func:`scan_chunk` but sliced at **absolute** offsets
    (``state.offset + t·panel``), so ``A`` stays a loop-invariant operand
    and no per-caller chunk copy is ever materialized (the fused
    sharded-simulate path reads one shared ``A`` for every worker). Caller
    must guarantee ``offset + num_panels·panel ≤ A.shape[1]`` — ragged
    tails go through the zero-padded :func:`scan_chunk` path instead.

    The body is chosen as in :func:`scan_chunk`; the fused body applies the
    chunk sketch to the dynamic window ``A[:, offset : offset +
    num_panels·panel]``.
    """
    offs = state.offset + jnp.arange(num_panels, dtype=jnp.int32) * panel
    if state.ops.telemetry is not None and state.tel is not None:
        # chunk-level Ψ fold (see scan_chunk); the dynamic window slice
        # fuses into the GEMM — no chunk copy is materialized
        block = jax.lax.dynamic_slice_in_dim(
            A, state.offset, num_panels * panel, axis=1
        )
        if state.quarantined is not None:
            block = zero_nonfinite_panels(block, panel)
        state = dataclasses.replace(
            state, tel=fold_psi_chunk(state.tel, block, state.offset)
        )

    if _fused_route_ok(state):
        with jax.named_scope(SCOPE_SKETCH):  # the chunk sketch's operand
            window = jax.lax.dynamic_slice_in_dim(
                A, state.offset, num_panels * panel, axis=1
            )
        return _fused_scan(state, A, state.offset, window, num_panels, panel)

    def body(st, off):
        with jax.named_scope(SCOPE_SKETCH):  # the panel is the sketch's operand
            A_L = jax.lax.dynamic_slice_in_dim(A, off, panel, axis=1)
        return panel_update(st, A_L), None

    state, _ = jax.lax.scan(body, state, offs)
    return state


# The compiled whole-stream entry points: one trace per (shapes, panel, ops)
# for the process lifetime, with the carried state DONATED — on backends
# with buffer donation the input accumulators are reused for the output, so
# streaming is allocation-free in steady state. Callers must not reuse the
# input state afterwards (see module docstring).
_scan_stream_chunk = jax.jit(
    scan_chunk, static_argnames=("panel",), donate_argnums=(0,)
)
_scan_stream_panels = jax.jit(
    scan_panels, static_argnames=("num_panels", "panel"), donate_argnums=(0,)
)

_JIT_MODES = ("scan", "per-panel")


def stream_panels(
    state: PanelState, A: jax.Array, panel: int, *, stop: Optional[int] = None,
    jit: str = "scan",
) -> PanelState:
    """Drive columns ``[offset, stop)`` of ``A`` through the engine in
    fixed-width panels, zero-padding the ragged tail. Host-side driver:
    ``state.offset`` must be concrete.

    ``jit`` selects the execution strategy:

    * ``"scan"`` (default) — the whole chunk runs as one compiled
      ``lax.scan`` program (:func:`scan_chunk`) with the input state's
      buffers donated: no per-panel dispatch, no per-panel accumulator
      re-materialization. The input ``state`` is *consumed*.
    * ``"per-panel"`` — one :data:`jitted_panel_update` dispatch per panel
      (the pre-scan behaviour; kept as the parity oracle).

    The tail padding is exact — not approximate — because the state's
    sketches were extended with ``pad_cols`` at init: windows past the true
    column count are zero-scaled, and the padded columns of ``A_L`` are zero,
    so the padded block contributes nothing to C, R or M.
    """
    if jit not in _JIT_MODES:
        raise ValueError(f"jit must be one of {_JIT_MODES}, got {jit!r}")
    n = A.shape[1]
    start = int(state.offset)
    stop = min(n, state.n) if stop is None else stop
    if state.R.shape[1] < padded_n(stop - start, panel) + start:
        raise ValueError(
            f"state was initialised without room for panel={panel} tail padding "
            f"(R width {state.R.shape[1]}, need {start + padded_n(stop - start, panel)}); "
            "pass `panel=` at init"
        )
    if stop <= start:
        return state
    if jit == "scan":
        width = stop - start
        num_panels = padded_n(width, panel) // panel
        with span(f"stream/{state.ops.name}/scan"):
            if width == num_panels * panel:
                # aligned: slice panels straight out of the shared A — no copy
                return _scan_stream_panels(state, A, num_panels=num_panels, panel=panel)
            chunk = A[:, start:stop]
            chunk = jnp.pad(chunk, ((0, 0), (0, num_panels * panel - width)))
            return _scan_stream_chunk(state, chunk, panel=panel)
    with span(f"stream/{state.ops.name}/per-panel"):
        if state.ops.telemetry is not None and state.tel is not None:
            # parity with the scan path: Ψ folds once over the consumed
            # window, not per panel (same sum up to float association)
            block = A[:, start:stop]
            if state.quarantined is not None:
                block = zero_nonfinite_panels(block, panel)
            state = dataclasses.replace(
                state, tel=fold_psi_chunk(state.tel, block, start)
            )
        for off in range(start, stop, panel):
            width = min(panel, stop - off)
            A_L = jax.lax.dynamic_slice_in_dim(A, off, width, axis=1)
            if width != panel:
                A_L = jnp.pad(A_L, ((0, 0), (0, panel - width)))
            state = jitted_panel_update(state, A_L)
    return state


def truncated_R(state: PanelState) -> jax.Array:
    """``R`` restricted to the true (unpadded) column range.

    For symmetric (tied-operand) streams the engine never accumulates R —
    it is *derived* here as ``Cᵀ`` (``C`` rows are never padded, so no
    truncation is needed).
    """
    if state.ops.symmetric:
        return state.C.T
    return state.R[:, : state.n]
