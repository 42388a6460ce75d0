"""DP-sharded panel-stream ingestion.

The sketches inside a :class:`~repro.stream.engine.PanelState` are fully
determined by the init key, so every data-parallel worker holds *bit-identical*
operators. Each worker then consumes a disjoint, contiguous, panel-aligned
column range of the stream at its correct global offset, and because all three
accumulators are sums of per-panel contributions into zero-initialised
buffers (``C`` and ``R`` writes are disjoint slots/blocks, ``M`` is a running
sum), the single-host result is recovered *exactly* (up to fp32 summation
order) by summing the worker accumulators:

    ``Σ_w state_w.{C,R,M}  ==  single-host state.{C,R,M}``

Two execution modes share the same math:

* :func:`simulate_sharded_stream` — run the workers in-process (any device
  count; what the parity tests and benchmarks use). Default execution is
  **one compiled program**: every worker's panel range runs as a local
  ``lax.scan`` (:func:`repro.stream.engine.scan_chunk`) and the merge happens
  inside the same dispatch, so a W-worker simulation costs one XLA call —
  the per-worker-per-panel dispatch & re-materialization overhead that used
  to make w2/w4 *slower* than single-host is gone. The pre-scan per-panel
  loop is retained behind ``jit="per-panel"`` as the parity oracle.
* :func:`mesh_sharded_stream` — one ``shard_map`` program over a named mesh
  axis: each shard scans its whole panel chunk locally, then the
  accumulators are all-reduced with **one ``psum`` per chunk** (never per
  panel — collective cadence is per streamed chunk, the real multi-device
  path, exercised by ``tests/multidev_scenario.py`` under forced host
  devices).

Application context that *does* diverge across workers (the adaptive-CUR
admission state) is reconciled through the optional ``PanelOps`` hooks
``prep_shard`` / ``bind_shard`` / ``merge_ctx`` / ``collective_ctx``, and
cross-worker repairs that must see the merged *accumulators* (adaptive row
dedup) run through ``merge_state`` after every merge path.

Symmetric (tied-operand) streams — SPSD / kernel approximation with
``R = Cᵀ`` (:mod:`repro.spsd.streaming`) — ride the same machinery
unchanged: their ``R`` is the ``(0, n_pad)`` placeholder (merge-sum and
psum are no-ops on it) while ``C`` and ``M`` obey the same
disjoint-write/running-sum algebra, so sharded tied-operand ingestion
reproduces the single-host factors exactly as well.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..obs.spans import span
from .engine import (
    SCOPE_PSUM, PanelState, padded_n, scan_chunk, scan_panels, stream_panels,
)

__all__ = [
    "shard_panel_ranges",
    "simulate_sharded_stream",
    "merge_states",
    "mesh_sharded_stream",
]


def shard_panel_ranges(n: int, panel: int, num_workers: int) -> List[Tuple[int, int]]:
    """Contiguous, panel-aligned column ranges ``[lo, hi)`` per worker.

    Panels are dealt out as evenly as possible; only the last worker's range
    can end ragged (at ``n``). Workers past the panel count get empty ranges.
    """
    num_panels = (n + panel - 1) // panel
    bounds = [round(i * num_panels / num_workers) for i in range(num_workers + 1)]
    return [
        (min(bounds[i] * panel, n), min(bounds[i + 1] * panel, n))
        for i in range(num_workers)
    ]


def _worker_state(state0: PanelState, ctx, lo: int) -> PanelState:
    return dataclasses.replace(state0, ctx=ctx, offset=jnp.asarray(lo, jnp.int32))


def merge_states(states: Sequence[PanelState]) -> PanelState:
    """Sum worker accumulators into the equivalent single-host state.

    When the application declares a ``merge_state`` hook (cross-worker
    repairs that touch the accumulators, e.g. adaptive row dedup), it runs
    last — after the accumulator sum and the ctx merge.

    Telemetry frames ride the same algebra: per-panel slots are disjoint
    worker writes and the rest are running sums, so
    ``TelemetryFrame.merge`` sums them (the constant test sketch excepted).
    """
    states = list(states)
    base = states[0]
    C = sum((s.C for s in states[1:]), base.C)
    R = sum((s.R for s in states[1:]), base.R)
    M = sum((s.M for s in states[1:]), base.M)
    if base.ops.merge_ctx is not None:
        ctx = base.ops.merge_ctx([s.ctx for s in states])
    else:
        ctx = base.ctx
    tel = base.tel
    if tel is not None:
        tel = tel.merge([s.tel for s in states])
    quarantined = base.quarantined
    if quarantined is not None:
        # per-worker quarantine counts are disjoint panel tallies — sum
        quarantined = sum((s.quarantined for s in states[1:]), quarantined)
    merged = dataclasses.replace(
        base, C=C, R=R, M=M, offset=jnp.asarray(base.n, jnp.int32), ctx=ctx, tel=tel,
        quarantined=quarantined,
    )
    if base.ops.merge_state is not None:
        merged = base.ops.merge_state(merged)
    return merged


def _scan_range(st: PanelState, A: jax.Array, lo: int, hi: int, panel: int) -> PanelState:
    """Scan one worker's ``[lo, hi)`` column range (traced; ``st.offset == lo``)."""
    from .engine import panel_update

    num_panels = padded_n(hi - lo, panel) // panel
    if hi - lo == num_panels * panel:
        if num_panels == 1:
            # single whole panel: no loop machinery, one unrolled step
            return panel_update(st, jax.lax.dynamic_slice_in_dim(A, lo, panel, axis=1))
        # aligned range: slice panels out of the shared A — no chunk copy
        return scan_panels(st, A, num_panels, panel)
    chunk = jnp.pad(A[:, lo:hi], ((0, 0), (0, num_panels * panel - (hi - lo))))
    return scan_chunk(st, chunk, panel)


@partial(jax.jit, static_argnames=("ranges", "panel"), donate_argnums=(0,))
def _fused_simulate(state0: PanelState, A: jax.Array, ranges, panel: int) -> PanelState:
    """One compiled program: every worker's local scan + the merge.

    ``ranges`` is the static per-worker panel partition. Two regimes:

    * **No shard hooks** (fixed-index CUR, SP-SVD): every accumulator update
      is a running sum or a disjoint slot/block write into zero-init
      buffers, so per-worker accumulators followed by a merge-sum are
      *provably identical* to chaining one state through the workers'
      ranges in order (and the chained fp summation order equals the
      single-host order exactly). The fused program therefore chains —
      W-worker simulation costs the single-host stream, no per-worker
      accumulator materialization, no merge. The un-chained per-worker
      machinery stays covered by ``jit="per-panel"`` and the mesh path.
    * **Shard hooks present** (adaptive CUR): only the admission *context*
      genuinely diverges per worker — the C/R/M accumulators remain
      disjoint-slot/disjoint-range writes and running sums even under
      adaptive admission (each worker only ever touches its own slot range
      and its own column range), so the accumulators chain through the
      workers exactly like the hook-less case while each worker's ctx
      starts from its own ``bind_shard`` binding; only the ctxs are merged
      (``merge_ctx``), with no per-worker accumulator materialization.

    ``state0`` is donated: on backends with buffer donation the fresh
    accumulators are reused for the output.
    """
    ops = state0.ops
    chainable = (
        ops.bind_shard is None and ops.merge_ctx is None and ops.collective_ctx is None
    )
    if chainable:
        st = state0
        if all(a[1] == b[0] for a, b in zip(ranges, ranges[1:])):
            # contiguous partition (always true for shard_panel_ranges):
            # chaining collapses to ONE scan over the union range — the
            # W-worker program IS the single-host program
            lo, hi = ranges[0][0], ranges[-1][1]
            if hi > lo:
                st = dataclasses.replace(st, offset=jnp.asarray(lo, jnp.int32))
                st = _scan_range(st, A, lo, hi, panel)
        else:  # pragma: no cover — defensive: non-contiguous custom ranges
            for lo, hi in ranges:
                if hi > lo:
                    st = dataclasses.replace(st, offset=jnp.asarray(lo, jnp.int32))
                    st = _scan_range(st, A, lo, hi, panel)
        st = dataclasses.replace(st, offset=jnp.asarray(state0.n, jnp.int32))
        return ops.merge_state(st) if ops.merge_state is not None else st
    worker_ctxs = []
    st = state0
    for w, (lo, hi) in enumerate(ranges):
        ctx = state0.ctx  # each worker's ctx starts fresh from the prepped base
        if ops.bind_shard is not None:
            ctx = ops.bind_shard(ctx, jnp.asarray(w, jnp.int32))
        # accumulators chain; ctx is swapped per worker
        st = dataclasses.replace(st, ctx=ctx, offset=jnp.asarray(lo, jnp.int32))
        if hi > lo:
            st = _scan_range(st, A, lo, hi, panel)
        worker_ctxs.append(st.ctx)
    ctx = ops.merge_ctx(worker_ctxs) if ops.merge_ctx is not None else state0.ctx
    st = dataclasses.replace(st, ctx=ctx, offset=jnp.asarray(state0.n, jnp.int32))
    return ops.merge_state(st) if ops.merge_state is not None else st


def simulate_sharded_stream(
    state0: PanelState, A: jax.Array, panel: int, num_workers: int, *, jit: str = "scan"
) -> PanelState:
    """Run ``num_workers`` DP workers in-process and merge.

    Exact parity with single-host streaming for SP-SVD and fixed-index CUR;
    for adaptive CUR each worker admits into its own slot range (see
    ``repro.stream.adaptive``), so the merged state is a valid — but not
    bitwise-identical — admission outcome.

    ``jit="scan"`` (default) runs all workers *and* the merge as one
    compiled program (:func:`_fused_simulate` — ``state0`` is consumed, per
    the engine's donation contract); ``jit="per-panel"`` keeps the pre-scan
    driver: one python loop over workers, each worker dispatching per panel
    — the parity oracle for the scan path.
    """
    if int(state0.offset) != 0:
        raise ValueError(
            "simulate_sharded_stream needs a fresh state: every worker clones "
            "state0's accumulators, so a partially-streamed prefix would be "
            f"summed once per worker (offset={int(state0.offset)})"
        )
    n = min(A.shape[1], state0.n)
    ranges = shard_panel_ranges(n, panel, num_workers)
    ctx0 = state0.ctx
    if state0.ops.prep_shard is not None:
        ctx0 = state0.ops.prep_shard(ctx0, num_workers)
    state0 = dataclasses.replace(state0, ctx=ctx0)
    if jit == "scan":
        with span(f"stream/{state0.ops.name}/sharded_simulate"):
            return _fused_simulate(state0, A, tuple(ranges), panel)
    shards = []
    for w, (lo, hi) in enumerate(ranges):
        ctx = ctx0
        if state0.ops.bind_shard is not None:
            ctx = state0.ops.bind_shard(ctx, jnp.asarray(w, jnp.int32))
        st = _worker_state(state0, ctx, lo)
        if hi > lo:
            st = stream_panels(st, A, panel, stop=hi, jit=jit)
        shards.append(st)
    # NB: every worker starts from state0's zero accumulators, so the merge
    # sum is exact only for a fresh (un-streamed) state0.
    return merge_states(shards)


def mesh_sharded_stream(
    state0: PanelState,
    A: jax.Array,
    panel: int,
    mesh,
    axis: str = "data",
) -> PanelState:
    """One ``shard_map`` program: shard ``A``'s columns over ``mesh[axis]``,
    scan each shard's whole panel chunk locally, ``psum`` the accumulators
    **once per chunk** (never per panel — the collective cadence is one
    all-reduce per streamed chunk regardless of panel count).

    Requires the (padded) column count to split into whole panels per worker:
    ``n_pad % (W · panel) == 0`` with ``W = mesh.shape[axis]``.
    """
    if int(state0.offset) != 0:
        raise ValueError(
            "mesh_sharded_stream needs a fresh state: every shard starts from "
            "state0's accumulators, so a partially-streamed prefix would be "
            f"psum-multiplied (offset={int(state0.offset)})"
        )
    n = state0.n
    W = int(mesh.shape[axis])
    n_pad = padded_n(n, panel)
    if n_pad % W or (n_pad // W) % panel:
        raise ValueError(
            f"padded column count {n_pad} must split into whole panels per "
            f"worker (W={W}, panel={panel})"
        )
    if A.shape[1] != n_pad:
        A = jnp.pad(A, ((0, 0), (0, n_pad - A.shape[1])))
    if state0.R.shape[1] != n_pad:
        raise ValueError("state was initialised without `panel=`; R is unpadded")
    ops = state0.ops
    ctx0 = state0.ctx
    if ops.prep_shard is not None:
        ctx0 = ops.prep_shard(ctx0, W)
    state0 = dataclasses.replace(state0, ctx=ctx0)
    with span(f"stream/{ops.name}/sharded_mesh"):
        return _mesh_stream(state0, A, panel=panel, mesh=mesh, axis=axis)


@partial(jax.jit, static_argnames=("panel", "mesh", "axis"))
def _mesh_stream(state0: PanelState, A: jax.Array, *, panel: int, mesh, axis: str) -> PanelState:
    """The compiled ``shard_map`` program of :func:`mesh_sharded_stream`
    (module scope, so repeated chunks of one stream reuse one compile)."""
    from jax.sharding import PartitionSpec as P

    ops = state0.ops
    n = state0.n
    shard_n = A.shape[1] // int(mesh.shape[axis])

    def body(state, A_shard):
        w = jax.lax.axis_index(axis)
        ctx = state.ctx
        if ops.bind_shard is not None:
            ctx = ops.bind_shard(ctx, w)
        st = dataclasses.replace(state, ctx=ctx, offset=(w * shard_n).astype(jnp.int32))
        st = scan_chunk(st, A_shard, panel)  # local scan; collectives below
        with jax.named_scope(SCOPE_PSUM):
            ctx = st.ctx
            if ops.collective_ctx is not None:
                ctx = ops.collective_ctx(ctx, axis)
            st = dataclasses.replace(
                st,
                C=jax.lax.psum(st.C, axis),
                # symmetric streams carry the (0, n_pad) placeholder — nothing to reduce
                R=jax.lax.psum(st.R, axis) if st.R.size else st.R,
                M=jax.lax.psum(st.M, axis),
                offset=jnp.asarray(n, jnp.int32),
                ctx=ctx,
                # telemetry reduces with the same disjoint-write algebra as C/R/M
                tel=st.tel.collective(axis) if st.tel is not None else None,
                quarantined=(
                    jax.lax.psum(st.quarantined, axis)
                    if st.quarantined is not None
                    else None
                ),
            )
        return ops.merge_state(st) if ops.merge_state is not None else st

    specs = jax.tree_util.tree_map(lambda _: P(), state0)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(specs, P(None, axis)),
        out_specs=specs,
        check_vma=False,
    )(state0, A)
