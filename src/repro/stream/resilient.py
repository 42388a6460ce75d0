"""Resilient streaming ingestion: resumable PanelState, fault injection,
graceful degradation.

The single-pass setting is exactly where failures hurt most: panels are
never retained, so a crash at panel k of a long stream loses the entire
ingest — yet the carried :class:`~repro.stream.engine.PanelState` (C/R/M +
adaptive ctx + telemetry) is only O(sketch-size), i.e. cheap to
checkpoint, and the factors can be maintained and finalized from that
state alone without a second pass (Tropp et al.'s practical-sketching
argument, PAPERS.md). This module owns the fault story in three layers:

* **Resumable streams** — :func:`run_resilient_stream` consumes panels
  from a :class:`PanelSource` in fixed chunks through the engine's scan
  entry point, checkpoints the full state every ``ckpt_every`` chunks
  through :mod:`repro.checkpoint` (atomic tmp+rename writes, torn
  checkpoints skipped on restore) with a ``panels_consumed`` cursor in the
  manifest, and on restart replays *only unconsumed panels*. Because the
  per-panel math is a pure fold over the chunk sequence, a restored run is
  **bitwise-equal** to an uninterrupted run at the same chunk cadence
  (``tests/test_resilient.py`` asserts this for fixed/adaptive CUR, SPSD
  and both drivers). Restores honor the engine's donation contract: a
  restored state is freshly materialized from disk, never a donated
  buffer.
* **Panel-level fault injection** — a deterministic :class:`FaultPlan`
  (crash-at-panel, NaN/Inf corruption, dropped / duplicated delivery,
  straggler delay) applied by :class:`FaultInjector` at the source
  boundary, so the driver's retry / dedup / restart handling is exercised
  by tests and the ``make chaos-check`` lane without touching the engine.
* **Graceful degradation** — :func:`repro.stream.engine.with_quarantine`
  arms the in-scan non-finite guard: a corrupt panel contributes exactly
  what an all-zero panel would, the state counts it, telemetry flags the
  panel with ``EVENT_QUARANTINED``, and the host driver mirrors the count
  into :mod:`repro.obs.metrics`. ``strict=True`` instead rolls the state
  back to the last checkpoint and raises :class:`QuarantineAbort`.

Distributed resume: :func:`run_resilient_sharded_stream` gives every
worker of a :func:`~repro.stream.distributed.simulate_sharded_stream` /
``mesh_sharded_stream``-style partition its own checkpoint directory, so a
single worker crash restores that worker's panel range and re-merges —
exact parity with the all-healthy run (asserted at 2 and 4 workers,
including against ``mesh_sharded_stream``).

Checkpoint cadence trades write cost against replay cost — see
``docs/resilience.md`` for the tradeoff and a worker-crash walkthrough.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, List, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from ..checkpoint.checkpoint import latest_step, restore, save
from ..obs.metrics import default_registry
from ..obs.spans import span
from . import engine
from .distributed import merge_states, shard_panel_ranges
from .engine import PanelState, fresh_pytree, padded_n, with_quarantine

__all__ = [
    "PanelSource",
    "ArrayPanelSource",
    "FaultPlan",
    "FaultInjector",
    "TransientReadError",
    "InjectedCrash",
    "QuarantineAbort",
    "StreamReport",
    "save_stream_state",
    "restore_stream_state",
    "run_resilient_stream",
    "run_resilient_sharded_stream",
]


class TransientReadError(RuntimeError):
    """A chunk read failed in a retryable way (dropped delivery)."""


class InjectedCrash(RuntimeError):
    """Deterministic process-death stand-in raised *before* the chunk
    containing ``FaultPlan.crash_at_panel`` is consumed."""


class QuarantineAbort(RuntimeError):
    """Strict-mode abort: a non-finite panel was detected and the stream
    state was rolled back to the last checkpoint.

    ``state`` is the rolled-back (fresh, never-donated) state and
    ``panels_consumed`` its cursor — re-invoke ``run_resilient_stream``
    with them once the source is repaired."""

    def __init__(self, msg: str, *, state: PanelState, panels_consumed: int):
        super().__init__(msg)
        self.state = state
        self.panels_consumed = panels_consumed


class PanelSource(Protocol):
    """Pull-model panel stream: idempotent, addressable chunk reads.

    ``read_chunk(lo_panel, num_panels)`` returns ``(tag, chunk)`` where
    ``chunk`` is the ``num_panels · panel`` column block starting at panel
    ``lo_panel`` (zero-padded past the true column count ``n``) and ``tag``
    identifies which panel the delivery actually starts at — the driver
    re-requests on a stale tag (duplicated delivery). Reads must be
    idempotent: replay after restore re-reads the same panels.
    """

    panel: int
    n: int
    num_panels: int

    def read_chunk(self, lo_panel: int, num_panels: int) -> Tuple[int, jax.Array]:
        """Return ``(tag, chunk)`` for the panel window (see class docs)."""
        ...


class ArrayPanelSource:
    """In-memory :class:`PanelSource` over a materialized operand ``A``
    (what the tests, benchmarks and the chaos lane stream from)."""

    def __init__(self, A: jax.Array, panel: int, *, n: Optional[int] = None):
        self.A = jnp.asarray(A)
        self.panel = panel
        self.n = self.A.shape[1] if n is None else n
        self.num_panels = padded_n(self.n, panel) // panel

    def read_chunk(self, lo_panel: int, num_panels: int) -> Tuple[int, jax.Array]:
        """Slice the window out of ``A``, zero-padding past column ``n``."""
        start = lo_panel * self.panel
        stop = min(start + num_panels * self.panel, self.n)
        chunk = self.A[:, start:stop]
        want = num_panels * self.panel
        if chunk.shape[1] < want:
            chunk = jnp.pad(chunk, ((0, 0), (0, want - chunk.shape[1])))
        return lo_panel, chunk


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Deterministic per-panel fault schedule (all panel ids are global).

    One-shot faults (crash, drop, duplicate, straggle) fire on the first
    read that covers the panel and never again — replay after a restart
    sees a healthy source, exactly like a real transient. ``corrupt_panels``
    is *persistent*: every read of those panels returns NaN data, so the
    quarantine guard's outcome is identical on replay.
    """

    crash_at_panel: Optional[int] = None  # raise InjectedCrash before consuming it
    corrupt_panels: Tuple[int, ...] = ()  # NaN-fill these panels (persistent)
    drop_panels: Tuple[int, ...] = ()  # first read covering it raises (one-shot)
    duplicate_panels: Tuple[int, ...] = ()  # first read re-delivers the previous chunk
    straggler_panels: Tuple[int, ...] = ()  # first read sleeps straggler_delay_s
    straggler_delay_s: float = 0.01


class FaultInjector:
    """Wrap a :class:`PanelSource` with a :class:`FaultPlan`.

    Faults fire at the read boundary — the engine and driver under test are
    unmodified production code. The injector is stateful (one-shot flags,
    last-delivery buffer for duplicates) and is deliberately *shared* across
    restarts within a process so a replayed read sees the post-fault
    source.
    """

    def __init__(self, source: PanelSource, plan: FaultPlan):
        self.source = source
        self.plan = plan
        self.panel = source.panel
        self.n = source.n
        self.num_panels = source.num_panels
        self._crashed = False
        self._dropped: set = set()
        self._duplicated: set = set()
        self._delayed: set = set()
        self._last: Optional[Tuple[int, jax.Array]] = None

    def read_chunk(self, lo_panel: int, num_panels: int) -> Tuple[int, jax.Array]:
        """Delegate to the wrapped source, firing any scheduled faults
        whose panel falls inside the requested window."""
        covered = range(lo_panel, lo_panel + num_panels)
        plan = self.plan
        if (
            plan.crash_at_panel is not None
            and plan.crash_at_panel in covered
            and not self._crashed
        ):
            self._crashed = True
            raise InjectedCrash(
                f"injected crash before consuming panel {plan.crash_at_panel}"
            )
        for t in plan.drop_panels:
            if t in covered and t not in self._dropped:
                self._dropped.add(t)
                raise TransientReadError(f"injected drop of panel {t}")
        for t in plan.straggler_panels:
            if t in covered and t not in self._delayed:
                self._delayed.add(t)
                time.sleep(plan.straggler_delay_s)
        for t in plan.duplicate_panels:
            if t in covered and t not in self._duplicated and self._last is not None:
                self._duplicated.add(t)
                return self._last  # stale tag — driver detects and re-requests
        tag, chunk = self.source.read_chunk(lo_panel, num_panels)
        for t in plan.corrupt_panels:
            if t in covered:
                rel = (t - lo_panel) * self.panel
                chunk = chunk.at[:, rel : rel + self.panel].set(jnp.nan)
        self._last = (tag, chunk)
        return tag, chunk


@dataclasses.dataclass
class StreamReport:
    """Host-side outcome of one resilient drive (per worker when sharded)."""

    chunks: int = 0  # chunks consumed (including replayed ones)
    panels_consumed: int = 0  # absolute cursor after the drive
    retries: int = 0  # dropped/duplicated deliveries re-requested
    restarts: int = 0  # in-process restore-and-replay cycles
    checkpoints: int = 0  # checkpoints written
    quarantined: Optional[int] = None  # final in-scan quarantine count (if armed)
    resumed_from: Optional[int] = None  # cursor restored at entry (cross-invocation)


def save_stream_state(
    directory: str,
    state: PanelState,
    panels_consumed: int,
    *,
    keep_last: int = 3,
    extra: Optional[dict] = None,
    durable: bool = True,
    async_: bool = False,
):
    """Checkpoint a :class:`PanelState` with its ``panels_consumed`` cursor.

    The step id *is* the cursor, so ``latest_step`` is "most panels
    consumed" and replay-from-latest is minimal. ``ops``/``n`` are static
    metadata and live in the restore template, not on disk. A PanelState
    is O(sketch size), so the **packed** single-file checkpoint layout is
    used — one write + one rename per save instead of one file per leaf.
    ``async_=True`` snapshots to host synchronously (donation safety) and
    writes on a worker thread, returning the Thread; ``durable=False``
    skips the fsync (process-crash atomicity only)."""
    meta = {
        "panels_consumed": int(panels_consumed),
        "stream": state.ops.name,
        **(extra or {}),
    }
    return save(
        directory, int(panels_consumed), state, extra=meta, keep_last=keep_last,
        durable=durable, async_=async_, pack=True,
    )




def restore_stream_state(directory: str, template: PanelState, *, step=None):
    """Restore ``(state, panels_consumed, extra)`` from the newest intact
    checkpoint.

    ``template`` supplies the pytree structure and the static ``ops``/``n``
    metadata (its array values are ignored); the returned state is freshly
    materialized from disk — never a donated buffer — so it can go straight
    back into the donating scan path."""
    tree, extra, step = restore(directory, template, step=step)
    return tree, int(extra.get("panels_consumed", step)), extra


def _read_with_retry(source, lo_panel, num, *, max_retries, backoff_s, report, reg):
    """One chunk read with bounded retry: transient errors back off
    exponentially, stale tags (duplicated delivery) re-request immediately."""
    for attempt in range(max_retries + 1):
        try:
            tag, chunk = source.read_chunk(lo_panel, num)
        except TransientReadError:
            if attempt >= max_retries:
                raise
            report.retries += 1
            reg.inc("stream/resilient/retries")
            if backoff_s:
                time.sleep(backoff_s * (2**attempt))
            continue
        if tag != lo_panel:
            if attempt >= max_retries:
                raise TransientReadError(
                    f"chunk at panel {lo_panel} kept arriving with stale tag {tag}"
                )
            report.retries += 1
            reg.inc("stream/resilient/retries")
            continue
        return chunk
    raise TransientReadError(f"chunk at panel {lo_panel} failed after retries")


def run_resilient_stream(
    state: PanelState,
    source: PanelSource,
    *,
    chunk_panels: int = 4,
    start_panel: Optional[int] = None,
    stop_panel: Optional[int] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 2,
    keep_last: int = 3,
    max_retries: int = 3,
    backoff_s: float = 0.0,
    max_restarts: int = 0,
    strict: bool = False,
    quarantine: bool = False,
    durable: bool = False,
    resume: bool = True,
) -> Tuple[PanelState, StreamReport]:
    """Drive panels ``[start_panel, stop_panel)`` of ``source`` through the
    engine with checkpoint/retry/restart handling.

    Chunks of ``chunk_panels`` panels run through the engine's donating
    scan program (:func:`repro.stream.engine.scan_chunk`); the input
    ``state`` is *consumed* per the engine contract — keep only the
    returned state. Factor bits depend only on the panel sequence, so two
    drives over the same source at the same ``chunk_panels`` produce
    bitwise-identical factors regardless of how many crash/restore cycles
    either suffered (the Ψ estimator folds once per chunk, hence the "same
    cadence" clause).

    * ``ckpt_dir`` — enables checkpointing every ``ckpt_every`` chunks plus
      once at completion, and **resume**: if the directory already holds an
      intact checkpoint, the drive restores it and replays only unconsumed
      panels (the passed ``state`` then only serves as the restore
      template).
    * ``max_restarts`` — in-process restore-and-replay budget for
      :class:`InjectedCrash`; beyond it (or without a budget) the crash
      propagates and a later invocation resumes from ``ckpt_dir``.
    * ``resume=False`` treats ``ckpt_dir`` as write-only: checkpoints left
      by an earlier drive are ignored (and overwritten in place), and
      in-process restarts/rollbacks only ever restore checkpoints written
      by *this* drive. Use it to re-run a fresh drive into the same
      directory — repeated benchmark drives would otherwise resume the
      previous run's final checkpoint and no-op.
    * ``quarantine`` — arm the in-scan non-finite guard
      (:func:`~repro.stream.engine.with_quarantine`); with ``strict=True``
      a quarantined panel instead rolls back to the last checkpoint and
      raises :class:`QuarantineAbort`.
    * Checkpoints use the packed single-file layout (one write + one
      rename per save — a PanelState is only O(sketch size), so the
      per-leaf directory layout's syscall count would dominate at stream
      cadence). ``durable`` defaults to False because the subsystem's
      fault model is process death (``InjectedCrash``), where the rename
      commit alone is atomic; pass True to also survive power loss at the
      price of an fsync per save.
    """
    panel = source.panel
    if quarantine or strict:
        state = with_quarantine(state)
    start = int(state.offset) // panel if start_panel is None else start_panel
    stop = source.num_panels if stop_panel is None else stop_panel
    report = StreamReport()
    reg = default_registry()
    # pristine copy for scratch restarts / rollbacks and as restore template
    # (restore only reads leaf shape/dtype, never the — possibly donated —
    # buffers, but scratch restart needs live buffers of its own)
    state0 = fresh_pytree(state)
    cursor = start
    last_saved: Optional[int] = None  # newest step written by THIS drive
    if resume and ckpt_dir is not None and latest_step(ckpt_dir) is not None:
        state, cursor, _ = restore_stream_state(ckpt_dir, state0)
        report.resumed_from = cursor
        last_saved = cursor

    def _rollback_step() -> Optional[int]:
        """The step a restart/rollback may restore: newest on disk when
        resuming, else only what this drive has written."""
        if ckpt_dir is None:
            return None
        return latest_step(ckpt_dir) if resume else last_saved

    armed = state.quarantined is not None
    q_seen = int(state.quarantined) if armed else 0
    chunks_since_ckpt = 0
    with span(f"stream/{state.ops.name}/resilient"):
        while cursor < stop:
            num = min(chunk_panels, stop - cursor)
            try:
                chunk = _read_with_retry(
                    source,
                    cursor,
                    num,
                    max_retries=max_retries,
                    backoff_s=backoff_s,
                    report=report,
                    reg=reg,
                )
                state = engine._scan_stream_chunk(state, chunk, panel=panel)
            except InjectedCrash:
                if report.restarts >= max_restarts:
                    raise
                report.restarts += 1
                reg.inc("stream/resilient/restarts")
                step = _rollback_step()
                if step is not None:
                    state, cursor, _ = restore_stream_state(ckpt_dir, state0, step=step)
                else:
                    state, cursor = fresh_pytree(state0), start
                q_seen = int(state.quarantined) if armed else 0
                chunks_since_ckpt = 0
                continue
            report.chunks += 1
            if armed:
                q_now = int(state.quarantined)
                if q_now > q_seen:
                    reg.inc("stream/resilient/quarantined", q_now - q_seen)
                    if strict:
                        step = _rollback_step()
                        if step is not None:
                            st, cur, _ = restore_stream_state(
                                ckpt_dir, state0, step=step
                            )
                        else:
                            st, cur = fresh_pytree(state0), start
                        raise QuarantineAbort(
                            f"non-finite panel in chunk [{cursor}, {cursor + num}); "
                            f"state rolled back to panel {cur}",
                            state=st,
                            panels_consumed=cur,
                        )
                q_seen = q_now
            cursor += num
            chunks_since_ckpt += 1
            if ckpt_dir is not None and (
                chunks_since_ckpt >= ckpt_every or cursor >= stop
            ):
                save_stream_state(
                    ckpt_dir, state, cursor, keep_last=keep_last, durable=durable
                )
                last_saved = cursor
                report.checkpoints += 1
                reg.inc("stream/resilient/checkpoints")
                chunks_since_ckpt = 0
    report.panels_consumed = cursor
    if armed:
        report.quarantined = q_seen
    return state, report


def run_resilient_sharded_stream(
    state0: PanelState,
    source: PanelSource,
    num_workers: int,
    *,
    ckpt_dir: Optional[str] = None,
    chunk_panels: int = 4,
    ckpt_every: int = 2,
    keep_last: int = 3,
    max_retries: int = 3,
    backoff_s: float = 0.0,
    max_restarts: int = 0,
    strict: bool = False,
    quarantine: bool = False,
    durable: bool = False,
    resume: bool = True,
) -> Tuple[PanelState, List[StreamReport]]:
    """Resilient counterpart of
    :func:`~repro.stream.distributed.simulate_sharded_stream`: every worker
    drives its contiguous panel-aligned range through
    :func:`run_resilient_stream` with its **own** checkpoint directory
    (``<ckpt_dir>/worker_<w>``), then the worker states merge exactly as
    the healthy path does (:func:`~repro.stream.distributed.merge_states`).

    A crash in one worker therefore loses at most that worker's
    panels-since-checkpoint: re-invoking with the same ``ckpt_dir`` resumes
    every completed worker from its final checkpoint (replaying nothing),
    restores the crashed worker's range, and re-merges — bitwise parity
    with the all-healthy run, including against ``mesh_sharded_stream``
    (``tests/test_resilient.py`` asserts both at 2/4 workers).

    ``state0`` must be fresh (offset 0) and is used purely as a template —
    each worker streams a deep copy, so ``state0`` survives a crashed
    invocation and can be passed again to resume.
    """
    if int(state0.offset) != 0:
        raise ValueError(
            "run_resilient_sharded_stream needs a fresh state: every worker "
            f"clones state0's accumulators (offset={int(state0.offset)})"
        )
    panel = source.panel
    if quarantine or strict:
        state0 = with_quarantine(state0)
    ops = state0.ops
    ranges = shard_panel_ranges(source.n, panel, num_workers)
    ctx0 = state0.ctx
    if ops.prep_shard is not None:
        ctx0 = ops.prep_shard(ctx0, num_workers)
    state0 = dataclasses.replace(state0, ctx=ctx0)
    shards: List[PanelState] = []
    reports: List[StreamReport] = []
    for w, (lo, hi) in enumerate(ranges):
        ctx = ctx0
        if ops.bind_shard is not None:
            ctx = ops.bind_shard(ctx, jnp.asarray(w, jnp.int32))
        st = fresh_pytree(
            dataclasses.replace(state0, ctx=ctx, offset=jnp.asarray(lo, jnp.int32))
        )
        lo_p = lo // panel
        hi_p = lo_p + padded_n(hi - lo, panel) // panel
        wdir = os.path.join(ckpt_dir, f"worker_{w:02d}") if ckpt_dir else None
        st, rep = run_resilient_stream(
            st,
            source,
            chunk_panels=chunk_panels,
            start_panel=lo_p,
            stop_panel=hi_p,
            ckpt_dir=wdir,
            ckpt_every=ckpt_every,
            keep_last=keep_last,
            max_retries=max_retries,
            backoff_s=backoff_s,
            max_restarts=max_restarts,
            strict=strict,
            quarantine=quarantine,
            durable=durable,
            resume=resume,
        )
        shards.append(st)
        reports.append(rep)
    return merge_states(shards), reports
