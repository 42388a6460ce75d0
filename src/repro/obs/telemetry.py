"""In-scan telemetry: a per-panel diagnostics pytree carried through the
streaming engine.

The panel engine (:mod:`repro.stream.engine`) runs the whole stream as one
donated-buffer ``lax.scan`` per chunk — there is no place to hang a host
callback without breaking scan compilation. Telemetry therefore rides *in*
the scan carry: a :class:`TelemetryFrame` of **fixed-shape** arrays lives in
``PanelState.tel`` and an application-chosen ``PanelOps.telemetry`` hook
folds one panel's diagnostics into it per engine step. Everything is indexed
by the **global panel id** ``t = offset // panel``, which gives the frame
three properties the rest of the repo's streaming algebra already relies on:

* *opt-in and inert*: ``tel=None`` (the default) contributes no pytree
  leaves, so the scan program, donation layout and jit cache keys are
  byte-identical to an untelemetered stream (asserted on the compiled
  program text in ``tests/test_obs.py``);
* *read-only with respect to the factors*: the hook runs after the C/R/M
  updates and only writes ``tel`` — factors are bit-identical with telemetry
  on or off;
* *distributed-exact*: per-panel slots are written by exactly one worker
  (workers own disjoint panel ranges), and the running sums
  (``energy_mass``, ``psi``, ``panels_seen``) are sums of per-panel
  contributions — so worker frames merge by summation
  (:meth:`TelemetryFrame.merge` in-process, :meth:`TelemetryFrame.collective`
  under ``shard_map``) with the same disjoint-write algebra as C/R/M.

The frame also carries the **a-posteriori error estimator**'s test sketch:
``psi`` accumulates ``Ψ = A Ω_test``, folded by the engine as **one GEMM per
consumed chunk** (:func:`fold_psi_chunk` — a rank-``q`` matmul inside the
scan carry costs ~3× its standalone wall-time, so the engine hoists it out
of the scan body; the chunk is consumed atomically by the same program, so
``Ψ`` and the factors still cover exactly the same columns at every program
boundary). :func:`repro.obs.error_estimate.estimate_rel_error` compares
``Ψ`` against the factors' action on the same ``Ω_test`` — see
``docs/observability.md`` for the Tropp test-sketch argument.

Per-panel values are **panel-local**, never cumulative (a cumulative value
would break the merge-by-sum contract): ``admitted[t]`` is the number of
columns admitted *in* panel ``t``, ``occupancy[t]`` the (worker-local) slot
occupancy *after* panel ``t``, and so on. Decode ``events`` with the
``EVENT_*`` bitmask constants.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TelemetryFrame",
    "init_telemetry",
    "adaptive_stream_telemetry",
    "fixed_stream_telemetry",
    "fold_psi_chunk",
    "telemetry_summary",
    "EVENT_ADMIT",
    "EVENT_EVICT",
    "EVENT_ROW_ADMIT",
    "EVENT_BUDGET_FULL",
    "EVENT_QUARANTINED",
]

# ``events`` bitmask: what happened in panel t.
EVENT_ADMIT = 1  # ≥1 column admitted
EVENT_EVICT = 2  # ≥1 column evicted (adaptive swap_gain policy)
EVENT_ROW_ADMIT = 4  # ≥1 row admitted (adaptive rows)
EVENT_BUDGET_FULL = 8  # the worker's column budget is full after this panel
EVENT_QUARANTINED = 16  # panel carried NaN/Inf and was zero-scaled in-scan

_QUANTILES = (0.0, 25.0, 50.0, 75.0, 100.0)


@dataclasses.dataclass(frozen=True)
class TelemetryFrame:
    """Fixed-shape per-panel diagnostics, carried in ``PanelState.tel``.

    ``P = padded_n(n, panel) // panel`` panel slots; all per-panel arrays are
    indexed by the global panel id and written by exactly one worker
    (disjoint panel ranges), so frames merge/psum by summation. ``omega`` is
    the estimator's test sketch — a constant, bit-identical on every worker,
    excluded from every reduction.
    """

    admitted: jax.Array  # (P,) int32 — columns admitted in panel t
    evicted: jax.Array  # (P,) int32 — columns evicted in panel t
    rows_admitted: jax.Array  # (P,) int32 — rows admitted in panel t
    occupancy: jax.Array  # (P,) int32 — filled slots after panel t (worker-local)
    events: jax.Array  # (P,) int32 — EVENT_* bitmask for panel t
    panel_scores: jax.Array  # (P, panel) f32 — raw per-panel column scores (padded cols 0)
    panel_energy: jax.Array  # (P,) f32 — Σ sketched column energy of panel t
    energy_mass: jax.Array  # () f32 — running Σ panel_energy over seen panels
    psi: jax.Array  # (m, q) f32 — running test sketch Ψ = A Ω_test
    omega: jax.Array  # (n_pad, q) f32 — the test sketch Ω_test (constant)
    panels_seen: jax.Array  # () int32 — panels folded into this frame
    panel: int  # static: panel width the frame is indexed by
    n: int  # static: true column count of the stream

    def merge(self, frames):
        """Sum worker frames into the single-stream frame (in-process merge).

        Per-panel slots are disjoint writes into zero-init arrays and the
        scalars/``psi`` are running sums, so summation is exact — the same
        algebra :func:`repro.stream.distributed.merge_states` uses for
        C/R/M. ``omega`` is identical on every worker and kept once.
        """

        def tot(get):
            return sum((get(f) for f in frames[1:]), get(frames[0]))

        return dataclasses.replace(
            frames[0],
            admitted=tot(lambda f: f.admitted),
            evicted=tot(lambda f: f.evicted),
            rows_admitted=tot(lambda f: f.rows_admitted),
            occupancy=tot(lambda f: f.occupancy),
            events=tot(lambda f: f.events),
            panel_scores=tot(lambda f: f.panel_scores),
            panel_energy=tot(lambda f: f.panel_energy),
            energy_mass=tot(lambda f: f.energy_mass),
            psi=tot(lambda f: f.psi),
            panels_seen=tot(lambda f: f.panels_seen),
            omega=self.omega,
        )

    def collective(self, axis) -> "TelemetryFrame":
        """``shard_map`` mirror of :meth:`merge`: one psum per leaf, with the
        constant ``omega`` excluded (reducing it would scale it by W)."""
        ps = lambda x: jax.lax.psum(x, axis)  # noqa: E731 — local shorthand
        return dataclasses.replace(
            self,
            admitted=ps(self.admitted),
            evicted=ps(self.evicted),
            rows_admitted=ps(self.rows_admitted),
            occupancy=ps(self.occupancy),
            events=ps(self.events),
            panel_scores=ps(self.panel_scores),
            panel_energy=ps(self.panel_energy),
            energy_mass=ps(self.energy_mass),
            psi=ps(self.psi),
            panels_seen=ps(self.panels_seen),
            omega=self.omega,
        )


jax.tree_util.register_dataclass(
    TelemetryFrame,
    data_fields=[
        "admitted", "evicted", "rows_admitted", "occupancy", "events",
        "panel_scores", "panel_energy", "energy_mass", "psi", "omega",
        "panels_seen",
    ],
    meta_fields=["panel", "n"],
)


def init_telemetry(key, m: int, n: int, panel: int, *, q: int = 16) -> TelemetryFrame:
    """Allocate a zero :class:`TelemetryFrame` + draw the estimator sketch.

    Args:
        key: PRNG key for the test sketch ``Ω_test`` — must be independent of
            the state's core sketches (the init functions fold a constant
            into their own key), or the estimator loses its held-out status.
        m: row count of the stream (``n`` for symmetric/kernel streams).
        n: true column count of the stream.
        panel: fixed panel width the stream will be driven with — the frame
            is indexed by ``offset // panel``, so driving the state with a
            different width scrambles the per-panel slots.
        q: test-sketch width. The estimator's relative accuracy concentrates
            like ``O(1/√q)`` (Tropp et al. 2017, §6) — the default 16 keeps
            it comfortably inside the 2× acceptance band at negligible cost
            (one rank-``q`` panel matmul per step).

    Returns:
        A zeroed frame with ``Ω_test ~ N(0,1)`` rows (padded rows ≥ ``n``
        zeroed, so zero-padded tail panels contribute nothing to ``Ψ``).
    """
    n_pad = ((n + panel - 1) // panel) * panel
    num_panels = n_pad // panel
    omega = jax.random.normal(key, (n_pad, q), jnp.float32)
    omega = jnp.where(jnp.arange(n_pad)[:, None] < n, omega, 0.0)
    return TelemetryFrame(
        admitted=jnp.zeros((num_panels,), jnp.int32),
        evicted=jnp.zeros((num_panels,), jnp.int32),
        rows_admitted=jnp.zeros((num_panels,), jnp.int32),
        occupancy=jnp.zeros((num_panels,), jnp.int32),
        events=jnp.zeros((num_panels,), jnp.int32),
        panel_scores=jnp.zeros((num_panels, panel), jnp.float32),
        panel_energy=jnp.zeros((num_panels,), jnp.float32),
        energy_mass=jnp.zeros((), jnp.float32),
        psi=jnp.zeros((m, q), jnp.float32),
        omega=omega,
        panels_seen=jnp.zeros((), jnp.int32),
        panel=panel,
        n=n,
    )


def _fold_panel(tel: TelemetryFrame, A_L, sc_a, scores, off):
    """Application-independent slice of the per-panel fold: raw score row
    and energy mass. Returns the updated frame and the global panel id ``t``.

    Deliberately cheap — everything here lives in the scan carry, where ops
    cost ~3–6× their standalone wall-time on a CPU. Score *quantiles* are therefore not computed in-scan: the raw
    ``(panel,)`` score row is stored (one dynamic-update-slice) and
    :func:`telemetry_summary` takes nearest-rank quantiles host-side. The
    estimator's ``Ψ`` update is likewise hoisted out of the scan body — the
    engine folds it once per chunk via :func:`fold_psi_chunk`."""
    L = A_L.shape[1]
    t = off // tel.panel
    if scores is None:
        y = sc_a.astype(jnp.float32)
        energy = jnp.sum(y * y, axis=0)  # (L,) sketched column energy
        svec = energy
    else:
        svec, energy = (s.astype(jnp.float32) for s in scores)
    valid = (off + jnp.arange(L)) < tel.n  # mask zero-padded tail columns
    energy = jnp.where(valid, energy, 0.0)
    tel = dataclasses.replace(
        tel,
        panel_scores=tel.panel_scores.at[t].set(jnp.where(valid, svec, 0.0)),
        panel_energy=tel.panel_energy.at[t].set(jnp.sum(energy)),
        energy_mass=tel.energy_mass + jnp.sum(energy),
        panels_seen=tel.panels_seen + 1,
    )
    return tel, t


def fold_psi_chunk(tel: TelemetryFrame, A_block, off) -> TelemetryFrame:
    """Fold a consumed block of columns into the estimator sketch:
    ``Ψ += A_block · Ω_test[off : off+W]`` as **one** GEMM.

    Called by the engine's scan entry points (and the per-panel fallback
    driver) on the whole block a program consumes, *outside* the
    ``lax.scan`` body — same result as a per-panel fold up to float
    summation order, at the standalone-GEMM price instead of the in-carry
    price. Zero-padded tail columns multiply zeroed ``Ω_test`` rows, so
    padding stays exact. ``off`` may be a tracer (the state's running
    offset)."""
    w = jax.lax.dynamic_slice_in_dim(tel.omega, off, A_block.shape[1], axis=0)
    return dataclasses.replace(tel, psi=tel.psi + A_block.astype(jnp.float32) @ w)


def fixed_stream_telemetry(tel, ctx, ctx_new, A_L, sc_a, scores, off):
    """``PanelOps.telemetry`` hook for the fixed-index plug-ins
    (``streaming_cur``, ``streaming_spsd``): "admission" is a selected
    column's panel streaming by, derived from the static ``col_idx`` table
    (identical on every worker, so per-panel counts are global)."""
    tel, t = _fold_panel(tel, A_L, sc_a, scores, off)
    L = A_L.shape[1]
    idx = ctx_new.col_idx
    adm = jnp.sum((idx >= off) & (idx < off + L)).astype(jnp.int32)
    occ = jnp.sum((idx >= 0) & (idx < off + L)).astype(jnp.int32)
    full = occ >= idx.shape[0]
    events = jnp.where(adm > 0, EVENT_ADMIT, 0) + jnp.where(full, EVENT_BUDGET_FULL, 0)
    return dataclasses.replace(
        tel,
        admitted=tel.admitted.at[t].set(adm),
        occupancy=tel.occupancy.at[t].set(occ),
        events=tel.events.at[t].set(events.astype(jnp.int32)),
    )


def adaptive_stream_telemetry(tel, ctx, ctx_new, A_L, sc_a, scores, off):
    """``PanelOps.telemetry`` hook for the adaptive policy
    (``adaptive_cur``, ``adaptive_spsd``): admission/eviction deltas are read
    off the pre-/post-update :class:`~repro.stream.adaptive.AdaptiveCURCtx`
    counters. Occupancy is **worker-local** under sharding (each worker
    audits its own slot range); merged frames keep the admitting worker's
    view, which is the post-hoc audit trail eviction analysis needs."""
    tel, t = _fold_panel(tel, A_L, sc_a, scores, off)
    adm = (ctx_new.n_filled - ctx.n_filled).astype(jnp.int32)
    ev = (ctx_new.n_evicted - ctx.n_evicted).astype(jnp.int32)
    occ = (ctx_new.n_filled - ctx_new.slot_lo).astype(jnp.int32)
    full = ctx_new.n_filled >= ctx_new.slot_lo + ctx_new.c_local
    if ctx_new.rows is not None:
        radm = (ctx_new.rows.n_filled - ctx.rows.n_filled).astype(jnp.int32)
    else:
        radm = jnp.zeros((), jnp.int32)
    events = (
        jnp.where(adm > 0, EVENT_ADMIT, 0)
        + jnp.where(ev > 0, EVENT_EVICT, 0)
        + jnp.where(radm > 0, EVENT_ROW_ADMIT, 0)
        + jnp.where(full, EVENT_BUDGET_FULL, 0)
    )
    return dataclasses.replace(
        tel,
        admitted=tel.admitted.at[t].set(adm),
        evicted=tel.evicted.at[t].set(ev),
        rows_admitted=tel.rows_admitted.at[t].set(radm),
        occupancy=tel.occupancy.at[t].set(occ),
        events=tel.events.at[t].set(events.astype(jnp.int32)),
    )


def telemetry_summary(state_or_tel) -> dict:
    """Host-side audit view of a streamed frame (the post-hoc eviction audit).

    Accepts a :class:`~repro.stream.engine.PanelState` (reads ``.tel``) or a
    :class:`TelemetryFrame`. Returns plain numpy/python values: the per-panel
    arrays, decoded event names per panel, and scalar totals — ready for
    :meth:`repro.obs.metrics.MetricsRegistry.record_stream_telemetry` or a
    notebook.
    """
    tel = getattr(state_or_tel, "tel", state_or_tel)
    if tel is None:
        raise ValueError("state has no telemetry (init with telemetry=True)")
    names = (
        (EVENT_ADMIT, "admit"), (EVENT_EVICT, "evict"),
        (EVENT_ROW_ADMIT, "row_admit"), (EVENT_BUDGET_FULL, "budget_full"),
        (EVENT_QUARANTINED, "quarantined"),
    )
    events = np.asarray(tel.events)
    # Nearest-rank score quantiles per panel, computed here (host-side)
    # from the raw in-scan score rows — see _fold_panel for why the scan
    # does not sort. Valid-count per panel comes from the global column
    # range; unseen panels are all-zero rows and quantile to zeros.
    scores = np.asarray(tel.panel_scores, np.float32)
    P, L = scores.shape
    score_q = np.zeros((P, len(_QUANTILES)), np.float32)
    for t in range(P):
        cnt = int(np.clip(tel.n - t * tel.panel, 0, L))
        if cnt > 0:
            srt = np.sort(scores[t, :cnt])
            ranks = np.clip(
                np.round(np.asarray(_QUANTILES) / 100.0 * (cnt - 1)), 0, cnt - 1
            ).astype(np.int64)
            score_q[t] = srt[ranks]
    return {
        "admitted": np.asarray(tel.admitted),
        "evicted": np.asarray(tel.evicted),
        "rows_admitted": np.asarray(tel.rows_admitted),
        "occupancy": np.asarray(tel.occupancy),
        "panel_scores": scores,
        "score_q": score_q,
        "panel_energy": np.asarray(tel.panel_energy),
        "events": [[nm for bit, nm in names if e & bit] for e in events],
        "energy_mass": float(tel.energy_mass),
        "panels_seen": int(tel.panels_seen),
        "total_admitted": int(np.sum(np.asarray(tel.admitted))),
        "total_evicted": int(np.sum(np.asarray(tel.evicted))),
        "total_rows_admitted": int(np.sum(np.asarray(tel.rows_admitted))),
    }
