"""Observability for the streaming engine: in-scan telemetry, a-posteriori
error estimation, and host-side metrics/spans.

Three layers, strictly opt-in at every level:

* :mod:`repro.obs.telemetry` — a fixed-shape per-panel diagnostics pytree
  (:class:`TelemetryFrame`) carried through the engine's ``lax.scan`` via
  the ``PanelOps.telemetry`` hook; off by default (``tel=None`` ⇒ the scan
  program is byte-identical to an untelemetered stream).
* :mod:`repro.obs.error_estimate` — ``estimate_rel_error``: a running
  relative Frobenius-error estimate from the independent test sketch
  ``Ψ = A Ω_test`` the telemetry frame maintains in-stream (Tropp et al.'s
  a-posteriori argument; no second pass over ``A``).
* :mod:`repro.obs.metrics` / :mod:`repro.obs.spans` — a host-side registry
  of counters/gauges/histograms with a JSON-lines dump, and host spans that
  always annotate the ``jax.profiler`` trace and are recorded (with their
  parent span) when the registry is enabled; the process default registry
  starts disabled.

Enable per stream with ``telemetry=True`` on the plug-in inits
(``adaptive_cur_init``, ``streaming_cur_init``, ``streaming_spsd_init``,
``adaptive_spsd_init``); see ``docs/observability.md`` for the metric
catalog and the estimator derivation.
"""

from .error_estimate import estimate_rel_error, low_rank_apply
from .metrics import MetricsRegistry, SpanRecord, default_registry, set_registry
from .spans import span
from .telemetry import (
    EVENT_ADMIT,
    EVENT_BUDGET_FULL,
    EVENT_EVICT,
    EVENT_QUARANTINED,
    EVENT_ROW_ADMIT,
    TelemetryFrame,
    adaptive_stream_telemetry,
    fixed_stream_telemetry,
    init_telemetry,
    telemetry_summary,
)

__all__ = [
    "TelemetryFrame",
    "init_telemetry",
    "adaptive_stream_telemetry",
    "fixed_stream_telemetry",
    "telemetry_summary",
    "EVENT_ADMIT",
    "EVENT_EVICT",
    "EVENT_ROW_ADMIT",
    "EVENT_BUDGET_FULL",
    "EVENT_QUARANTINED",
    "estimate_rel_error",
    "low_rank_apply",
    "MetricsRegistry",
    "SpanRecord",
    "default_registry",
    "set_registry",
    "span",
]
