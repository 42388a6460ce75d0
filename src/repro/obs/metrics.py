"""Host-side metrics registry: counters / gauges / histograms + JSON-lines.

The in-scan half of observability (:mod:`repro.obs.telemetry`) lives inside
the jitted stream and is device-resident by design. This module is the
*host* half: a process-local :class:`MetricsRegistry` that benchmark
drivers, serving paths and training loops write structured metrics into,
and that dumps one JSON object per line (``dump_jsonl``) so CI can archive
it next to the ``BENCH_*.json`` artifacts.

Three instrument kinds, all keyed by a flat string name (convention:
``layer/subject_unit``, e.g. ``serve/kv_rel_err``, ``stream/admitted``):

* **counter** — monotonically increasing total (:meth:`MetricsRegistry.inc`);
* **gauge** — last-write-wins scalar (:meth:`MetricsRegistry.set_gauge`);
* **histogram** — every observation retained, summarized at dump time with
  count/mean/min/p50/p90/max (:meth:`MetricsRegistry.observe`).

The registry also collects the span records emitted by
:func:`repro.obs.spans.span` (wall-clock + parent span) — one shared sink
so a single ``dump_jsonl`` captures the whole run.

The module-level default registry starts **disabled**: every instrument
method is a cheap early-return, so library code can emit unconditionally
(``serve/kv_compress``'s per-call metrics, the engine's spans) without
taxing production paths. Opt in per process with ``set_registry`` or
``default_registry().enabled = True``.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

import numpy as np

__all__ = [
    "MetricsRegistry",
    "SpanRecord",
    "default_registry",
    "set_registry",
]


@dataclasses.dataclass
class SpanRecord:
    """One closed :func:`repro.obs.spans.span`: wall-clock + parent span.

    ``start`` is seconds since the registry's epoch (its construction time),
    ``duration`` seconds of host wall-clock — dispatch time, not device time,
    unless the caller blocked on the result inside the span. ``parent`` is
    the name of the span that was open around it (``None`` at top level), so
    a layer's self time is its duration less its children's.
    """

    name: str
    start: float
    duration: float
    parent: Optional[str]


class MetricsRegistry:
    """Process-local sink for counters, gauges, histograms and spans.

    Disabled registries (``enabled=False``) turn every write into an
    early-return, so instrumented library code costs one attribute check
    when observability is off.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.counters: dict = {}
        self.gauges: dict = {}
        self.histograms: dict = {}
        self.spans: list = []
        self.epoch = time.perf_counter()
        self._span_stack: list = []  # open span names (parent tracking)

    def inc(self, name: str, value: float = 1) -> None:
        """Add ``value`` to counter ``name`` (created at 0)."""
        if not self.enabled:
            return
        self.counters[name] = self.counters.get(name, 0) + value

    def set_gauge(self, name: str, value: float) -> None:
        """Set gauge ``name`` (last write wins)."""
        if not self.enabled:
            return
        self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        """Record one observation into histogram ``name``."""
        if not self.enabled:
            return
        self.histograms.setdefault(name, []).append(float(value))

    def histogram_summary(self, name: str) -> dict:
        """count/mean/min/p50/p90/max summary of histogram ``name``."""
        obs = np.asarray(self.histograms[name], np.float64)
        return {
            "count": int(obs.size),
            "mean": float(obs.mean()),
            "min": float(obs.min()),
            "p50": float(np.percentile(obs, 50)),
            "p90": float(np.percentile(obs, 90)),
            "max": float(obs.max()),
        }

    def record_stream_telemetry(self, state_or_tel, prefix: str = "stream") -> None:
        """Fold a streamed :class:`~repro.obs.telemetry.TelemetryFrame` into
        host metrics: scalar totals become counters/gauges, the per-panel
        score medians and energies become histograms (one observation per
        seen panel). One device→host transfer per array, after the stream —
        never inside it."""
        if not self.enabled:
            return
        from .telemetry import telemetry_summary

        s = telemetry_summary(state_or_tel)
        self.inc(f"{prefix}/admitted", s["total_admitted"])
        self.inc(f"{prefix}/evicted", s["total_evicted"])
        self.inc(f"{prefix}/rows_admitted", s["total_rows_admitted"])
        self.inc(f"{prefix}/panels", s["panels_seen"])
        self.set_gauge(f"{prefix}/energy_mass", s["energy_mass"])
        occ = s["occupancy"]
        if occ.size:
            self.set_gauge(f"{prefix}/final_occupancy", float(occ[-1]))
        for t in range(s["panels_seen"]):
            self.observe(f"{prefix}/panel_score_p50", float(s["score_q"][t, 2]))
            self.observe(f"{prefix}/panel_energy", float(s["panel_energy"][t]))

    def record_kv_compression(self, errs, *, ratio=None, ranks=None, prefix="serve") -> None:
        """Fold a head-batch of KV-compression quality metrics into the host
        registry with **one** device→host transfer per array: ``errs`` (any
        shape of per-head relative reconstruction errors) feeds the
        ``{prefix}/kv_rel_err`` histogram and the
        ``{prefix}/kv_heads_compressed`` counter; optional ``ratio`` (host
        scalar) sets the ``{prefix}/kv_compression_ratio`` gauge; optional
        ``ranks`` (adaptive per-head allocations) feed the
        ``{prefix}/kv_head_rank`` histogram."""
        if not self.enabled:
            return
        e = np.asarray(errs, np.float64).ravel()  # the single transfer
        for v in e:
            self.observe(f"{prefix}/kv_rel_err", float(v))
        self.inc(f"{prefix}/kv_heads_compressed", int(e.size))
        if ratio is not None:
            self.set_gauge(f"{prefix}/kv_compression_ratio", float(ratio))
        if ranks is not None:
            for r in np.asarray(ranks, np.float64).ravel():
                self.observe(f"{prefix}/kv_head_rank", float(r))

    def to_records(self) -> list:
        """Flatten the registry into dump-ready dicts (one per instrument)."""
        recs = [
            {"type": "counter", "name": k, "value": v}
            for k, v in sorted(self.counters.items())
        ]
        recs += [
            {"type": "gauge", "name": k, "value": v}
            for k, v in sorted(self.gauges.items())
        ]
        recs += [
            {"type": "histogram", "name": k, **self.histogram_summary(k)}
            for k in sorted(self.histograms)
        ]
        recs += [
            {
                "type": "span",
                "name": s.name,
                "start_s": round(s.start, 6),
                "duration_s": round(s.duration, 6),
                "parent": s.parent,
            }
            for s in self.spans
        ]
        return recs

    def dump_jsonl(self, path) -> None:
        """Write :meth:`to_records` as JSON-lines (one object per line)."""
        with open(path, "w") as fh:
            for rec in self.to_records():
                fh.write(json.dumps(rec) + "\n")


_default = MetricsRegistry(enabled=False)


def default_registry() -> MetricsRegistry:
    """The process-wide registry library code emits into (starts disabled)."""
    return _default


def set_registry(registry: Optional[MetricsRegistry]) -> MetricsRegistry:
    """Swap the process-wide registry; returns the previous one so callers
    (tests, benchmark drivers) can restore it. ``None`` installs a fresh
    disabled registry."""
    global _default
    prev = _default
    _default = registry if registry is not None else MetricsRegistry(enabled=False)
    return prev
