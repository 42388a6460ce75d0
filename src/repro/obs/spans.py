"""Named host spans: ``jax.profiler`` annotations + wall-clock records.

:func:`span` is a context manager instrumenting the host side of a dispatch.
It always wraps the body in a :class:`jax.profiler.TraceAnnotation`, so the
span lands on the profiler's clock next to the device's operations whenever
a profiler session is live (with none live, the annotation is one check in
C++). When the active :class:`~repro.obs.metrics.MetricsRegistry` is
enabled it also records a :class:`~repro.obs.metrics.SpanRecord` (start,
duration, parent span).

Span naming scheme (see ``docs/observability.md`` for the catalog):
``layer/subject/stage`` — e.g. ``stream/adaptive_cur/scan``,
``stream/adaptive_cur/init``, ``serve/kv_compress/prefill``.

Async-dispatch caveat: JAX returns before the device finishes, so a span
around a bare jitted call measures dispatch, not execution. Block inside the
span (``jax.block_until_ready(out)``) when device wall-clock is the thing
being measured. What the device did meanwhile is named by the engine's
``jax.named_scope`` scopes (``stream.sketch``, ``stream.mfold``, …) in the
device trace.
"""

from __future__ import annotations

import functools
import time
from contextlib import contextmanager
from typing import Optional

import jax

from .metrics import MetricsRegistry, SpanRecord, default_registry

__all__ = ["span", "spanned"]


@contextmanager
def span(name: str, registry: Optional[MetricsRegistry] = None):
    """Annotate the profiler trace with the host span ``name`` and, when
    ``registry`` (default: the process registry) is enabled, record it."""
    reg = registry if registry is not None else default_registry()
    with jax.profiler.TraceAnnotation(name):
        if not reg.enabled:
            yield
            return
        parent = reg._span_stack[-1] if reg._span_stack else None
        reg._span_stack.append(name)
        start = time.perf_counter()
        try:
            yield
        finally:
            duration = time.perf_counter() - start
            reg._span_stack.pop()
            reg.spans.append(
                SpanRecord(name=name, start=start - reg.epoch, duration=duration, parent=parent)
            )


def spanned(name: str):
    """Decorator: run every call of the function inside ``span(name)``."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return call

    return wrap
