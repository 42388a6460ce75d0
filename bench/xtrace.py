"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A traced run writes one ``*.xplane.pb`` file. :func:`load_dir` keeps of it
each TPU's device operations (the ``XLA Ops`` line of the
``/device:TPU:<n>`` planes: HLO instruction name such as
``%panel_update_kernel.9``, start, duration), the starts of the harness's
stage markers on each TPU (programs ``jit_bench_<stage>`` on the ``XLA
Modules`` line), the benchmark's own host spans (``TraceAnnotation`` events
named ``bench/...``), and apart from them the events of the ``Async XLA
Ops`` line (copies and collectives in flight). All are on the profiler's one
clock, in nanoseconds. On a TPU v5e the ``XLA Ops`` line nests: a ``%while``
(a scan) spans the operations of its body.

A chip runs programs in the order they were dispatched, and a traced job
dispatches a marker before each of its stages and one after the last, so on
each chip a stage runs from its marker's start to the next marker's start.
:class:`Trace` then answers the questions the metric readers ask: device
busy time (the union of operation intervals) inside the measured window or
inside a stage, the count and summed time of the innermost operations whose
name matches, the innermost operations that took most time, and the longest
idle gaps labelled by the span the host was in.

``Trace.to_json``/``from_json`` store the kept events, so the reduction can
be checked on a recorded trace without a chip (``bench/tests``).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW = "bench/window"
# a stage marker is the program ``bench_<stage>``, ``jit_bench_<stage>(<id>)``
# on the device; ``bench_end`` follows a job's last stage
MARK_PREFIX = "bench_"
MARK_MODULE = re.compile(r"^jit_" + MARK_PREFIX + r"(\w+)\(")
END = "end"

Interval = Tuple[int, int]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of half-open ``[start, end)`` intervals as sorted disjoint ones."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Total length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Tuple[str, int, int]]]  # chip -> [(name, start_ns, dur_ns)]
    spans: List[Tuple[str, int, int]]  # [(name, start_ns, end_ns)] host spans bench/*
    async_ops: Dict[int, List[Tuple[str, int, int]]] = dataclasses.field(default_factory=dict)
    marks: Dict[int, List[Tuple[str, int]]] = dataclasses.field(default_factory=dict)

    # -- io ---------------------------------------------------------------

    def to_json(self, path: str) -> None:
        doc = {"ops": {str(k): v for k, v in self.ops.items()}, "spans": self.spans,
               "async_ops": {str(k): v for k, v in self.async_ops.items()},
               "marks": {str(k): v for k, v in self.marks.items()}}
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)

    @staticmethod
    def from_json(path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        ops = {int(k): [tuple(e) for e in v] for k, v in doc["ops"].items()}
        async_ops = {int(k): [tuple(e) for e in v] for k, v in doc.get("async_ops", {}).items()}
        marks = {int(k): [tuple(e) for e in v] for k, v in doc.get("marks", {}).items()}
        return Trace(ops=ops, spans=[tuple(s) for s in doc["spans"]], async_ops=async_ops,
                     marks=marks)

    # -- window and spans -------------------------------------------------

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Interval:
        for name, lo, hi in self.spans:
            if name == WINDOW:
                return lo, hi
        raise ValueError(f"no {WINDOW} span in the trace")

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) * 1e-9

    def has_stage(self, name: str) -> bool:
        """Whether some chip ran a marker of the stage ``name``."""
        return any(n == name for marks in self.marks.values() for n, _ in marks)

    def stage(self, chip: int, name: str) -> List[Interval]:
        """Sorted disjoint intervals in which ``chip`` ran the stage ``name``
        inside the window: from each of its markers' starts to the next
        marker's start (or the window's end)."""
        lo, hi = self.window()
        marks = sorted(self.marks.get(chip, []), key=lambda m: m[1])
        ends = [t for _, t in marks[1:]] + [hi]
        return merge([(max(a, lo), min(b, hi)) for (n, a), b in zip(marks, ends)
                      if n == name and b > lo and a < hi])

    # -- device time ------------------------------------------------------

    def _intervals(self, chip: int):
        lo, hi = self.window()
        return merge([(max(s, lo), min(s + d, hi)) for n, s, d in self.ops[chip]
                      if s + d > lo and s < hi])

    def leaves(self, chip: int) -> List[Tuple[str, int, int]]:
        """The operations of ``chip`` that enclose no other (a ``%while``
        spans its body's operations; counting both would count twice)."""
        ops = sorted(self.ops[chip], key=lambda e: (e[1], -e[2]))
        return [e for e, nxt in zip(ops, ops[1:] + [None])
                if nxt is None or nxt[1] >= e[1] + e[2]]

    def busy_s(self, chip: int, stage: Optional[str] = None) -> float:
        """Seconds in which some operation ran on ``chip`` inside the window,
        or inside the stage ``stage``."""
        busy = self._intervals(chip)
        if stage is None:
            return sum(b - a for a, b in busy) * 1e-9
        return overlap(busy, self.stage(chip, stage)) * 1e-9

    def op_stats(self, chip: int, pattern: str) -> Tuple[int, float]:
        """(count, summed seconds) of the innermost operations on ``chip``
        whose name matches the regular expression ``pattern``, started in
        the window."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        hits = [d for n, s, d in self.leaves(chip) if lo <= s < hi and rx.search(n)]
        return len(hits), sum(hits) * 1e-9

    def in_flight_s(self, chip: int, pattern: str) -> float:
        """Seconds inside the window in which an operation whose name
        matches ``pattern`` ran or was in flight on ``chip``: the union over
        the ``XLA Ops`` line (a synchronous collective, or the start and the
        wait of an asynchronous one) and the ``Async XLA Ops`` line."""
        rx = re.compile(pattern)
        lo, hi = self.window()
        events = self.ops[chip] + self.async_ops.get(chip, [])
        return sum(b - a for a, b in merge(
            [(max(s, lo), min(s + d, hi)) for n, s, d in events
             if s + d > lo and s < hi and rx.search(n)])) * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """Innermost operation names by summed device seconds inside the
        window, averaged over the chips, most first."""
        if not self.chips:
            return []
        lo, hi = self.window()
        tot: Dict[str, float] = {}
        for chip in self.chips:
            for n, s, d in self.leaves(chip):
                if s + d > lo and s < hi:
                    tot[n] = tot.get(n, 0.0) + (min(s + d, hi) - max(s, lo)) * 1e-9
        rows = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips)] for n, v in rows]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the chip that idles most, each named
        by the host span (``bench/...``, not the window) that covers most of
        it, or ``"none"``."""
        if not self.chips:
            return []
        lo, hi = self.window()
        chip = max(self.chips, key=lambda c: -self.busy_s(c))
        busy = self._intervals(chip)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        spans = [(n, a, b) for n, a, b in self.spans if n != WINDOW]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
            best, label = 0, "none"
            for n, sa, sb in spans:
                ov = min(b, sb) - max(a, sa)
                if ov > best:
                    best, label = ov, n
            out.append([label, (b - a) * 1e-9])
        return out


def op_name(hlo: str) -> str:
    """``%name`` of an ``XLA Ops`` event, whose name is the whole HLO
    instruction (``%while.12 = (...) while(...), ...``)."""
    return hlo.split(" = ", 1)[0]


def load_dir(path: str) -> Trace:
    """Read the one ``*.xplane.pb`` under ``path`` (as ``jax.profiler`` writes it)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {path}, found {len(files)}")
    pd = ProfileData.from_file(files[0])
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    async_ops: Dict[int, List[Tuple[str, int, int]]] = {}
    marks: Dict[int, List[Tuple[str, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in (OPS_LINE, ASYNC_LINE):
                into = ops if line.name == OPS_LINE else async_ops
                into.setdefault(int(dev.group(1)), []).extend(
                    (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                    for e in line.events)
            elif dev and line.name == MODULES_LINE:
                for e in line.events:
                    mark = MARK_MODULE.match(e.name)
                    if mark:
                        marks.setdefault(int(dev.group(1)), []).append(
                            (mark.group(1), int(e.start_ns)))
            elif not dev:
                spans.extend((e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=sorted(spans, key=lambda s: s[1]), async_ops=async_ops,
                 marks={k: sorted(v, key=lambda m: m[1]) for k, v in marks.items()})
