"""Device scopes and program spans in a JAX profiler trace, beside ``xtrace``.

:func:`xtrace.load_dir` keeps each TPU operation's HLO name, the stage
markers and the benchmark's own host spans (``bench/...``). The program
names its own stages too, and this module keeps them:

- **scopes**: the engine wraps each stage of a panel update in a
  ``jax.named_scope`` (``stream.sketch``, ``stream.mfold``, ``stream.admit``,
  ... and ``finalize.solve``), which XLA writes into the ``op_name``
  metadata of every HLO instruction traced inside it. An operation's scope is
  the innermost such name in its op-name path. XLA's own loops and copies
  carry no metadata; such an operation takes the scope of the operation that
  encloses it on the chip (a ``%while`` spans the operations of its body),
  or none;
- **program spans**: the program's host spans (``stream/<ops>/init``,
  ``.../scan``, ``.../sharded_mesh``, ``.../finalize``), which
  ``repro.obs.span`` always writes into the profiler's trace.

:class:`ScopedTrace` is a :class:`xtrace.Trace` with two more fields: every
field, method and number of the base class reads what it reads there, from
the same events. It adds :meth:`ScopedTrace.scope_s` (device seconds of the
innermost operations in a scope) and :meth:`ScopedTrace.idle_in_spans_s`
(device idle time while the host was inside a program span). A trace of a
program without scopes or spans loads with both fields empty, and both
methods then read 0.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

import xtrace

# a scope is one component of an op-name path: ``stream.<stage>`` or
# ``finalize.<stage>``; the last one in the path is the innermost
SCOPE = re.compile(r"(?:^|/)((?:stream|finalize)\.[a-z_]+)(?=[/:]|$)")
# the stat of a device operation's metadata that holds its op-name path
OP_NAME_STAT = b"tf_op"
PROGRAM_SPAN_PREFIX = "stream/"
# an HLO copy's text: ``%copy-done.4 = f32[...] copy-done(... %copy-start.4)``
COPY_OF = re.compile(r" copy(?:-start|-done)?\(.*(%[\w.\-]+)\)")


def scope_of(path: str) -> Optional[str]:
    """The innermost scope of an op-name path
    (``jit(f)/while/body/stream.mfold/add:`` -> ``stream.mfold``), or None."""
    found = SCOPE.findall(path)
    return found[-1] if found else None


def _xspace_class():
    """A message class for the part of the profiler's ``XSpace`` proto this
    module reads: each plane's name, its lines' names and events' metadata
    ids, and the ``tf_op`` stat of each event metadata (strings as bytes)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="xscope_xplane.proto", package="xscope",
                                           syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for field, number, kind, repeated, of in fields:
            fd = m.field.add(name=field, number=number, type=kind,
                             label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL)
            if of:
                fd.type_name = ".xscope." + of

    I64, U64, BYTES, MSG = T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_BYTES, T.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, I64, 0, None), ("str_value", 5, BYTES, 0, None),
            ("ref_value", 7, U64, 0, None))
    message("XEventMetadata", ("id", 1, I64, 0, None), ("name", 2, BYTES, 0, None),
            ("stats", 5, MSG, 1, "XStat"))
    message("XStatMetadata", ("id", 1, I64, 0, None), ("name", 2, BYTES, 0, None))
    # map<int64, ...> fields, as their repeated entries
    message("EventMetadataEntry", ("key", 1, I64, 0, None), ("value", 2, MSG, 0, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, I64, 0, None), ("value", 2, MSG, 0, "XStatMetadata"))
    message("XEvent", ("metadata_id", 1, I64, 0, None))
    message("XLine", ("name", 2, BYTES, 0, None), ("events", 4, MSG, 1, "XEvent"))
    message("XPlane", ("name", 2, BYTES, 0, None), ("lines", 3, MSG, 1, "XLine"),
            ("event_metadata", 4, MSG, 1, "EventMetadataEntry"),
            ("stat_metadata", 5, MSG, 1, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, MSG, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xscope.XSpace"))


def device_scopes(pb: str) -> Dict[int, List[Optional[str]]]:
    """Chip -> the scope of each event of its ``XLA Ops`` line, in the order
    :func:`xtrace.load_dir` keeps them. A TPU trace names each operation by
    its HLO text; its op-name path is the ``tf_op`` stat of the event's
    metadata, which :class:`jax.profiler.ProfileData` does not show. A copy
    that XLA inserted (``copy``, ``copy-start``, ``copy-done``) carries no
    op name and takes the scope of the last run of the operation it copies."""
    space = _xspace_class()()
    with open(pb, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[int, List[Optional[str]]] = {}
    for plane in space.planes:
        dev = xtrace.DEVICE_PLANE.match(plane.name.decode())
        if not dev:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = [k for k, n in names.items() if n == OP_NAME_STAT]
        meta: Dict[int, Tuple[str, Optional[str], Optional[str]]] = {}
        for entry in plane.event_metadata:
            path = b""
            for stat in entry.value.stats:
                if tf_op and stat.metadata_id == tf_op[0]:
                    path = stat.str_value or names.get(stat.ref_value, b"")
            hlo = entry.value.name.decode(errors="replace")
            copy = COPY_OF.search(hlo)
            meta[entry.key] = (xtrace.op_name(hlo), scope_of(path.decode(errors="replace")),
                               copy.group(1) if copy else None)
        for line in plane.lines:
            if line.name.decode() != xtrace.OPS_LINE:
                continue
            last: Dict[str, Optional[str]] = {}  # operation -> scope of its last run
            scopes = out.setdefault(int(dev.group(1)), [])
            for e in line.events:
                name, scope, source = meta.get(e.metadata_id, ("", None, None))
                if scope is None and source:
                    scope = last.get(source)
                last[name] = scope
                scopes.append(scope)
    return out


@dataclasses.dataclass
class ScopedTrace(xtrace.Trace):
    # chip -> the scope of each operation of ``ops[chip]``, in the same order
    scopes: Dict[int, List[Optional[str]]] = dataclasses.field(default_factory=dict)
    # [(name, start_ns, end_ns)] the program's host spans (``stream/...``)
    program_spans: List[Tuple[str, int, int]] = dataclasses.field(default_factory=list)
    # (chip, stage) -> seconds by scope, made once
    _shares: Dict[tuple, dict] = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)

    # -- io ---------------------------------------------------------------

    def to_json(self, path: str) -> None:
        """The base class's document plus ``scopes`` and ``program_spans``
        (:meth:`xtrace.Trace.from_json` reads it and ignores both)."""
        super().to_json(path)
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        doc["scopes"] = {str(k): v for k, v in self.scopes.items()}
        doc["program_spans"] = self.program_spans
        with gzip.open(path, "wt") as f:
            json.dump(doc, f)

    @staticmethod
    def from_json(path: str) -> "ScopedTrace":
        base = xtrace.Trace.from_json(path)
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        return ScopedTrace(
            **{f.name: getattr(base, f.name) for f in dataclasses.fields(xtrace.Trace)},
            scopes={int(k): v for k, v in doc.get("scopes", {}).items()},
            program_spans=[tuple(s) for s in doc.get("program_spans", [])])

    # -- scopes -----------------------------------------------------------

    def effective_scopes(self, chip: int) -> Dict[Tuple[str, int, int], Optional[str]]:
        """Each operation of ``chip`` -> its scope, or that of the innermost
        operation enclosing it that has one."""
        own = self.scopes.get(chip) or [None] * len(self.ops[chip])
        order = sorted(zip(self.ops[chip], own), key=lambda e: (e[0][1], -e[0][2]))
        out: Dict[Tuple[str, int, int], Optional[str]] = {}
        stack: List[Tuple[int, Optional[str]]] = []  # (end_ns, scope) of enclosing ops
        for (name, s, d), scope in order:
            while stack and stack[-1][0] <= s:
                stack.pop()
            if scope is None and stack:
                scope = stack[-1][1]
            out[(name, s, d)] = scope
            stack.append((s + d, scope))
        return out

    def scope_shares(self, chip: int, stage: Optional[str] = None) -> Dict[Optional[str], float]:
        """Seconds of ``chip``'s innermost operations inside the window, or
        inside the stage ``stage``, by scope (``None``: no scope), most first."""
        if (chip, stage) not in self._shares:
            lo, hi = self.window()
            where = [(lo, hi)] if stage is None else self.stage(chip, stage)
            eff = self.effective_scopes(chip)
            tot: Dict[Optional[str], int] = {}
            j = 0
            for op in self.leaves(chip):  # sorted by start, as ``where``
                _, s, d = op
                while j < len(where) and where[j][1] <= s:
                    j += 1
                ov, k = 0, j
                while k < len(where) and where[k][0] < s + d:
                    ov += min(s + d, where[k][1]) - max(s, where[k][0])
                    k += 1
                if ov:
                    tot[eff[op]] = tot.get(eff[op], 0) + ov
            self._shares[(chip, stage)] = dict(sorted(
                ((k, v * 1e-9) for k, v in tot.items()), key=lambda kv: -kv[1]))
        return self._shares[(chip, stage)]

    def scope_s(self, chip: int, scope: Optional[str], stage: Optional[str] = None) -> float:
        """Device seconds of the innermost operations of ``chip`` whose scope
        is ``scope`` (``None``: those with none), inside the window, or inside
        the stage ``stage``."""
        return self.scope_shares(chip, stage).get(scope, 0.0)

    # -- program spans ----------------------------------------------------

    def in_spans(self, prefix: str) -> List[xtrace.Interval]:
        """Sorted disjoint intervals inside the window in which the host was
        inside a program span whose name starts with ``prefix``."""
        lo, hi = self.window()
        return xtrace.merge([(max(a, lo), min(b, hi)) for n, a, b in self.program_spans
                             if n.startswith(prefix) and b > lo and a < hi])

    def idle_in_spans_s(self, chip: int, prefix: str) -> float:
        """Seconds inside the window in which ``chip`` ran no operation while
        the host was inside a program span whose name starts with ``prefix``."""
        spans = self.in_spans(prefix)
        return (sum(b - a for a, b in spans) - xtrace.overlap(self._intervals(chip), spans)) * 1e-9

    def span_gaps(self, k: int = 10) -> List[List]:
        """:meth:`xtrace.Trace.idle_gaps`, each gap named by the program span
        that covers most of it, or ``"none"``."""
        window = [s for s in self.spans if s[0] == xtrace.WINDOW]
        return dataclasses.replace(self, spans=window + self.program_spans).idle_gaps(k)


def load_dir(path: str) -> ScopedTrace:
    """:func:`xtrace.load_dir` of the one ``*.xplane.pb`` under ``path``, with
    each device operation's scope and the program's host spans."""
    from jax.profiler import ProfileData

    base = xtrace.load_dir(path)
    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    scopes = device_scopes(pb)
    for chip, ops in base.ops.items():
        if len(scopes.get(chip, ())) != len(ops):
            raise ValueError(f"chip {chip}: {len(ops)} operations, {len(scopes.get(chip, ()))} scopes")
    spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
             for plane in ProfileData.from_file(pb).planes
             if not xtrace.DEVICE_PLANE.match(plane.name)
             for line in plane.lines for e in line.events
             if e.name.startswith(PROGRAM_SPAN_PREFIX)]
    return ScopedTrace(**{f.name: getattr(base, f.name) for f in dataclasses.fields(xtrace.Trace)},
                       scopes=scopes, program_spans=sorted(spans, key=lambda s: s[1]))
