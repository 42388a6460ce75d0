"""Loop-aware census of a compiled HLO module.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified: an
8-iteration scan reports 1/8 of the unrolled flops), and collectives inside
a layer scan appear once in the HLO text. Since every transformer here is a
scan-over-layers, naive counting under-reports by ~n_layers×.

This walker parses ``compiled.as_text()`` into computations, builds the
call graph (while bodies, fusions, calls), extracts each while loop's trip
count from its condition's integer bound, and weights every instruction by
the product of enclosing trip counts. It reports:

  * flops        — 2·M·N·K per ``dot`` (batch/contract dims parsed per-op)
  * hbm_bytes    — Σ (result + operand bytes) of top-level instructions
                   (fusion internals excluded: values inside a fusion never
                   round-trip through HBM)
  * n_ops        — weighted count of *real* top-level instructions (pure
                   bookkeeping — parameter/constant/tuple/gte/bitcast/
                   reshape — excluded): a dispatch-overhead proxy that a
                   fused scan body should shrink alongside its traffic
  * collectives  — per-op count / result bytes / ring-algorithm wire bytes

All numbers are per-device (the SPMD module is the per-device program).

These are structural counts of a program, not timings: the tests read them
to check what a compiled program holds (a scan's trip-weighted flops, the
wire bytes of the compressed gradient all-reduce). Speed is measured on the
chip by ``bench/``.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "u64": 8, "s64": 8, "u32": 4, "s32": 4, "u16": 2, "s16": 2,
    "u8": 1, "s8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\((.*)\)\s*->.*\{\s*$")
_TRIP_COUNT = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_SHAPE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|u64|s64|u32|s32|u16|s16|u8|s8|pred|c64|c128)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPKIND = re.compile(r"([a-z][a-z0-9\-]*)\(")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND = re.compile(r"condition=%?([\w.\-]+)")
_DOT_DIMS = re.compile(
    r"lhs_batch_dims=\{([0-9,]*)\}.*?lhs_contracting_dims=\{([0-9,]*)\}"
    r".*?rhs_batch_dims=\{([0-9,]*)\}.*?rhs_contracting_dims=\{([0-9,]*)\}"
)
_DOT_DIMS_NOBATCH = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}.*?rhs_contracting_dims=\{([0-9,]*)\}")
_CONST_INT = re.compile(r"constant\((\d+)\)")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of all array shapes appearing in a type string."""
    total = 0
    for dt, dims in _SHAPE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _first_shape_bytes(type_str: str) -> int:
    m = _SHAPE.search(type_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _shape_dims(type_str: str) -> Optional[List[int]]:
    m = _SHAPE.search(type_str)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instruction:
    name: str
    body: str  # everything after '='
    kind: str
    is_root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    params: Dict[str, str]  # param name -> type string
    instructions: List[Instruction]
    is_fusion: bool


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HEADER.match(line.strip())
            if m:
                name, params_str = m.groups()
                params = {}
                for p in params_str.split(","):
                    p = p.strip()
                    if not p:
                        continue
                    if ":" in p:
                        pname, ptype = p.split(":", 1)
                        params[pname.strip().lstrip("%")] = ptype.strip()
                cur = Computation(
                    name=name,
                    params=params,
                    instructions=[],
                    is_fusion="fused_computation" in name or name.startswith("region"),
                )
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            iname, body = m.groups()
            km = _OPKIND.search(body)
            kind = km.group(1) if km else "unknown"
            cur.instructions.append(
                Instruction(
                    name=iname, body=body, kind=kind,
                    is_root=line.lstrip().startswith("ROOT"),
                )
            )
    return comps


def _trip_count(while_body: str, cond: Optional[Computation]) -> int:
    """XLA records the analyzed bound in backend_config; fall back to the
    largest integer constant in the condition computation."""
    m = _TRIP_COUNT.search(while_body)
    if m:
        return int(m.group(1))
    best = 1
    if cond is not None:
        for ins in cond.instructions:
            for c in _CONST_INT.findall(ins.body):
                best = max(best, int(c))
    return best


def _wire_factor(op: str, g: int) -> float:
    if op == "all-reduce":
        return 2.0 * (g - 1) / g
    if op == "all-gather":
        return (g - 1) / g
    if op == "reduce-scatter":
        return float(g - 1)
    if op == "all-to-all":
        return (g - 1) / g
    if op == "collective-permute":
        return 1.0
    return 1.0


def _dot_flops(ins: Instruction, type_of: Dict[str, str]) -> float:
    ops = _OPERANDS.findall(ins.body.split("(", 1)[1])
    if len(ops) < 2:
        return 0.0
    lhs_t, rhs_t = type_of.get(ops[0]), type_of.get(ops[1])
    if lhs_t is None or rhs_t is None:
        return 0.0
    lhs, rhs = _shape_dims(lhs_t), _shape_dims(rhs_t)
    if lhs is None or rhs is None:
        return 0.0
    m = _DOT_DIMS.search(ins.body)
    if m:
        lb = [int(x) for x in m.group(1).split(",") if x]
        lc = [int(x) for x in m.group(2).split(",") if x]
        rb = [int(x) for x in m.group(3).split(",") if x]
        rc = [int(x) for x in m.group(4).split(",") if x]
    else:
        m2 = _DOT_DIMS_NOBATCH.search(ins.body)
        lb, rb = [], []
        if m2:
            lc = [int(x) for x in m2.group(1).split(",") if x]
            rc = [int(x) for x in m2.group(2).split(",") if x]
        else:
            lc, rc = [len(lhs) - 1], [0]
    batch = 1
    for d in lb:
        batch *= lhs[d]
    contract = 1
    for d in lc:
        contract *= lhs[d]
    lhs_free = 1
    for i, d in enumerate(lhs):
        if i not in lb and i not in lc:
            lhs_free *= d
    rhs_free = 1
    for i, d in enumerate(rhs):
        if i not in rb and i not in rc:
            rhs_free *= d
    return 2.0 * batch * contract * lhs_free * rhs_free


def _op_shape_bytes(name: str, type_of: Dict[str, str]) -> int:
    t = type_of.get(name)
    if not t:
        return 0
    return _first_shape_bytes(t[: t.find("(")] if "(" in t else t)


def _fusion_traffic(ins: Instruction, type_of: Dict[str, str], comps: Dict[str, "Computation"]) -> Optional[float]:
    """Honest HBM traffic of a fusion: per-parameter read sizes (a parameter
    consumed only through dynamic-slice reads only the slice; the aliased
    buffer of a root dynamic-update-slice reads nothing) + write sizes (a
    root DUS writes only the update region)."""
    callees = _CALLS.findall(ins.body)
    if not callees or callees[0] not in comps:
        return None
    fused = comps[callees[0]]
    param_names = list(fused.params)
    argpart = ins.body[ins.body.find("(") :] if "(" in ins.body else ""
    operand_names = _OPERANDS.findall(argpart)[: len(param_names)]

    by_name: Dict[str, str] = dict(fused.params)
    root = None
    for fin in fused.instructions:
        by_name[fin.name] = fin.body
        if fin.is_root:
            root = fin
    if root is None and fused.instructions:
        root = fused.instructions[-1]
    if root is None:
        return None

    def result_bytes_of(name: str) -> float:
        b = by_name.get(name, "")
        return float(_first_shape_bytes(b[: b.find("(")] if "(" in b else b))

    def op_list(body: str):
        return _OPERANDS.findall(body[body.find("(") :]) if "(" in body else []

    # classify every fusion parameter by how it is consumed — per use, so a
    # carry buffer that is dynamic-sliced AND the aliased root-DUS target
    # (XLA CPU's serial scatter lowering: read row, add, write row back)
    # charges only the sliced rows, not the whole accumulator per trip
    reads = 0.0
    for pname, oname in zip(param_names, operand_names):
        uses = []
        for fin in fused.instructions:
            if pname in op_list(fin.body):
                uses.append(fin)
        full = _op_shape_bytes(oname, type_of) or result_bytes_of(pname)
        if not uses:
            continue
        sliced = 0.0
        fallback = False
        for u in uses:
            if u.kind == "dynamic-slice":
                sliced += result_bytes_of(u.name)
            elif u.kind == "gather" and op_list(u.body)[0] == pname:
                # sparse read: a k-column/row gather touches ~result bytes
                sliced += result_bytes_of(u.name)
            elif u.kind == "dynamic-update-slice" and op_list(u.body)[0] == pname:
                pass  # aliased in-place carry buffer: reads nothing
            else:
                fallback = True
        reads += full if fallback else sliced

    # writes: root DUS (possibly behind bitcast / in a tuple) writes updates only
    def write_bytes(rname: str, depth=0) -> float:
        body = by_name.get(rname, "")
        kind_m = _OPKIND.search(body)
        kind = kind_m.group(1) if kind_m else ""
        if kind == "dynamic-update-slice":
            ops = op_list(body)
            return result_bytes_of(ops[1]) if len(ops) > 1 else 0.0
        if kind in ("bitcast", "copy") and depth < 3:
            ops = op_list(body)
            if ops:
                return write_bytes(ops[0], depth + 1)
        if kind == "tuple":
            return sum(write_bytes(o, depth + 1) for o in op_list(body))
        head = body[: body.find("(")] if "(" in body else body
        return float(_shape_bytes(head))

    writes = write_bytes(root.name)
    return reads + writes


def _instr_traffic(ins: Instruction, type_of: Dict[str, str], comps: Optional[Dict[str, "Computation"]] = None) -> float:
    """HBM bytes touched by one top-level instruction.

    In-place semantics honoured: dynamic-update-slice / scatter (bare or as
    fusion roots) rewrite only the updated region (XLA aliases the carried
    buffer), so they charge 2×update bytes, not operand+result.
    """
    kind = ins.kind
    head = ins.body[: ins.body.find("(")] if "(" in ins.body else ins.body
    rb = _shape_bytes(head)
    argpart = ins.body[ins.body.find("(") :] if "(" in ins.body else ""
    ops = _OPERANDS.findall(argpart)

    if kind in ("reshape", "bitcast", "get-tuple-element", "tuple", "parameter", "constant"):
        return 0.0
    if kind == "dynamic-update-slice":
        upd = _op_shape_bytes(ops[1], type_of) if len(ops) > 1 else 0
        return 2.0 * upd
    if kind == "scatter":
        upd = _op_shape_bytes(ops[2], type_of) if len(ops) > 2 else 0
        idx = _op_shape_bytes(ops[1], type_of) if len(ops) > 1 else 0
        return 2.0 * upd + idx
    if kind in ("dynamic-slice", "slice", "copy", "transpose", "concatenate", "gather"):
        return 2.0 * rb
    if kind == "fusion" and comps is not None:
        t = _fusion_traffic(ins, type_of, comps)
        if t is not None:
            return t
    if kind in ("fusion", "dot", "convert", "broadcast", "reduce", "pad",
                "select-and-scatter", "sort", "custom-call", "iota", "rng",
                "cholesky", "triangular-solve") or kind in COLLECTIVE_OPS:
        ob = sum(_op_shape_bytes(o, type_of) for o in ops[:8])
        return rb + ob
    return 0.0


def census(hlo: str) -> dict:
    """Loop-aware census of ``hlo``, rooted at the module's ENTRY."""
    comps = parse_computations(hlo)
    entry_name = None
    for raw in hlo.splitlines():
        s = raw.strip()
        if s.startswith("ENTRY"):
            m = _COMP_HEADER.match(s)
            if m:
                entry_name = m.group(1)
                break
    if entry_name is None or entry_name not in comps:
        # fall back: the computation with the most instructions
        entry_name = max(comps, key=lambda c: len(comps[c].instructions))

    # weights: BFS through the call graph multiplying while trip counts
    weights: Dict[str, float] = defaultdict(float)
    weights[entry_name] = 1.0
    order = [entry_name]
    seen = {entry_name}
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        comp = comps[cname]
        w = weights[cname]
        for ins in comp.instructions:
            callees = _CALLS.findall(ins.body)
            cond = _COND.findall(ins.body)
            mult = 1.0
            if ins.kind == "while":
                mult = float(
                    _trip_count(ins.body, comps.get(cond[0]) if cond else None)
                )
            for callee in callees + cond:
                if callee in comps:
                    weights[callee] += w * (mult if callee not in cond else 1.0)
                    if callee not in seen:
                        seen.add(callee)
                        order.append(callee)

    flops = 0.0
    hbm_bytes = 0.0
    n_ops = 0.0
    colls = defaultdict(lambda: {"count": 0.0, "result_bytes": 0.0, "wire_bytes": 0.0})
    trip_info = []
    _BOOKKEEPING = ("parameter", "constant", "tuple", "get-tuple-element",
                    "bitcast", "reshape")

    for cname, comp in comps.items():
        w = weights.get(cname, 0.0)
        if w <= 0:
            continue
        type_of: Dict[str, str] = dict(comp.params)
        for ins in comp.instructions:
            type_of[ins.name] = ins.body
        for ins in comp.instructions:
            if ins.kind == "dot":
                flops += w * _dot_flops(ins, type_of)
            if ins.kind in COLLECTIVE_OPS:
                rb = _shape_bytes(ins.body.split(" ", 1)[0] if False else ins.body[: ins.body.find("(")])
                g = 1
                gm = _GROUPS_RE.search(ins.body)
                if gm:
                    g = int(gm.group(2))
                else:
                    gm2 = _GROUPS_EXPL_RE.search(ins.body)
                    if gm2:
                        g = len(gm2.group(1).split(","))
                colls[ins.kind]["count"] += w
                colls[ins.kind]["result_bytes"] += w * rb
                colls[ins.kind]["wire_bytes"] += w * rb * _wire_factor(ins.kind, max(g, 1))
            if not comp.is_fusion:
                hbm_bytes += w * _instr_traffic(ins, type_of, comps)
                if ins.kind not in _BOOKKEEPING:
                    n_ops += w

    # record while trip counts for transparency
    for cname, comp in comps.items():
        for ins in comp.instructions:
            if ins.kind == "while":
                cond = _COND.findall(ins.body)
                trip_info.append({
                    "while_in": cname,
                    "trip": _trip_count(ins.body, comps.get(cond[0]) if cond else None),
                })

    return {
        "flops": flops,
        "hbm_bytes": hbm_bytes,
        "n_ops": n_ops,
        "collectives": {k: dict(v) for k, v in colls.items()},
        "while_trip_counts": trip_info,
        "n_computations": len(comps),
    }
