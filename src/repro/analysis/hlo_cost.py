"""Loop-aware cost analysis of optimized (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts each while-loop body ONCE, so any
scan-based program (layer stacks, blockwise attention, chunked CE)
underreports FLOPs/bytes/collectives by the trip counts.  This module
re-derives per-device totals from the optimized HLO text with loop
multipliers applied:

  1. parse every computation and its ops (one pass, regex line format);
  2. build the call graph: while(body/condition) with
     ``backend_config known_trip_count``, fusion/call ``calls=``,
     conditional branches, reduce ``to_apply``;
  3. propagate execution multipliers from ENTRY;
  4. FLOPs: 2 * |result| * prod(contracting dims) per dot (+conv ignored —
     no conv HLO in this codebase);
     bytes: result+operand sizes of memory-moving ops;
     collectives: result sizes of all-gather / all-reduce / reduce-scatter /
     all-to-all / collective-permute.

Validated against XLA's own cost_analysis on loop-free modules
(tests/test_roofline.py).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z]\d*[a-z]*\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) (?:\([^)]*\))?.*\{\s*$")
_OP_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TF_RE = re.compile(r"(?:true|false)_computation=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# ops whose operand+result sizes approximate HBM traffic
_MEMORY_OPS = {
    "fusion", "dot", "copy", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "sort", "transpose", "reduce", "broadcast",
    "convolution", "concatenate", "slice", "pad", "reverse", "select",
    "add", "multiply", "subtract", "divide", "tanh", "exponential",
    "convert", "iota", "compare", "maximum", "minimum", "rsqrt", "log",
    "custom-call", "cholesky", "triangular-solve",
} | set(_COLLECTIVES)


def _shape_bytes(txt: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(txt):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += int(n * _DTYPE_BYTES[dt])
    return total


def _result_dims(txt: str) -> Optional[Tuple[str, List[int]]]:
    m = _SHAPE_RE.search(txt)
    if not m:
        return None
    dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    return m.group(1), dims


class Op:
    __slots__ = ("name", "result_type", "kind", "rest")

    def __init__(self, name, result_type, kind, rest):
        self.name, self.result_type = name, result_type
        self.kind, self.rest = kind, rest


class Computation:
    def __init__(self, name):
        self.name = name
        self.ops: List[Op] = []


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m and ("->" in line or line.strip().startswith("ENTRY")):
                cur = Computation(m.group(1))
                if line.strip().startswith("ENTRY"):
                    cur.entry = True
                comps[cur.name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            cur.ops.append(Op(m.group(1), m.group(2), m.group(3),
                              m.group(4)))
    return comps


def _find_entry(text: str, comps) -> str:
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            m = re.match(r"ENTRY %?([\w.\-]+)", line)
            if m:
                return m.group(1)
    # fallback: computation never referenced by others
    referenced = set()
    for c in comps.values():
        for op in c.ops:
            for pat in (_CALLS_RE, _BODY_RE, _COND_RE, _TO_APPLY_RE):
                for mm in pat.finditer(op.rest):
                    referenced.add(mm.group(1))
    for name in comps:
        if name not in referenced:
            return name
    raise ValueError("no entry computation found")


def _multipliers(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    mult: Dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    # topological-ish propagation via worklist
    work = [entry]
    seen_edges = set()
    while work:
        cname = work.pop()
        m = mult[cname]
        comp = comps.get(cname)
        if comp is None:
            continue
        for op in comp.ops:
            targets: List[Tuple[str, float]] = []
            if op.kind == "while":
                trip = 1.0
                tm = _TRIP_RE.search(op.rest)
                if tm:
                    trip = float(tm.group(1))
                bm = _BODY_RE.search(op.rest)
                cm = _COND_RE.search(op.rest)
                if bm:
                    targets.append((bm.group(1), trip))
                if cm:
                    targets.append((cm.group(1), trip + 1))
            elif op.kind == "conditional":
                names = _BRANCHES_RE.search(op.rest)
                if names:
                    for n in _OPERAND_RE.finditer(names.group(1)):
                        targets.append((n.group(1), 1.0))
                for n in _TF_RE.finditer(op.rest):
                    targets.append((n.group(1), 1.0))
            else:
                for pat in (_CALLS_RE, _TO_APPLY_RE):
                    mm = pat.search(op.rest)
                    if mm:
                        targets.append((mm.group(1), 1.0))
            for tname, factor in targets:
                key = (cname, tname, factor)
                add = m * factor
                # accumulate: a computation called from several sites runs
                # the sum of its call-site multipliers
                if key in seen_edges:
                    continue
                seen_edges.add(key)
                mult[tname] += add
                work.append(tname)
    return dict(mult)


def _dot_flops(op: Op, symtab: Dict[str, str]) -> float:
    """2 * |result| * prod(contracting dims)."""
    res = _result_dims(op.result_type)
    if res is None:
        return 0.0
    _, rdims = res
    out = 1.0
    for d in rdims:
        out *= d
    # contracting dims from lhs shape
    lhs_m = _OPERAND_RE.search(op.rest)
    cdims_m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    k = 1.0
    if lhs_m and cdims_m and cdims_m.group(1):
        lhs_type = symtab.get(lhs_m.group(1), "")
        lr = _result_dims(lhs_type)
        if lr:
            _, ldims = lr
            for ci in cdims_m.group(1).split(","):
                ci = int(ci)
                if ci < len(ldims):
                    k *= ldims[ci]
    return 2.0 * out * k


_METADATA_RE = re.compile(r'op_name="([^"]*)"')


def top_collectives(text: str, n: int = 15):
    """Largest collective contributors: (kind, total_bytes, count, op_name)."""
    comps = parse_module(text)
    entry = _find_entry(text, comps)
    mult = _multipliers(comps, entry)
    rows = []
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for op in comp.ops:
            if op.kind in _COLLECTIVES:
                size = _shape_bytes(op.result_type) * m
                meta = _METADATA_RE.search(op.rest)
                rows.append((op.kind, size, m,
                             meta.group(1)[-120:] if meta else op.name))
    rows.sort(key=lambda r: -r[1])
    return rows[:n]


def analyze(text: str) -> dict:
    comps = parse_module(text)
    entry = _find_entry(text, comps)
    mult = _multipliers(comps, entry)
    symtab: Dict[str, str] = {}
    for c in comps.values():
        for op in c.ops:
            symtab[op.name] = op.result_type

    flops = 0.0
    bytes_acc = 0.0
    bytes_min = 0.0  # dots/gathers/collectives only — assumes perfect
    #                  elementwise fusion (TPU-realistic lower bound)
    coll = {k: 0.0 for k in _COLLECTIVES}
    _MIN_OPS = {"dot", "gather", "scatter", "dynamic-slice",
                "dynamic-update-slice", "sort", "custom-call",
                "convolution"} | set(_COLLECTIVES)
    fusion_inner_bytes_skip = set()  # comps called by fusion: bytes counted
    for c in comps.values():
        for op in c.ops:
            if op.kind == "fusion":
                mm = _CALLS_RE.search(op.rest)
                if mm:
                    fusion_inner_bytes_skip.add(mm.group(1))

    # computations whose root is a dynamic-update-slice: in-place
    # accumulator updates — traffic is the slice, not the buffer.
    dus_roots = set()
    for c in comps.values():
        if c.ops and c.ops[-1].kind == "dynamic-update-slice":
            dus_roots.add(c.name)

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        in_fusion = cname in fusion_inner_bytes_skip
        for op in comp.ops:
            if op.kind == "dot":
                flops += m * _dot_flops(op, symtab)
            if op.kind in _COLLECTIVES:
                size = _shape_bytes(op.result_type)
                coll[op.kind] += m * size
            if not in_fusion and op.kind in _MEMORY_OPS:
                res = _shape_bytes(op.result_type)
                ops_sizes = []
                for om in _OPERAND_RE.finditer(op.rest.split(
                        ", sharding=")[0].split(", metadata=")[0]):
                    ops_sizes.append(_shape_bytes(
                        symtab.get(om.group(1), "")))
                sz = res + sum(ops_sizes)
                # in-place accumulator pattern (DUS / DUS-rooted fusion):
                # the aliased big buffer is not streamed — drop the largest
                # operand and the duplicated result write.
                is_dus = op.kind == "dynamic-update-slice"
                base_kind = op.kind
                if op.kind == "fusion":
                    mm = _CALLS_RE.search(op.rest)
                    if mm and mm.group(1) in comps and \
                            comps[mm.group(1)].ops:
                        base_kind = comps[mm.group(1)].ops[-1].kind
                    is_dus = bool(mm) and mm.group(1) in dus_roots
                if is_dus and ops_sizes and res == max(ops_sizes):
                    sz = sz - res - max(ops_sizes)
                # slicing/gather reads only the slice, not the operand
                # (scan xs slicing is pointer arithmetic, not traffic)
                if base_kind in ("dynamic-slice", "slice", "gather") and \
                        ops_sizes and max(ops_sizes) > 2 * res:
                    sz = sz - max(ops_sizes)
                bytes_acc += m * sz
                if base_kind in _MIN_OPS:
                    bytes_min += m * sz
    return {
        "flops": flops,
        "bytes_accessed": bytes_acc,
        "bytes_min": bytes_min,
        "collective_bytes": coll,
        "collective_total": sum(coll.values()),
        "n_computations": len(comps),
    }


def analyze_file(path: str) -> dict:
    with open(path) as f:
        return analyze(f.read())

