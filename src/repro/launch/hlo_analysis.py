"""Trip-count-aware analysis of compiled (post-SPMD, scheduled) HLO text.

Why this exists: XLA's HloCostAnalysis (what compiled.cost_analysis()
reports) counts a while-loop body ONCE, but our programs put all heavy
compute inside lax.scan loops (workers x superblocks x flash blocks x MoE
experts). This module parses the HLO text, reconstructs the call graph,
resolves canonical while-loop trip counts from their condition computations,
and reports loop-aware totals (per device):

  * flops            — 2 * prod(result) * prod(contracted) per dot op
  * hbm_bytes        — operand+result bytes of top-level (unfused) ops in
                       control computations (entry / while bodies)
  * collectives      — per-kind count and ring-traffic bytes
                       (all-reduce 2x, others 1x result bytes)

All values are per-device: post-partitioning HLO shapes are local shapes.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
                "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s*"
    r"([a-z][a-z0-9\-]*)\((.*)$")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_COND_RE = re.compile(r"condition=%([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute", "ragged-all-to-all")
_SKIP_BYTES_OPS = {"tuple", "get-tuple-element", "parameter", "constant",
                   "bitcast", "after-all", "iota"}


def shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    result_type: str
    opcode: str
    rest: str                       # operands + attributes (raw tail)
    is_root: bool = False

    @property
    def operands(self) -> List[str]:
        # names before the first "),"-ish boundary; conservative: all %refs
        # in the call-arg segment (before any attr with '=')
        seg = self.rest.split("),")[0]
        return _OPERAND_RE.findall(seg)


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    is_entry: bool = False

    def symbol_table(self) -> Dict[str, str]:
        return {i.name: i.result_type for i in self.instrs}

    def param_access_bytes(self) -> List[Optional[int]]:
        """For each parameter: bytes actually touched per call if the param
        is consumed ONLY through windowed reads (dynamic-slice / gather),
        else None (meaning: count the full operand).

        Used to avoid charging a scan body with its whole stacked-weights
        array when it dynamic-slices one layer per iteration."""
        params: Dict[int, str] = {}
        for i in self.instrs:
            if i.opcode == "parameter":
                m = re.match(r"(\d+)", i.rest)
                if m:
                    params[int(m.group(1))] = i.name
        users: Dict[str, List[Instr]] = {n: [] for n in params.values()}
        for i in self.instrs:
            for op in i.operands:
                if op in users:
                    users[op].append(i)
        out: List[Optional[int]] = []
        for idx in range(len(params)):
            name = params.get(idx)
            touched = 0
            windowed = bool(users.get(name))
            for u in users.get(name, []):
                if u.opcode in ("dynamic-slice", "gather") and \
                        u.operands and u.operands[0] == name:
                    touched += shape_bytes(u.result_type)
                elif u.opcode == "dynamic-update-slice" and \
                        len(u.operands) > 1 and u.operands[0] == name:
                    # in-place window write: read+write of the update only
                    touched += 0  # update operand charged separately
                else:
                    windowed = False
                    break
            out.append(touched if windowed else None)
        return out


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m and ("->" in line):
                cur = Computation(name=m.group(1), instrs=[],
                                  is_entry=line.strip().startswith("ENTRY"))
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if m:
            cur.instrs.append(Instr(name=m.group(1), result_type=m.group(2),
                                    opcode=m.group(3), rest=m.group(4),
                                    is_root=line.lstrip().startswith("ROOT")))
    return comps


def _trip_count(cond: Computation) -> int:
    """Canonical jax scan loops compare the induction var against a constant
    upper bound; take the max scalar-int constant in the condition."""
    best = 1
    for i in cond.instrs:
        if i.opcode == "constant" and i.result_type.strip() in (
                "s32[]", "u32[]", "s64[]", "u64[]"):
            m = re.match(r"(\d+)", i.rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _dot_flops(instr: Instr, symbols: Dict[str, str]) -> float:
    out = shape_dims(instr.result_type)
    ops = instr.operands
    if not ops:
        return 0.0
    lhs_type = symbols.get(ops[0], "")
    lhs = shape_dims(lhs_type)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.rest)
    contracted = 1
    if m and lhs:
        for d in m.group(1).split(","):
            if d:
                contracted *= lhs[int(d)]
    return 2.0 * math.prod(out or [1]) * contracted


def _instr_hbm_bytes(i: Instr, symbols: Dict[str, str],
                     comps: Dict[str, "Computation"]) -> int:
    """HBM traffic of one top-level instruction: result + operands, with
    windowed reads (dynamic-slice/gather, incl. inside fusions) charged at
    slice size instead of full-buffer size."""
    ops = i.operands
    if i.opcode == "dynamic-slice":
        return 2 * shape_bytes(i.result_type)
    if i.opcode == "gather":
        idx = shape_bytes(symbols.get(ops[1], "")) if len(ops) > 1 else 0
        return 2 * shape_bytes(i.result_type) + idx
    if i.opcode == "dynamic-update-slice":
        upd = shape_bytes(symbols.get(ops[1], "")) if len(ops) > 1 else 0
        return 2 * upd
    if i.opcode == "scatter":
        upd = shape_bytes(symbols.get(ops[2], "")) if len(ops) > 2 else 0
        idx = shape_bytes(symbols.get(ops[1], "")) if len(ops) > 1 else 0
        return 2 * upd + idx
    b = shape_bytes(i.result_type)
    if i.opcode == "fusion":
        cm = re.search(r"calls=%([\w.\-]+)", i.rest)
        if cm and cm.group(1) in comps:
            callee = comps[cm.group(1)]
            # fusion rooted at dynamic-update-slice writes only the window
            root = next((x for x in callee.instrs if x.is_root), None)
            if root is not None and root.opcode == "dynamic-update-slice":
                upd = root.operands[1] if len(root.operands) > 1 else None
                st = callee.symbol_table()
                b = 2 * shape_bytes(st.get(upd, "")) if upd else b
            access = callee.param_access_bytes()
            for pos, op in enumerate(ops):
                win = access[pos] if pos < len(access) else None
                b += win if win is not None else \
                    shape_bytes(symbols.get(op, ""))
            return b
    for op in ops:
        b += shape_bytes(symbols.get(op, ""))
    return b


def analyze(text: str) -> dict:
    comps = parse_module(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {"flops": 0.0, "hbm_bytes": 0.0, "collectives": {}}

    # Call-graph edges: (caller, callee, trip_multiplier, keeps_control).
    # Multipliers are ADDITIVE over call sites and multiplicative down the
    # graph; computed in topological order below.
    edges: Dict[str, List[tuple]] = {c: [] for c in comps}
    for comp in comps.values():
        for i in comp.instrs:
            if i.opcode == "while":
                bm = _BODY_RE.search(i.rest)
                cm = _COND_RE.search(i.rest)
                trips = _trip_count(comps[cm.group(1)]) if cm and \
                    cm.group(1) in comps else 1
                if bm and bm.group(1) in comps:
                    edges[comp.name].append((bm.group(1), trips, True))
                if cm and cm.group(1) in comps:
                    edges[comp.name].append((cm.group(1), trips, False))
            else:
                keeps = i.opcode in ("call", "conditional", "while")
                for callee in _CALLS_RE.findall(i.rest):
                    if callee in comps:
                        edges[comp.name].append((callee, 1, keeps))

    # topological order via DFS from entry
    order: List[str] = []
    seen: set = set()

    def topo(name: str):
        if name in seen:
            return
        seen.add(name)
        for callee, _, _ in edges[name]:
            topo(callee)
        order.append(name)

    topo(entry.name)
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    control: set = {entry.name}
    mult[entry.name] = 1.0
    for name in reversed(order):
        for callee, trips, keeps in edges[name]:
            mult[callee] += mult[name] * trips
            if name in control and keeps:
                control.add(callee)

    flops = 0.0
    hbm = 0.0
    coll = {k: {"count": 0.0, "bytes": 0.0} for k in COLLECTIVE_OPS}
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        symbols = comp.symbol_table()
        for i in comp.instrs:
            if i.opcode in ("dot", "convolution"):
                flops += m * _dot_flops(i, symbols)
            base = i.opcode.rstrip("-start").replace("-start", "")
            for k in COLLECTIVE_OPS:
                if i.opcode in (k, k + "-start"):
                    b = shape_bytes(i.result_type)
                    w = 2 if k == "all-reduce" else 1
                    coll[k]["count"] += m
                    coll[k]["bytes"] += m * w * b
            if cname in control and i.opcode not in _SKIP_BYTES_OPS \
                    and not i.opcode.endswith("-done") \
                    and i.opcode != "while":
                b = _instr_hbm_bytes(i, symbols, comps)
                hbm += m * b
    coll_total = sum(v["bytes"] for v in coll.values())
    return {"flops": flops, "hbm_bytes": hbm,
            "collectives": coll, "collective_bytes": coll_total}
