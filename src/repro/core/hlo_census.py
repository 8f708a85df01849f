"""HLO census: trip-count-aware cost analysis over optimized HLO text.

WHY THIS EXISTS — verified on this container (see tests/test_hlo_census.py):
``compiled.cost_analysis()`` counts a ``while`` loop's body ONCE, so for a
model whose layers run under ``lax.scan`` (every deep model here — compile
time must not scale with depth), FLOPs / bytes / collective counts are
undercounted by roughly the layer count.  This module parses the optimized
HLO text, extracts each while loop's trip count from its condition
computation, and walks the call graph with multipliers:

  flops       — 2 * numel(result) * prod(contracting dims) per dot
  memory bytes— operand + result bytes of every top-level instruction
                (post-fusion: fusion internals never touch HBM, so counting
                at fusion boundaries approximates HBM traffic — the same
                model HloCostAnalysis uses)
  collectives — operand bytes per op kind, times the loop multiplier

Known approximations (documented in EXPERIMENTS.md):
  - non-dot FLOPs (elementwise, reductions) are ignored — dots dominate all
    our workloads by >100x;
  - conditional branches count once (rare in these models);
  - a while whose trip count cannot be inferred gets multiplier 1 and is
    reported in ``unknown_trip_whiles``.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "u4": 1, "s4": 1, "u8": 1, "s8": 1,
    "u16": 2, "s16": 2, "f16": 2, "bf16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "u32": 4, "s32": 4, "f32": 4,
    "u64": 8, "s64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"(pred|u4|u8|u16|u32|u64|s4|s8|s16|s32|s64|bf16|f8e4m3fn|f8e5m2|f16|f32|f64|c64|c128)\[([0-9,]*)\]"
)

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# %name = <type> opcode(...)
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^=]*?\)|[\w\[\]\{\},:\.\#\*]+)\s+([\w\-]+)"
)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+(?:\([^)]*\)\s*->\s*[^{]+)?\{?\s*$")


def _shape_numel_bytes(type_str: str) -> Tuple[int, int]:
    """Total (elements, bytes) over all shapes in a type string."""
    n_el, n_by = 0, 0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        n_el += n
        n_by += n * _DTYPE_BYTES[dtype]
    return n_el, n_by


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    line: str


@dataclasses.dataclass
class CensusResult:
    flops: float
    memory_bytes: float
    collective_bytes: float
    collective_bytes_by_kind: Dict[str, float]
    collective_count_by_kind: Dict[str, float]
    dot_flops_by_multiplier: Dict[int, float]
    unknown_trip_whiles: List[str]

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "memory_bytes": self.memory_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_bytes_by_kind": self.collective_bytes_by_kind,
            "collective_count_by_kind": self.collective_count_by_kind,
            "unknown_trip_whiles": self.unknown_trip_whiles,
        }


_KERNEL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z_][\w-]*?)(?:\.\d+)*\s*=.*"
    r'custom_call_target="tpu_custom_call"')


def kernel_launches(hlo: str) -> Counter:
    """Pallas kernels in a compiled TPU module, by kernel name: each
    ``tpu_custom_call`` instruction is named after its kernel (the
    ``name=`` of its ``pallas_call``).  Static count — a kernel inside a
    scanned layer body counts once."""
    return Counter(m.group(1) for line in hlo.splitlines()
                   if (m := _KERNEL_RE.match(line)))


_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _parse_computations(hlo: str) -> Dict[str, List[Instr]]:
    comps: Dict[str, List[Instr]] = {}
    current: Optional[str] = None
    entry_marker: Optional[str] = None
    for raw in hlo.splitlines():
        # strip /*index=5*/-style comments: the '=' inside them breaks both
        # header detection and tuple-type parsing
        line = _COMMENT_RE.sub("", raw).rstrip()
        s = line.strip()
        if not s or s.startswith("//") or s.startswith("HloModule"):
            continue
        # computation header: "%name (args) -> type {" or "ENTRY %name ... {"
        if s.endswith("{") and not re.match(r"^(ROOT\s+)?%?[\w\.\-]+\s*=", s):
            m = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)", s)
            if m:
                current = m.group(2)
                comps[current] = []
                if m.group(1):
                    entry_marker = current
            continue
        if s == "}" or s.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        im = _INSTR_RE.match(line)
        if im:
            comps[current].append(
                Instr(name=im.group(1), type_str=im.group(2),
                      opcode=im.group(3), line=s)
            )
    if entry_marker is not None:
        comps["__entry__"] = comps[entry_marker]
    return comps


def _find_entry(comps: Dict[str, List[Instr]]) -> Optional[str]:
    if "__entry__" in comps:
        for k, v in comps.items():
            if k != "__entry__" and v is comps["__entry__"]:
                return k
    # fallback: computation that is never referenced as body/cond/fusion
    referenced = set()
    for instrs in comps.values():
        for i in instrs:
            for attr in ("body=", "condition=", "calls=", "to_apply=",
                         "branch_computations="):
                for m in re.finditer(attr + r"\{?%?([\w\.\-]+)", i.line):
                    referenced.add(m.group(1))
    cands = [k for k in comps if k not in referenced and k != "__entry__"]
    return cands[0] if cands else None


def _trip_count(cond_instrs: List[Instr]) -> Optional[int]:
    """Extract the loop bound from a scan-style condition computation:
    compare(induction, constant(L), LT) (or LE/GT variants)."""
    consts: Dict[str, int] = {}
    for i in cond_instrs:
        if i.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", i.line)
            if m:
                consts[i.name] = int(m.group(1))
    for i in cond_instrs:
        if i.opcode == "compare":
            direction = "LT"
            dm = re.search(r"direction=(\w+)", i.line)
            if dm:
                direction = dm.group(1)
            refs = re.findall(r"%([\w\.\-]+)", i.line.split("compare", 1)[1])
            vals = [consts[r] for r in refs if r in consts]
            # inline constant operand, e.g. compare(%gte, s32[] constant(126))
            for m in re.finditer(r"constant\((-?\d+)\)", i.line):
                vals.append(int(m.group(1)))
            if vals:
                bound = max(vals)
                if direction in ("LT", "GT"):
                    return bound
                if direction in ("LE", "GE"):
                    return bound + 1
    return None


def _dot_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    """2 * numel(result) * prod(contracting dim sizes)."""
    res_el, _ = _shape_numel_bytes(instr.type_str)
    # operand shapes: inline or by reference
    after = instr.line.split(instr.opcode, 1)[1]
    inside = after[after.find("(") + 1:]
    depth, end = 1, len(inside)
    for j, ch in enumerate(inside):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = j
                break
    operand_str = inside[:end]
    lhs_shape = None
    sm = _SHAPE_RE.search(operand_str)
    if sm:
        lhs_shape = sm.group(0)
    else:
        refs = re.findall(r"%([\w\.\-]+)", operand_str)
        if refs and refs[0] in shapes:
            lhs_shape = shapes[refs[0]]
    if lhs_shape is None:
        return 0.0
    m = _SHAPE_RE.search(lhs_shape)
    if not m:
        return 0.0
    lhs_dims = [int(d) for d in m.group(2).split(",")] if m.group(2) else []
    cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", instr.line)
    contract = 1
    if cm and cm.group(1):
        for idx in cm.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    else:
        contract = lhs_dims[-1] if lhs_dims else 1
    return 2.0 * res_el * contract


_SKIP_BYTES_OPS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "token", "partition-id", "replica-id",
}

# Ops that touch only a slice of a (possibly huge, in-place-aliased) operand:
# counting full operand bytes would charge a one-token KV-cache update with
# the whole cache (observed ~100x inflation on decode cells).  We charge
# 2x the moved-data size instead (read + write):
#   dynamic-slice:         2x result
#   dynamic-update-slice:  2x update operand (XLA aliases the buffer in place)
#   gather:                2x result (embedding lookups!)
#   scatter:               2x updates operand
_SLICE_BYTES_OPS = {"dynamic-slice", "gather"}
_UPDATE_BYTES_OPS = {"dynamic-update-slice", "scatter"}


def _operand_types(seg: str, shapes: Dict[str, str]) -> List[str]:
    """Split a top-level operand list; return a type string per operand."""
    parts, depth, cur = [], 0, []
    for ch in seg:
        if ch == "(" or ch == "{" or ch == "[":
            depth += 1
        elif ch == ")" or ch == "}" or ch == "]":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    out = []
    for p in parts:
        if _SHAPE_RE.search(p):
            out.append(p)
        else:
            m = re.search(r"%([\w\.\-]+)", p)
            out.append(shapes.get(m.group(1), "") if m else "")
    return out


def census(hlo: str) -> CensusResult:
    comps = _parse_computations(hlo)
    entry = _find_entry(comps)
    if entry is None:
        return CensusResult(0, 0, 0, {}, {}, {}, ["<no entry>"])

    # global name->type table for bare-ref operand resolution
    shapes: Dict[str, str] = {}
    for instrs in comps.values():
        for i in instrs:
            shapes[i.name] = i.type_str

    flops = 0.0
    mem_bytes = 0.0
    coll_bytes: Dict[str, float] = {k: 0.0 for k in COLLECTIVE_OPS}
    coll_count: Dict[str, float] = {k: 0.0 for k in COLLECTIVE_OPS}
    dot_by_mult: Dict[int, float] = {}
    unknown: List[str] = []

    visited_stack: List[str] = []

    def walk(comp_name: str, mult: float):
        nonlocal flops, mem_bytes
        if comp_name not in comps or comp_name in visited_stack:
            return
        visited_stack.append(comp_name)
        for i in comps[comp_name]:
            op = i.opcode
            if op == "while":
                body = cond = None
                bm = re.search(r"body=\{?%?([\w\.\-]+)", i.line)
                cm_ = re.search(r"condition=\{?%?([\w\.\-]+)", i.line)
                if bm:
                    body = bm.group(1)
                if cm_:
                    cond = cm_.group(1)
                trips = None
                # XLA annotates scan-style loops directly:
                tm_ = re.search(r'known_trip_count[":{\s]+n[":\s]+"?(\d+)', i.line)
                if tm_:
                    trips = int(tm_.group(1))
                if trips is None and cond and cond in comps:
                    trips = _trip_count(comps[cond])
                if trips is None:
                    trips = 1
                    unknown.append(i.name)
                if body:
                    walk(body, mult * trips)
                if cond and cond in comps:
                    walk(cond, mult * trips)
                continue
            if op in ("call", "async-start"):
                tm = re.search(r"to_apply=\{?%?([\w\.\-]+)", i.line)
                if tm:
                    walk(tm.group(1), mult)
            if op == "conditional":
                for m in re.finditer(r"branch_computations=\{([^}]*)\}", i.line):
                    for b in re.findall(r"%?([\w\.\-]+)", m.group(1)):
                        walk(b, mult)  # upper bound: all branches counted
            # ---- costs at this instruction ----
            if op in ("dot", "convolution"):
                f = _dot_flops(i, shapes) * mult
                flops += f
                key = int(mult)
                dot_by_mult[key] = dot_by_mult.get(key, 0.0) + f
            if op == "fusion":
                # descend for dots (fusions CAN contain dots on CPU backend)
                fm = re.search(r"calls=\{?%?([\w\.\-]+)", i.line)
                if fm and fm.group(1) in comps:
                    for fi in comps[fm.group(1)]:
                        if fi.opcode in ("dot", "convolution"):
                            f = _dot_flops(fi, shapes) * mult
                            flops += f
                            key = int(mult)
                            dot_by_mult[key] = dot_by_mult.get(key, 0.0) + f
            kind = None
            base = op[:-6] if op.endswith("-start") else op
            if base in COLLECTIVE_OPS:
                kind = base
            if kind and not op.endswith("-done"):
                after = i.line.split(op, 1)[1]
                paren = after.find("(")
                inside = after[paren + 1:]
                depth, end = 1, len(inside)
                for j, ch in enumerate(inside):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            end = j
                            break
                seg = inside[:end]
                _, b = _shape_numel_bytes(seg)
                if b == 0:
                    b = sum(
                        _shape_numel_bytes(shapes.get(r, ""))[1]
                        for r in re.findall(r"%([\w\.\-]+)", seg)
                    )
                if b == 0:
                    _, b = _shape_numel_bytes(i.type_str)
                coll_bytes[kind] += b * mult
                coll_count[kind] += mult
            # memory bytes: result + operands (bare refs resolved) at the
            # top level only (fusion internals excluded by construction)
            if op not in _SKIP_BYTES_OPS:
                _, rb = _shape_numel_bytes(i.type_str)
                after = i.line.split(op, 1)[1] if op in i.line else ""
                seg = ""
                paren = after.find("(")
                if paren >= 0:
                    inside = after[paren + 1:]
                    depth, end = 1, len(inside)
                    for j, ch in enumerate(inside):
                        if ch == "(":
                            depth += 1
                        elif ch == ")":
                            depth -= 1
                            if depth == 0:
                                end = j
                                break
                    seg = inside[:end]
                if op in _SLICE_BYTES_OPS:
                    mem_bytes += 2 * rb * mult
                elif op in _UPDATE_BYTES_OPS:
                    otypes = _operand_types(seg, shapes)
                    upd_idx = 1 if op == "dynamic-update-slice" else (
                        len(otypes) - 1 if otypes else 0)
                    ub = (_shape_numel_bytes(otypes[upd_idx])[1]
                          if 0 <= upd_idx < len(otypes) else rb)
                    mem_bytes += 2 * max(ub, 1) * mult
                else:
                    _, ob = _shape_numel_bytes(seg)
                    if ob == 0:
                        ob = sum(
                            _shape_numel_bytes(shapes.get(r, ""))[1]
                            for r in re.findall(r"%([\w\.\-]+)", seg)
                        )
                    mem_bytes += (rb + ob) * mult
        visited_stack.pop()

    walk(entry, 1.0)
    return CensusResult(
        flops=flops,
        memory_bytes=mem_bytes,
        collective_bytes=sum(coll_bytes.values()),
        collective_bytes_by_kind=coll_bytes,
        collective_count_by_kind=coll_count,
        dot_flops_by_multiplier=dot_by_mult,
        unknown_trip_whiles=unknown,
    )
