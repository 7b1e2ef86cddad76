"""Post-SPMD HLO analysis — the dry-run "profiler".

``compiled.cost_analysis()`` counts every while-loop body ONCE (verified on
this container: a 64-layer scanned train step reports ~1/64 of the unrolled
FLOPs), so scanned-layer models need loop-aware rollup. This module parses
``compiled.as_text()`` into a computation call graph, extracts while-loop
trip counts from the loop-condition constants, and rolls up:

  * dot FLOPs (2 * prod(output) * contracted sizes — matmul-dominated
    models; elementwise FLOPs are second-order and reported via the raw
    cost_analysis column),
  * memory-traffic estimate (sum of output bytes of top-level non-trivial
    ops, x2 for read+write — post-fusion this approximates HBM traffic),
  * collective bytes by kind (all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute), using each op's max(result, operand)
    bytes, with replica-group size recorded so pod-crossing traffic can be
    split out.

All numbers are PER DEVICE (the HLO is the per-partition module).
Validated against an unrolled compile of the same model in tests.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "opaque": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes_and_elems(type_str: str) -> Tuple[int, int]:
    """Total bytes and element count across all arrays in a type string."""
    total_b = 0
    total_e = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                if d:
                    elems *= int(d)
        total_e += elems
        total_b += elems * _DTYPE_BYTES[dt]
    return total_b, total_e


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    out_type: str
    line: str


def _split_operands(s: str) -> List[str]:
    """Split an operand list on top-level commas only — shapes like
    ``f32[4,32]{1,0}`` and tuple types contain commas of their own."""
    parts: List[str] = []
    depth, cur = 0, []
    for ch in s:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        parts.append("".join(cur))
    return parts


def _operand_type(operand: str, types: Dict[str, str]) -> str:
    """Type string of one operand; XLA prints operands as bare names
    (``%get-tuple-element.4``), so it is looked up by name."""
    return types.get(operand.strip().lstrip("%"), "")


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    # local (non-rolled-up) numbers
    dot_flops: float = 0.0
    mem_bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    coll_by_group: Dict[Tuple[str, int], float] = dataclasses.field(
        default_factory=dict)
    calls: List[Tuple[str, float]] = dataclasses.field(default_factory=list)
    # callee name -> multiplier


# type is matched non-greedily up to the first `opcode(` token — tuple
# types (which may contain /*index=N*/ comments) never have a bare
# `word(` inside, so the first such token is the opcode.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)")
_DIMS_ATTR = re.compile(r"(\w+_contracting_dims)=\{([0-9,]*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def parse_hlo(text: str) -> Dict[str, Computation]:
    """Computation headers are non-indented lines ending in '{' that start
    with ENTRY or %name; instructions are indented '%name = ...' lines."""
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if line and not line.startswith(" ") and line.endswith("{"):
            s = line.strip()
            is_entry = s.startswith("ENTRY")
            if is_entry:
                s = s[len("ENTRY"):].strip()
            if s.startswith("%") or is_entry:
                name = re.split(r"[\s(]", s.lstrip("%"), maxsplit=1)[0]
                cur = Computation(name, [])
                comps[name] = cur
                if is_entry:
                    entry = name
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if m:
            name, out_type, opcode, rest = m.groups()
            cur.instrs.append(Instr(name, opcode, out_type, line))
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


def _analyze_computation(comp: Computation, param_types: Dict[str, str]):
    """Populate local stats + call edges for one computation."""
    # map instr name -> out type, for operand byte lookups
    types = dict(param_types)
    for ins in comp.instrs:
        types[ins.name] = ins.out_type

    for ins in comp.instrs:
        op = ins.opcode
        out_b, out_e = _shape_bytes_and_elems(ins.out_type)

        if op == "dot":
            # flops = 2 * prod(output dims) * prod(contracting dims of lhs)
            mm = re.search(r"dot\(([^)]*)\)", ins.line)
            operands = _split_operands(mm.group(1)) if mm else []
            cdims = dict(_DIMS_ATTR.findall(ins.line))
            lhs_c = cdims.get("lhs_contracting_dims", "")
            contracted = 1
            if operands and lhs_c:
                lhs_t = _operand_type(operands[0], types)
                sm = _SHAPE_RE.search(lhs_t)
                if sm and sm.group(2):
                    dims = [int(d) for d in sm.group(2).split(",") if d]
                    for ci in lhs_c.split(","):
                        if ci and int(ci) < len(dims):
                            contracted *= dims[int(ci)]
            comp.dot_flops += 2.0 * out_e * contracted

        if op.startswith("while"):
            mb = re.search(r"body=%?([\w\.\-]+)", ins.line)
            mc = re.search(r"condition=%?([\w\.\-]+)", ins.line)
            if mb:
                comp.calls.append((mb.group(1), -1.0))  # trip filled later
                comp._while_conds = getattr(comp, "_while_conds", [])
                comp._while_conds.append((mb.group(1),
                                          mc.group(1) if mc else None))
        elif op in ("fusion", "call", "custom-call", "conditional",
                    "reduce", "sort", "scatter", "map", "reduce-window",
                    "select-and-scatter", "all-reduce", "reduce-scatter"):
            # called computation's FLOPs count once, but its internal ops
            # do NOT touch HBM (fused into registers/VMEM): mem_mult = 0
            for cname in _CALLED_RE.findall(ins.line):
                if "body=" not in ins.line and "condition=" not in ins.line:
                    comp.calls.append((cname, 1.0, 0.0))

        for kind in _COLLECTIVES:
            if op.startswith(kind) and not op.endswith("-done"):
                # wire-volume estimate: max of result/operand bytes
                mm = re.search(rf"{kind}[\w\-]*\((.*?)\)", ins.line)
                in_b = 0
                if mm:
                    for o in _split_operands(mm.group(1)):
                        tb, _ = _shape_bytes_and_elems(
                            _operand_type(o, types))
                        in_b += tb
                vol = float(max(out_b, in_b))
                comp.coll_bytes[kind] = comp.coll_bytes.get(kind, 0.0) + vol
                gm = _GROUPS_RE.search(ins.line)
                group_size = 0
                if gm:
                    group_size = int(gm.group(2))
                else:
                    gb = _GROUPS_BRACE.search(ins.line)
                    if gb:
                        group_size = len(gb.group(1).split(","))
                k = (kind, group_size)
                comp.coll_by_group[k] = comp.coll_by_group.get(k, 0.0) + vol
                break

        if op not in ("parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast", "reshape", "copy-done", "copy-start",
                      "after-all", "partition-id"):
            comp.mem_bytes += 2.0 * out_b


def _trip_count(cond: Optional[Computation]) -> float:
    """Extract the trip count from a counted-loop condition computation."""
    if cond is None:
        return 1.0
    best = None
    for ins in cond.instrs:
        m = _CONST_RE.search(ins.line)
        if m:
            v = int(m.group(1))
            best = v if best is None else max(best, v)
    return float(best) if best else 1.0


@dataclasses.dataclass
class HloSummary:
    dot_flops: float
    mem_bytes: float
    coll_bytes: Dict[str, float]
    coll_by_group: Dict[Tuple[str, int], float]
    coll_total: float
    n_while: int
    trip_counts: List[float]

    def cross_pod_bytes(self, intra_pod_group_sizes=(1, 16, 256)) -> float:
        """Collective bytes on groups that span pods. On the 512-device
        (2,16,16) mesh: model-axis groups = 16, data x model = 256 are
        intra-pod; 32 (pod x data) and 512 (global) cross pods."""
        return sum(v for (k, gs), v in self.coll_by_group.items()
                   if gs not in intra_pod_group_sizes)


def analyze(text: str) -> HloSummary:
    comps = parse_hlo(text)
    entry = comps.get("__entry__")
    if entry is None:
        raise ValueError("no ENTRY computation found")

    for c in comps.values():
        if not hasattr(c, "_analyzed"):
            _analyze_computation(c, {})
            c._analyzed = True

    trips: List[float] = []

    # resolve while multipliers: calls are (name, flops_mult, mem_mult)
    for c in comps.values():
        conds = getattr(c, "_while_conds", [])
        cond_of = dict(conds)
        new_calls = []
        for entry_call in c.calls:
            name, mult = entry_call[0], entry_call[1]
            mem_mult = entry_call[2] if len(entry_call) > 2 else mult
            if mult < 0:
                cond_name = cond_of.get(name)
                t = _trip_count(comps.get(cond_name)) if cond_name else 1.0
                trips.append(t)
                new_calls.append((name, t, t))
            else:
                new_calls.append((name, mult, mem_mult))
        c.calls = new_calls

    memo: Dict[str, Tuple[float, float, Dict, Dict]] = {}

    def roll(name: str, depth=0):
        if name in memo:
            return memo[name]
        c = comps.get(name)
        if c is None or depth > 64:
            return (0.0, 0.0, {}, {})
        fl, mb = c.dot_flops, c.mem_bytes
        cb = dict(c.coll_bytes)
        cg = dict(c.coll_by_group)
        for callee, mult, mem_mult in c.calls:
            if callee == name:
                continue
            cfl, cmb, ccb, ccg = roll(callee, depth + 1)
            fl += mult * cfl
            mb += mem_mult * cmb
            for k, v in ccb.items():
                cb[k] = cb.get(k, 0.0) + mult * v
            for k, v in ccg.items():
                cg[k] = cg.get(k, 0.0) + mult * v
        memo[name] = (fl, mb, cb, cg)
        return memo[name]

    fl, mb, cb, cg = roll(entry.name)
    return HloSummary(dot_flops=fl, mem_bytes=mb, coll_bytes=cb,
                      coll_by_group=cg, coll_total=sum(cb.values()),
                      n_while=len(trips), trip_counts=sorted(trips)[-8:])
