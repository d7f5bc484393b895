"""Static analyzer for optimized HLO text → roofline terms.

Why not ``compiled.cost_analysis()``?  XLA's aggregate cost analysis counts
a ``while`` body ONCE — but our production programs are scan-over-layers
(and scan-over-chunks inside attention), so virtually all FLOPs live inside
nested loops whose trip counts the aggregate numbers drop (verified
empirically: an 8-layer scanned MLP reports exactly 1 layer of FLOPs).

This module re-derives per-device costs by walking the HLO call graph and
multiplying every computation's cost by the trip counts of its enclosing
loops:

  flops        — dot ops (2·|out|·|contraction|), including inside fusions
  hbm bytes    — operands+outputs of *materializing* top-level ops
                 (fusion internals excluded: fused ops don't touch HBM)
  collective   — per-type byte totals with ring-model per-device traffic:
                   all-gather       out·(g-1)/g
                   reduce-scatter   in·(g-1)/g
                   all-reduce       2·in·(g-1)/g
                   all-to-all       in·(g-1)/g
                   collective-permute  in
Trip counts come from the loop-condition comparison constant (scan lowers
to a while with a 0..N counter; we take the max s32/u32 constant compared
in the condition — exact for scan-generated loops).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of a (possibly tuple) shape string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_elems(shape_str: str) -> int:
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return 1
    dims = m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    shape: str          # result shape string
    line: str
    operands: List[str]


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    by_name: Dict[str, Op]


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_bytes: float = 0.0
    by_collective: Dict[str, float] = dataclasses.field(default_factory=dict)
    collective_count: int = 0
    cross_pod_bytes: float = 0.0   # collectives whose groups span pods

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        self.collective_bytes += other.collective_bytes * mult
        self.collective_count += int(other.collective_count * mult)
        self.cross_pod_bytes += other.cross_pod_bytes * mult
        for k, v in other.by_collective.items():
            self.by_collective[k] = self.by_collective.get(k, 0.0) + v * mult

    def as_dict(self):
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "collective_count": self.collective_count,
            "cross_pod_bytes": self.cross_pod_bytes,
            "by_collective": {k: round(v) for k, v in
                              sorted(self.by_collective.items())},
        }


# group 2 (result shape) is matched lazily: tuple shapes can contain
# /*index=N*/ comments (with '='!) and layout braces, so we accept anything
# up to the first whitespace-preceded `opname(` — TPU layouts carry
# tiling parens (`{1,0:T(8,128)}`) but never a space before them.
_OP_LINE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)$")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR.match(line)
            if m:
                cur = Computation(m.group(1), [], {})
                if line.lstrip().startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, shape, kind, rest = m.groups()
        operands = re.findall(r"%([\w.\-]+)", rest)
        op = Op(name, kind, shape, line, operands)
        cur.ops.append(op)
        cur.by_name[name] = op
    if cur is not None:
        comps[cur.name] = cur
    return comps, entry


def _trip_count(cond: Computation) -> int:
    """Max integer constant compared in the loop condition (exact for scan)."""
    best = 1
    for op in cond.ops:
        if op.kind == "constant":
            m = re.search(r"constant\((\d+)\)", op.line)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _group_size(line: str, default: int = 1) -> int:
    # v2 format: replica_groups=[ngroups,gsize]<=[total]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+", line)
    if m:
        return int(m.group(2))
    # explicit: replica_groups={{0,1,2,...},{...}}
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


def _spans_pods(line: str, pod_size: int = 256) -> bool:
    """True when the collective's replica groups contain devices from
    different pods (device id // pod_size differs within a group)."""
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
        line)
    if m:
        ng, gs, dims_s, perm_s = m.groups()
        import numpy as _np
        dims = [int(d) for d in dims_s.split(",")]
        total = 1
        for d in dims:
            total *= d
        if total <= pod_size:
            return False
        devs = _np.arange(total).reshape(dims)
        if perm_s:
            devs = devs.transpose([int(p) for p in perm_s.split(",")])
        groups = devs.reshape(int(ng), int(gs))
        return bool((_np.ptp(groups // pod_size, axis=1) > 0).any())
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        ids = [int(x) for x in m.group(1).split(",") if x.strip()]
        return len({i // pod_size for i in ids}) > 1
    return False


def _operand_shapes(op: Op, comp: Computation) -> List[str]:
    """Inline shapes if printed, else look up defs in the computation."""
    inline = re.findall(r"((?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+%[\w.\-]+",
                        op.line.split("(", 1)[1] if "(" in op.line else "")
    if inline:
        return inline
    out = []
    for name in op.operands:
        d = comp.by_name.get(name)
        if d is not None:
            out.append(d.shape)
    return out


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = shape_elems(op.shape)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.line)
    ops_shapes = _operand_shapes(op, comp)
    if not m or not ops_shapes:
        return 2.0 * out_elems  # degenerate
    lhs_dims_m = _SHAPE_RE.search(ops_shapes[0])
    if not lhs_dims_m:
        return 2.0 * out_elems
    dims = ([int(d) for d in lhs_dims_m.group(2).split(",")]
            if lhs_dims_m.group(2) else [])
    k = 1
    for ci in (int(c) for c in m.group(1).split(",") if c):
        if ci < len(dims):
            k *= dims[ci]
    return 2.0 * out_elems * k


def _conv_flops(op: Op, comp: Computation) -> float:
    out_elems = shape_elems(op.shape)
    shapes = _operand_shapes(op, comp)
    if len(shapes) >= 2:
        kernel = shape_elems(shapes[1])
        m = _SHAPE_RE.search(shapes[1])
        # 2 * out * (kernel spatial*in_ch) = 2*out*kernel_elems/out_ch
        if m and m.group(2):
            out_ch = int(m.group(2).split(",")[-1])
            return 2.0 * out_elems * kernel / max(out_ch, 1)
    return 2.0 * out_elems


_CALL_ATTR = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations|"
    r"true_computation|false_computation)=\{?%?([\w.\-, %]+)\}?")

_SKIP_BYTES_KINDS = {
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def _cost_of(comp: Computation, comps: Dict[str, Computation],
             memo: Dict[Tuple[str, bool], Cost], *,
             inside_fusion: bool) -> Cost:
    key = (comp.name, inside_fusion)
    if key in memo:
        return memo[key]
    total = Cost()
    memo[key] = total  # guard (HLO call graphs are acyclic; safe placeholder)
    for op in comp.ops:
        if op.kind == "dot":
            total.flops += _dot_flops(op, comp)
        elif op.kind == "convolution":
            total.flops += _conv_flops(op, comp)
        elif op.kind in _COLLECTIVES:
            g = _group_size(op.line)
            opshapes = _operand_shapes(op, comp)
            in_b = sum(shape_bytes(s) for s in opshapes) or shape_bytes(
                op.shape)
            out_b = shape_bytes(op.shape)
            frac = (g - 1) / g if g > 1 else 0.0
            if op.kind == "all-gather":
                b = out_b * frac
            elif op.kind == "reduce-scatter":
                b = in_b * frac
            elif op.kind == "all-reduce":
                b = 2.0 * in_b * frac
            elif op.kind == "all-to-all":
                b = in_b * frac
            else:  # collective-permute
                b = in_b
            total.collective_bytes += b
            total.collective_count += 1
            total.by_collective[op.kind] = (
                total.by_collective.get(op.kind, 0.0) + b)
            if _spans_pods(op.line):
                total.cross_pod_bytes += b
            if not inside_fusion:
                total.hbm_bytes += in_b + out_b
        if op.kind == "while":
            body_name = re.search(r"body=%?([\w.\-]+)", op.line)
            cond_name = re.search(r"condition=%?([\w.\-]+)", op.line)
            # XLA annotates scan-derived loops with the exact trip count
            m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', op.line)
            if m:
                trips = int(m.group(1))
            else:
                trips = 1
                if cond_name and cond_name.group(1) in comps:
                    trips = _trip_count(comps[cond_name.group(1)])
            if body_name and body_name.group(1) in comps:
                total.add(_cost_of(comps[body_name.group(1)], comps, memo,
                                   inside_fusion=inside_fusion), trips)
            continue
        if op.kind == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", op.line)
            if m and m.group(1) in comps:
                total.add(_cost_of(comps[m.group(1)], comps, memo,
                                   inside_fusion=True))
            if not inside_fusion:
                opshapes = _operand_shapes(op, comp)
                total.hbm_bytes += (sum(shape_bytes(s) for s in opshapes)
                                    + shape_bytes(op.shape))
            continue
        if op.kind == "conditional":
            branches = re.findall(
                r"(?:branch_computations=\{([^}]*)\}|"
                r"true_computation=%?([\w.\-]+)|"
                r"false_computation=%?([\w.\-]+))", op.line)
            names: List[str] = []
            for tup in branches:
                for t in tup:
                    if t:
                        names.extend(n.strip().lstrip("%")
                                     for n in t.split(","))
            if names:
                # runtime executes ONE branch: take the max-cost branch
                sub = [_cost_of(comps[n], comps, memo,
                                inside_fusion=inside_fusion)
                       for n in names if n in comps]
                if sub:
                    best = max(sub, key=lambda c: c.flops + c.hbm_bytes)
                    total.add(best)
            continue
        if op.kind in ("call", "custom-call"):
            m = re.search(r"to_apply=%?([\w.\-]+)", op.line)
            if m and m.group(1) in comps:
                total.add(_cost_of(comps[m.group(1)], comps, memo,
                                   inside_fusion=inside_fusion))
        # ---- HBM bytes for materializing ops --------------------------------
        if (not inside_fusion and op.kind not in _SKIP_BYTES_KINDS
                and op.kind not in _COLLECTIVES and op.kind != "fusion"):
            opshapes = _operand_shapes(op, comp)
            total.hbm_bytes += (sum(shape_bytes(s) for s in opshapes)
                                + shape_bytes(op.shape))
    memo[key] = total
    return total


def analyze(hlo_text: str) -> Cost:
    """Per-device cost of the compiled module (SPMD: one partition)."""
    comps, entry = parse_hlo(hlo_text)
    if entry is None or entry not in comps:
        # fall back: largest computation
        if not comps:
            return Cost()
        entry = max(comps, key=lambda c: len(comps[c].ops))
    memo: Dict[Tuple[str, bool], Cost] = {}
    total = Cost()
    total.add(_cost_of(comps[entry], comps, memo, inside_fusion=False))
    return total


def integer_allreduces(hlo_text: str, dtype: str) -> List[Tuple[str, int]]:
    """``(result shape, replica group size)`` of every all-reduce whose
    reduction adds ``dtype`` values (e.g. ``"s8"``).

    Matches both the typed form (CPU: ``s8[P] all-reduce``) and the form
    XLA's TPU backend gives a narrow all-reduce: the ``dtype`` lanes packed
    into u32 words, with a reduction computation that unpacks, adds in
    ``dtype`` and repacks.
    """
    comps, _ = parse_hlo(hlo_text)
    out = []
    for comp in comps.values():
        for op in comp.ops:
            if op.kind != "all-reduce":
                continue
            m = re.search(r"to_apply=%?([\w.\-]+)", op.line)
            red = comps.get(m.group(1)) if m else None
            if red and any(o.kind == "add" and o.shape.startswith(dtype + "[")
                           for o in red.ops):
                out.append((op.shape.split("{")[0], _group_size(op.line)))
    return out


def mosaic_kernels(hlo_text: str) -> set:
    """Names of the Mosaic (Pallas TPU) kernels in compiled HLO: the
    ``tpu_custom_call`` ops, by instruction name less its ``.N`` suffix
    (the ``name=`` each ``pallas_call`` is given)."""
    comps, _ = parse_hlo(hlo_text)
    return {re.sub(r"\.\d+$", "", op.name)
            for comp in comps.values() for op in comp.ops
            if op.kind == "custom-call"
            and 'custom_call_target="tpu_custom_call"' in op.line}
