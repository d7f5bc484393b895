"""Reduction of a profiler trace to what the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes, with
``jax.profiler.ProfileData`` and nothing else, and returns a
``Reduced`` record:

- the traced window: from the first to the last host span the benchmark
  recorded with ``TraceAnnotation`` (names ``bench.<what>``) around its
  calls into the program;
- per chip, the device's operations (the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane; an event's name is its HLO instruction)
  clipped to the window: busy time is the union of their intervals,
  idle time the rest of the window;
- device time per instruction, per kind and per Pallas kernel.  The kind
  comes from the compiled program's HLO text, when given: a fusion whose
  computation holds a convolution is a convolution; all-reduce,
  all-gather, reduce-scatter, collective-permute and all-to-all are
  collectives; a ``tpu_custom_call`` is a kernel, named by its
  instruction less the ``.N`` suffix (the name its ``pallas_call`` was
  given); while, conditional and call only contain other operations
  and count towards busy time alone;
- the idle gaps of the first chip, each attributed to the host span it
  falls in ("outside spans" where none).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTROL = ("while", "conditional", "call")

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-_]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) [^=]*\{\s*$")


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]                  # ns, host clock
    chips: int                               # device planes reduced
    busy_s: List[float]                      # per chip
    op_time_s: List[Dict[str, float]]        # per chip: instruction -> s
    op_kind: Dict[str, str]                  # instruction -> kind
    kind_time_s: List[Dict[str, float]]      # per chip: kind -> s
    kernel_time_s: List[Dict[str, float]]    # per chip: kernel -> s
    kernel_calls: List[Dict[str, int]]       # per chip: kernel -> calls
    gaps: Dict[str, float]                   # host span -> idle s (chip 0)
    spans: Dict[str, float]                  # host span -> s

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def op_time(self, kernel: str, chip: int = 0) -> float:
        return self.kernel_time_s[chip].get(kernel, 0.0)

    def op_calls(self, kernel: str, chip: int = 0) -> int:
        return self.kernel_calls[chip].get(kernel, 0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((f"{k} ({self.op_kind.get(k, 'other')})", v)
                      for k, v in self.op_time_s[0].items()
                      if self.op_kind.get(k) != "control"),
                     key=lambda kv: -kv[1])
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def base_name(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def _kind_of(opcode: str, line: str, fused: set) -> str:
    if opcode == "custom-call" and "tpu_custom_call" in line:
        return "kernel"
    if opcode in CONTROL:
        return "control"
    ops = fused | {opcode}
    if any(o.startswith(c) for o in ops for c in COLLECTIVES):
        return "collective"
    if "convolution" in ops:
        return "convolution"
    return "other"


def hlo_kinds(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> kind, from compiled HLO text."""
    comps: Dict[str, List[Tuple[str, str, str, List[str]]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        op = _OPCODE.search(line, m.end() - 1)
        cur.append((m.group(1), op.group(1) if op else "",
                    line if "custom-call" in line else "",
                    _CALLS.findall(line)))
    memo: Dict[str, set] = {}

    def opcodes(comp: str, seen=()) -> set:
        if comp in memo:
            return memo[comp]
        out = set()
        for _, op, _, calls in comps.get(comp, []):
            out.add(op)
            for c in calls:
                if c not in seen:
                    out |= opcodes(c, seen + (comp,))
        memo[comp] = out
        return out

    kinds = {}
    for instrs in comps.values():
        for name, op, line, calls in instrs:
            fused = set()
            if op == "fusion":
                for c in calls:
                    fused |= opcodes(c)
            kinds[name] = _kind_of(op, line, fused)
    return kinds


def event_instruction(text: str) -> Tuple[str, str]:
    """``(instruction name, kind from the event text alone)``."""
    m = _INSTR.match(text)
    if m is None:
        return text, "other"
    op = _OPCODE.search(text, m.end() - 1)
    return m.group(1), _kind_of(op.group(1) if op else "", text, set())


def _union(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Idle ``(start, end)`` intervals of ``[lo, hi]`` not covered."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def device_planes(data):
    planes = [p for p in data.planes
              if re.match(r"^/device:TPU:\d+$", p.name)]
    return sorted(planes, key=lambda p: int(p.name.rsplit(":", 1)[1]))


def _op_lines(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return named or [ln for ln in lines
                     if ln.name not in ("Steps", "XLA Modules")]


def host_spans(data) -> List[Tuple[str, int, int]]:
    out = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name[len(SPAN_PREFIX):],
                                int(ev.start_ns), int(ev.end_ns)))
    return sorted(out, key=lambda s: s[1])


def reduce(data, chips: int, hlo_text: Optional[str] = None) -> Reduced:
    """Reduce a ``ProfileData`` (or a trace directory) over the first
    ``chips`` device planes; ``hlo_text`` is the compiled program's."""
    if isinstance(data, str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(find_xplane(data))
    spans = host_spans(data)
    if not spans:
        raise ValueError("the trace holds no bench.* host spans")
    lo, hi = spans[0][1], max(e for _, _, e in spans)
    planes = device_planes(data)[:chips]
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    kinds = hlo_kinds(hlo_text) if hlo_text else {}
    op_kind: Dict[str, str] = {}
    busy, times, by_kind, ktime, kcalls, gaps = [], [], [], [], [], {}
    for i, plane in enumerate(planes):
        intervals = []
        t, k = defaultdict(float), defaultdict(float)
        kt, kc = defaultdict(float), defaultdict(int)
        for ln in _op_lines(plane):
            for ev in ln.events:
                s, e = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
                if e <= s:
                    continue
                name, kind = event_instruction(ev.name)
                kind = kinds.get(name, kind)
                op_kind[name] = kind
                intervals.append((s, e))
                if kind == "control":
                    continue
                d = (e - s) * 1e-9
                t[name] += d
                k[kind] += d
                if kind == "kernel":
                    kt[base_name(name)] += d
                    kc[base_name(name)] += 1
        busy.append(_union(intervals) * 1e-9)
        times.append(dict(t))
        by_kind.append(dict(k))
        ktime.append(dict(kt))
        kcalls.append(dict(kc))
        if i == 0:
            gaps = attribute_gaps(_gaps(intervals, lo, hi), spans)
    span_time = defaultdict(float)
    for name, s, e in spans:
        span_time[name] += (e - s) * 1e-9
    return Reduced((lo, hi), len(planes), busy, times, op_kind, by_kind,
                   ktime, kcalls, gaps, dict(span_time))


def attribute_gaps(gaps, spans) -> Dict[str, float]:
    """Idle seconds by the host span they overlap; time in no span is
    "outside spans"."""
    out = defaultdict(float)
    for gs, ge in gaps:
        cur = gs
        for name, s, e in spans:
            if e <= cur or s >= ge:
                continue
            if s > cur:
                out["outside spans"] += (s - cur) * 1e-9
                cur = s
            end = min(e, ge)
            out[name] += (end - cur) * 1e-9
            cur = end
            if cur >= ge:
                break
        if cur < ge:
            out["outside spans"] += (ge - cur) * 1e-9
    return dict(out)

