"""The round program's phases and ``Experiment.run``'s host spans, read
from a profiler trace.

The program names its own work.  On the device, ``jax.named_scope``
puts ``fed.<phase>`` into each instruction's ``metadata={op_name=...}``
in the compiled HLO: ``fed.gather``, ``fed.noise``, ``fed.local_train``,
``fed.psm``, ``fed.uplink``, ``fed.server``, ``fed.eval``.  The
innermost such name owns an instruction, also inside a transform
(``transpose(jvp(fed.psm))`` is ``psm``); an instruction with none is
``unscoped``.  On the host, ``Experiment.run`` opens
``TraceAnnotation`` spans named ``fed.run.<what>``.

This module adds to ``trace.py`` and changes nothing it computes:

- ``hlo_scopes(hlo_text)``: instruction -> phase;
- ``phase_time_s(reduced, scopes)``: per chip, phase -> device seconds
  over the non-control operations ``trace.reduce`` kept (so the phases
  and ``unscoped`` sum to its per-instruction times exactly);
- ``reduce_host(data)``: the ``bench.*`` and ``fed.run.*`` host spans,
  with the first chip's idle time inside the window given to the
  innermost span that covers it.

Run as a script, it drives one cell of the benchmark (set-up, an
untraced window, a traced window) and prints what a ``--trace 1`` run of
``bench/run.py`` cannot report yet: the set-up split into trace and
lowering, backend compilation and the rest; the ``fed.run.*`` spans per
call; the idle time by innermost span; the traced calls' wall time
against the untraced ones.  It checks no numbers against the reference.

    python3 bench/phases.py --workload <cell> --seed <n> [--seconds <s>]
                            [--hlo-out <file>]
"""
from __future__ import annotations

import time

T_START = time.time()

import dataclasses  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Tuple  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

trace = harness.load_module(harness.BENCH / "trace.py")

PROGRAM_PREFIX = "fed.run."
UNSCOPED = "unscoped"
OUTSIDE = "outside spans"

_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_PHASE = re.compile(r"(?<![\w.])fed\.([A-Za-z_]\w*)")


def scope_of(op_name: str) -> str:
    """The innermost ``fed.<phase>`` of an op_name, or ``unscoped``."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else UNSCOPED


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> phase, for every instruction of every
    computation of compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = trace._INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = scope_of(op.group(1)) if op else UNSCOPED
    return out


def phase_kind_time_s(reduced, scopes: Dict[str, str]
                      ) -> List[Dict[Tuple[str, str], float]]:
    """Per chip, ``(phase, kind)`` -> device seconds over the non-control
    operations a ``trace.Reduced`` timed (an instruction not in
    ``scopes`` is unscoped).  XLA can give a fused instruction the name
    of an operation it absorbed, so a phase may hold another's
    convolutions: the kind shows it."""
    out = []
    for times in reduced.op_time_s:
        t = defaultdict(float)
        for name, s in times.items():
            t[scopes.get(name, UNSCOPED),
              reduced.op_kind.get(name, "other")] += s
        out.append(dict(t))
    return out


def phase_time_s(reduced, scopes: Dict[str, str]) -> List[Dict[str, float]]:
    """Per chip, phase -> device seconds (``phase_kind_time_s`` summed
    over kinds)."""
    out = []
    for by_kind in phase_kind_time_s(reduced, scopes):
        t = defaultdict(float)
        for (phase, _), s in by_kind.items():
            t[phase] += s
        out.append(dict(t))
    return out


# ---- host spans ----------------------------------------------------------

@dataclasses.dataclass
class Host:
    window: Tuple[int, int]              # ns: first to last bench.* span
    spans: List[Tuple[str, int, int]]    # bench.* and fed.run.*, full names
    gaps: Dict[str, float]               # innermost span -> idle s (chip 0)

    def span_s(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name) * 1e-9


def host_spans(data) -> List[Tuple[str, int, int]]:
    """Every ``bench.*`` and ``fed.run.*`` host event, by start."""
    out = []
    for p in data.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX)):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return sorted(out, key=lambda s: s[1])


def innermost(spans, lo: int, hi: int) -> List[Tuple[int, int, str]]:
    """``[lo, hi]`` cut into pieces, each with the innermost span that
    covers it (the latest to start; "outside spans" where none does)."""
    points = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                                if lo < t < hi})
    out = []
    for a, b in zip(points, points[1:]):
        cover = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        out.append((a, b, max(cover)[2] if cover else OUTSIDE))
    return out


def attribute_gaps(gaps, pieces) -> Dict[str, float]:
    """Idle seconds by the innermost span of each piece they overlap."""
    out = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            a, b, name = pieces[k]
            out[name] += (min(b, ge) - max(a, gs)) * 1e-9
            k += 1
    return dict(out)


def reduce_host(data) -> Host:
    """The host spans of a ``ProfileData`` and the first chip's idle time
    inside the window (as ``trace.reduce`` takes it) by innermost span."""
    spans = host_spans(data)
    bench = [s for s in spans if s[0].startswith(trace.SPAN_PREFIX)]
    if not bench:
        raise ValueError("the trace holds no bench.* host spans")
    lo, hi = bench[0][1], max(e for _, _, e in bench)
    planes = trace.device_planes(data)
    if not planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane")
    intervals = []
    for ln in trace._op_lines(planes[0]):
        for ev in ln.events:
            s, e = max(int(ev.start_ns), lo), min(int(ev.end_ns), hi)
            if e > s:
                intervals.append((s, e))
    gaps = trace._gaps(intervals, lo, hi)
    return Host((lo, hi), spans,
                attribute_gaps(gaps, innermost(spans, lo, hi)))


# ---- the tool ------------------------------------------------------------

class SetupClock:
    """Sums JAX's own compile timings while active: jaxpr tracing and
    lowering to MLIR (``lower_s``), backend compilation, which times a
    compile-cache read too (``compile_s``), and the cache reads alone."""

    LOWER = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.lower_s = self.compile_s = self.cache_read_s = 0.0
        self.active = True
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if not self.active:
            return
        if event.endswith(self.LOWER):
            self.lower_s += duration
        elif event.endswith("backend_compile_duration"):
            self.compile_s += duration
        elif event.endswith("cache_retrieval_time_sec"):
            self.cache_read_s += duration


def _top_ops(reduced, scopes, chip, rounds, top=5):
    """Per phase, its ``top`` longest instructions with their kind."""
    by_phase = defaultdict(list)
    for name, t in reduced.op_time_s[chip].items():
        by_phase[scopes.get(name, UNSCOPED)].append(
            [name, reduced.op_kind.get(name, "other"), 1e3 * t / rounds])
    return {k: sorted(v, key=lambda x: -x[2])[:top]
            for k, v in sorted(by_phase.items())}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr)


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args(argv)
    try:
        ctx = harness.workload(args.workload)
        jax = harness.setup_jax(ctx["chips"])
    except harness.BenchError as e:
        _log(f"phases: {e}")
        return 2
    clock = SetupClock()
    cell = harness.engine(ctx["traffic"]).Cell(ctx, args.seed)
    cell.first_call()
    setup_s = time.time() - T_START
    clock.active = False
    rest = setup_s - clock.lower_s - clock.compile_s
    _log(f"setup {setup_s!r} s: trace and lower {clock.lower_s!r}, backend "
         f"compile {clock.compile_s!r} (of it cache reads "
         f"{clock.cache_read_s!r}), rest {rest!r}")
    rounds, wall, calls = cell.window(args.seconds)
    untraced = [c[0] for c in cell.call_s]
    tdir = harness.ROOT / ".bench_cache" / "trace" / f"phases-{ctx['name']}"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    def annotate(name):
        return jax.profiler.TraceAnnotation(trace.SPAN_PREFIX + name)

    with jax.profiler.trace(str(tdir), profiler_options=opts):
        t_rounds, t_wall = cell.traced_window(annotate)
    hlo = cell.hlo_text()
    if args.hlo_out:
        Path(args.hlo_out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.hlo_out).write_text(hlo)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace.find_xplane(str(tdir)))
    reduced = trace.reduce(data, ctx["chips"], hlo)
    host = reduce_host(data)
    shutil.rmtree(tdir, ignore_errors=True)
    scopes = hlo_scopes(hlo)
    phases = phase_time_s(reduced, scopes)
    kinds = phase_kind_time_s(reduced, scopes)
    chip = max(range(len(phases)), key=lambda i: sum(phases[i].values()))
    ops_s = sum(phases[chip].values())
    busy = reduced.busy_s[chip]
    t_calls = t_rounds // cell.R
    run_spans = sorted({n for n, _, _ in host.spans
                        if n.startswith(PROGRAM_PREFIX)})
    idle_run = sum(v for k, v in host.gaps.items()
                   if k.startswith(PROGRAM_PREFIX))
    idle_in_call = idle_run + host.gaps.get(
        trace.SPAN_PREFIX + "Experiment.run", 0.0)
    out = {
        "workload": ctx["name"], "seed": args.seed,
        "device": harness.device_info(jax, ctx["chips"]),
        "setup_s": setup_s, "setup_lower_s": clock.lower_s,
        "setup_compile_s": clock.compile_s,
        "setup_cache_read_s": clock.cache_read_s, "setup_rest_s": rest,
        "untraced_call_s": untraced, "traced_call_s": t_wall / t_calls,
        "traced_rounds": t_rounds, "window_s": reduced.window_s,
        "busy_s": busy, "op_s": ops_s,
        "phase_ms_per_round": {k: 1e3 * v / t_rounds
                               for k, v in sorted(phases[chip].items())},
        "phase_kind_ms_per_round": {f"{p}/{k}": 1e3 * v / t_rounds
                                    for (p, k), v in
                                    sorted(kinds[chip].items())},
        "scoped_share": 1.0 - phases[chip].get(UNSCOPED, 0.0) / ops_s,
        "span_ms_per_call": {n: 1e3 * host.span_s(n) / t_calls
                             for n in run_spans},
        "host_ms_per_call": 1e3 * (host.span_s("fed.run.schedule")
                                   + host.span_s("fed.run.result")) / t_calls,
        "idle_gaps_s": dict(sorted(host.gaps.items(), key=lambda kv: -kv[1])),
        "idle_in_call_to_program_span": (idle_run / idle_in_call
                                         if idle_in_call > 0 else None),
        "kernel_ms_per_round": {k: 1e3 * v / t_rounds for k, v in
                                reduced.kernel_time_s[chip].items()},
        "top_ops_ms_per_round": _top_ops(reduced, scopes, chip, t_rounds),
    }
    for k, v in out["phase_ms_per_round"].items():
        _log(f"phase {k}: {v!r} ms/round "
             f"({100 * v * t_rounds / 1e3 / busy:.2f}% of busy)")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
