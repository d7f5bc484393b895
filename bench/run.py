"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and
a traffic mix; their files, the engine's runner, the reference, the
limits and the per-layer reducers are found by name under ``bench/``.

Set-up (process start to the end of the first call): imports, the
dataset and the weights made on the device, the program built and
compiled (or found in the compile cache under ``.bench_cache/``, or in
``JAX_COMPILATION_CACHE_DIR`` where that lies inside the checkout or the
run's own ``HOME``, ``XDG_CACHE_HOME`` or ``TMPDIR``), and the first
call, whose rounds the reference later follows.  ``--trace 0``
then measures ``--seconds`` of calls and reports the end-to-end
metrics; ``--trace 1`` traces the mix's short window with the profiler
and reports the per-layer metrics.  After the window the program's state
is freed and the plain reference follows the first call, and round 0 of
each further checked call (the mix's ``check_calls``): every compared
number and its limit goes last to stderr and under ``checks`` (last) in
the result line, the last line of stdout.

Without a TPU, with fewer chips than the cell needs, or with the kernel
backend resolving to anything but compiled Pallas, the run exits 2 and
prints no result line.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import harness  # noqa: E402


def traced(cell, ctx, jax, counter):
    trace_mod = harness.load_module(harness.BENCH / "trace.py")
    tdir = harness.ROOT / ".bench_cache" / "trace" / ctx["name"]
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0

    def annotate(name):
        return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)

    with jax.profiler.trace(str(tdir), profiler_options=opts):
        counter.active = True
        rounds, wall = cell.traced_window(annotate)
        counter.active = False
    reduced = trace_mod.reduce(str(tdir), ctx["chips"], cell.hlo_text())
    shutil.rmtree(tdir, ignore_errors=True)
    kind = jax.devices()[0].device_kind
    m = {"reduced": reduced, "cell": cell, "rounds": rounds,
         "chips": ctx["chips"], "peaks": harness.peaks(kind),
         "leaf_sizes": [int(x.size) for x in
                        jax.tree_util.tree_leaves(cell.params)]}
    metrics = {}
    for spec in ctx["per_layer"]:
        value = harness.metric(spec["name"]).reduce(m)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    print(f"traced window: {rounds} rounds in {wall!r} s (host clock); "
          f"device window {reduced.window_s!r} s", file=sys.stderr)
    return rounds, metrics, reduced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        ctx = harness.workload(args.workload)
        jax = harness.setup_jax(ctx["chips"])
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    counter = harness.CompileCounter()
    pauses = harness.GcPauses()
    cell = harness.engine(ctx["traffic"]).Cell(ctx, args.seed)
    first = cell.first_call()
    setup_s = time.time() - T_START
    finite = first["finite"]
    result = {}
    if args.trace:
        rounds, metrics, reduced = traced(cell, ctx, jax, counter)
        result["breakdown"] = reduced.breakdown()
        extra = {"busy_s": sum(reduced.busy_s) / len(reduced.busy_s),
                 "window_s": reduced.window_s}
    else:
        counter.active = pauses.active = True
        rounds, wall, calls = cell.window(args.seconds)
        counter.active = pauses.active = False
        metrics = {"rounds_per_s": {"value": rounds / wall,
                                    "unit": "rounds/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        print(f"window: {rounds} rounds in {calls} calls over {wall!r} s; "
              f"setup {setup_s!r} s", file=sys.stderr)
        print("window calls, wall / host CPU / run-queue / steal s: "
              + ", ".join("/".join(f"{v:.4f}" for v in c)
                          for c in cell.call_s), file=sys.stderr)
        print(f"host gc in the window: {pauses.n} collections, "
              f"{pauses.total_s!r} s, longest {pauses.max_s!r} s",
              file=sys.stderr)
        extra = {}
    print(f"compilations inside the window: {counter.compiles} "
          f"(jaxpr traces {counter.traces}); compile cache "
          f"{jax.config.jax_compilation_cache_dir}", file=sys.stderr)
    if "check_calls" in ctx["traffic"]:
        first["calls"] = cell.checked_calls()
    finite &= cell.finite
    device = {**harness.device_info(jax, ctx["chips"]), **extra}
    cell.release()
    gc.collect()
    t0 = time.time()
    ref = cell.reference(first)
    nums = cell.compare(first, ref)
    print(f"reference: {time.time() - t0!r} s; numbers "
          f"{ {k: v for k, v in sorted(nums.items())} }", file=sys.stderr)
    correct, checks = check.judge(nums, ctx["limits"], finite)
    result = {"correct": correct, "attempted": rounds,
              "failed": 0 if finite else 1, "metrics": metrics,
              "device": device, **result}
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
