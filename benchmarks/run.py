"""Benchmark harness: one section per paper table/figure (+ roofline).

Prints ``name,us_per_call,derived`` CSV.  ``derived`` is the
table-specific metric (accuracy for Tables/Figs, bits-per-param for the
comm table, useful-compute ratio for the roofline).

The ``engine``/``kernels``/``scale``/``service`` sections additionally write
machine-readable results (per-engine rates + config + commit) to
``BENCH_<name>.json`` at the repo root, so the bench trajectory is
tracked across commits instead of living only in stdout.  On every
invocation the harness checks the tracked BENCH files' recorded commits
against HEAD and warns about any that is NOT an ancestor (i.e. the
numbers predate a rebase/amend and no longer belong to this history).
A section that raises prints ``<name>/ERROR`` and its traceback, and the
harness then exits non-zero.  Compiled programs are cached in
``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``.jax_cache/`` at
the repo root.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
import argparse
import glob
import json
import os
import subprocess
import sys
import traceback

ROOT = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _warn_stale_bench_files() -> None:
    """Warn when a BENCH_*.json records a commit that is not an ancestor
    of HEAD — its numbers were produced on a line of history this
    checkout does not contain (rebase/amend), so the bench trajectory
    has a hole until the section is re-run."""
    for path in sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json"))):
        try:
            commit = json.load(open(path)).get("commit", "unknown")
        except (OSError, ValueError):
            continue
        if commit == "unknown":
            continue
        try:
            ok = subprocess.run(
                ["git", "merge-base", "--is-ancestor", commit, "HEAD"],
                cwd=ROOT, capture_output=True).returncode == 0
        except OSError:       # no git binary
            return
        if not ok:
            print(f"# WARNING: {os.path.basename(path)} was recorded at "
                  f"{commit[:12]}, which is not an ancestor of HEAD — "
                  f"re-run its section to refresh it", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds / smaller populations (CI mode)")
    ap.add_argument("--only", default=None,
                    help="table1|fig4|fig5|fig6|comm|engine|kernels|"
                         "scale|service|privacy|roofline")
    args = ap.parse_args()

    _warn_stale_bench_files()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))

    from . import (engine_bench, fl_suite, kernel_bench, privacy_bench,
                   roofline_report, scale_bench, service_bench)

    rounds = 6 if args.quick else 15
    sections = {
        "table1": lambda: fl_suite.table1_accuracy(rounds=rounds),
        "fig4": lambda: fl_suite.fig4_ablation(rounds=rounds),
        "fig5": lambda: fl_suite.fig5_noise(rounds=max(4, rounds - 3)),
        "fig6": fl_suite.fig6_complexity,
        "comm": fl_suite.comm_table,
        "engine": lambda: (
            engine_bench.engine_rows(n_rounds=10 if args.quick else 30)
            + engine_bench.sweep_rows(n_rounds=5 if args.quick else 10,
                                      n_seeds=8 if args.quick else 32)
            + engine_bench.wire_rows(n_rounds=5 if args.quick else 20)),
        "kernels": lambda: kernel_bench.kernel_rows(smoke=args.quick),
        "scale": lambda: scale_bench.scale_rows(quick=args.quick),
        "service": lambda: service_bench.service_rows(quick=args.quick),
        "privacy": lambda: privacy_bench.privacy_rows(quick=args.quick),
        "roofline": roofline_report.roofline_rows,
    }
    if args.only:
        sections = {args.only: sections[args.only]}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in sections.items():
        try:
            rows = fn()
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"{row['derived']}")
                sys.stdout.flush()
            if name == "engine":
                path = engine_bench.write_bench_json(
                    rows, n_rounds=10 if args.quick else 30,
                    n_sweep_seeds=8 if args.quick else 32)
                print(f"# wrote {path}", file=sys.stderr)
            elif name == "kernels":
                path = kernel_bench.write_bench_json(rows,
                                                     smoke=args.quick)
                print(f"# wrote {path}", file=sys.stderr)
            elif name == "scale":
                path = scale_bench.write_bench_json(rows,
                                                    quick=args.quick)
                print(f"# wrote {path}", file=sys.stderr)
            elif name == "service":
                path = service_bench.write_bench_json(rows,
                                                      quick=args.quick)
                print(f"# wrote {path}", file=sys.stderr)
            elif name == "privacy":
                path = privacy_bench.write_bench_json(rows,
                                                      quick=args.quick)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — report, run the rest
            traceback.print_exc()
            print(f"{name}/ERROR,0.0,{type(e).__name__}")
            failed.append(name)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
