"""Helpers of the benchmark's own tests: a copy of ``bench/`` and a
``BENCHMARK.json`` at a small size, runnable on the CPU."""
from __future__ import annotations

import contextlib
import io
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

# the configuration's widths stay (the control's rounding shows at them)
SMALL_CONFIG = {"hw": 16, "n_train": 1200, "n_test": 100}
SMALL_TRAFFIC = {"num_clients": 12, "clients_per_round": 4,
                 "local_steps": 3, "batch_size": 8, "rounds_per_call": 3,
                 "eval_every": 2}


def small_root(tmp: Path, cell: str, entry: dict = None,
               limits: dict = None) -> Path:
    """A checkout in ``tmp`` whose only cell is ``cell`` of the real
    benchmark (or the workload ``entry`` with its ``limits``), with its
    configuration and traffic cut to a CPU size (everything else as
    committed)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    root = tmp / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    (root / "src").symlink_to(ROOT / "src")
    if limits is not None:
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(limits))
    w = entry or next(w for w in spec["workloads"] if w["name"] == cell)
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    cfg_path = root / c["file"]
    cfg = json.loads(cfg_path.read_text())
    cfg.update(SMALL_CONFIG)
    cfg_path.write_text(json.dumps(cfg))
    tr_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    tr = json.loads(tr_path.read_text())
    tr.update({k: v for k, v in SMALL_TRAFFIC.items() if k in tr})
    tr_path.write_text(json.dumps(tr))
    spec["workloads"] = [w]
    spec["configs"] = [c]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


_CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_compilation_cache_max_size")


@contextlib.contextmanager
def jax_cache_flags_restored():
    """Put back the compile-cache flags that ``harness.setup_jax`` sets."""
    import jax
    saved = {k: getattr(jax.config, k) for k in _CACHE_FLAGS}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


@contextlib.contextmanager
def on_cpu(monkeypatch, root: Path):
    """Point the harness at ``root`` and skip its look for a chip."""
    import harness
    real = harness.setup_jax
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    def cpu_jax(chips):
        jax = real(chips, require_tpu=False)
        # the tests leave no compile cache behind in the checkout
        jax.config.update("jax_enable_compilation_cache", False)
        return jax

    monkeypatch.setattr(harness, "setup_jax", cpu_jax)
    with jax_cache_flags_restored():
        yield harness


def run_cell(monkeypatch, root: Path, cell: str, seed: int = 2**31 + 77):
    """``bench/run.py`` in this process; returns ``(rc, result line)``."""
    import run
    out = io.StringIO()
    with on_cpu(monkeypatch, root), contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", "0.2", "--trace", "0"])
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def unchanged_state(monkeypatch, algorithm):
    """Plant a fault in the program: the round body returns the weights
    it was given."""
    import dataclasses
    from repro.fed import algorithms
    algo = algorithms.get_algorithm(algorithm)

    def make(loss_fn, cfg, params):
        body = algo.make_round_body(loss_fn, cfg, params)

        def stuck(seed, w, state, *args):
            out = body(seed, w, state, *args)
            return (w,) + tuple(out[1:])
        return stuck

    monkeypatch.setitem(algorithms.ALGORITHMS, algorithm,
                        dataclasses.replace(algo, make_round_body=make))


def half_batch(monkeypatch, algorithm):
    """Plant a fault in the program: the model's loss is the mean over
    half of the batch."""
    from repro.models import cnn
    loss = cnn.cnn_loss
    monkeypatch.setattr(cnn, "cnn_loss", lambda p, b: loss(
        p, (b[0][:b[0].shape[0] // 2], b[1][:b[1].shape[0] // 2])))


PROGRAM_FAULTS = {"unchanged_state": unchanged_state,
                  "half_batch": half_batch}


def control_readings(monkeypatch, tmp, cell, faults, control=True,
                     seeds=(2**31 + 5,), root=None):
    """``calibrate.readings`` at the small size on the CPU."""
    import calibrate
    root = root or small_root(tmp, cell)
    with on_cpu(monkeypatch, root) as harness:
        ctx = harness.workload(cell)
        harness.setup_jax(ctx["chips"])
        c = harness.engine(ctx["traffic"]).Cell(ctx, seeds[0])
        res = calibrate.readings(c, list(seeds), len(seeds), list(faults),
                                 log=lambda s: None, control_run=control)
    return ctx["limits"], res


def fails_a_limit(numbers, limits):
    return any(not (numbers[k] <= v) for k, v in limits.items())

