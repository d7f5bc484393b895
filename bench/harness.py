"""Shared plumbing of the benchmark: where things live, how a cell's
files are found by name, the device checks, and the result line.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel sits in a file of its own under ``bench/`` and is found
here by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json      sizes, source, reduced, assumed
    bench/configs/<family>.py        the family's data, init, program
                                     adapter, FLOP count and reference
    bench/traffic/<traffic>.json     engine, algorithm, federation knobs
    bench/engines/<engine>.py        the runner of that engine
    bench/reference/<algorithm>.py   the plain reference round
    bench/limits/<workload>.json     the numbers compared for `correct`
    bench/metrics/<metric>.py        one per-layer reducer each
    bench/kernels/<kernel>.py        FLOPs and HBM bytes of one call
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, wrong backend, ...)."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by its path (names hold '-', '.')."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = None) -> dict:
    path = (root or ROOT) / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def workload(name: str, bench: Path = None, root: Path = None) -> dict:
    """The cell ``name`` with its configuration and traffic loaded."""
    bench, root = bench or BENCH, root or ROOT
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[cell["config"]]
    config = load_json(root / cfg_entry["file"])
    config["name"] = cell["config"]
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    traffic["name"] = cell["traffic"]
    limits_path = bench / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.is_file() else {}
    per_layer = [m for m in spec.get("per_layer", [])
                 if name in m.get("workloads", [name])
                 and any(e["name"] == m["moves"]
                         and name in e.get("workloads", [name])
                         for e in spec["end_to_end"])]
    end_to_end = [e for e in spec["end_to_end"]
                  if name in e.get("workloads", [name])]
    return {"name": name, "chips": int(cell["chips"]), "config": config,
            "traffic": traffic, "limits": limits, "per_layer": per_layer,
            "end_to_end": end_to_end}


def family(config: dict, bench: Path = None):
    return load_module((bench or BENCH) / "configs" / f"{config['family']}.py")


def engine(traffic: dict, bench: Path = None):
    name = traffic["engine"]
    return load_module((bench or BENCH) / "engines" / f"{name}.py")


def reference(algorithm: str, bench: Path = None):
    return load_module((bench or BENCH) / "reference" / f"{algorithm}.py")


def metric(name: str, bench: Path = None):
    return load_module((bench or BENCH) / "metrics" / f"{name}.py")


def kernel(name: str, bench: Path = None):
    return load_module((bench or BENCH) / "kernels" / f"{name}.py")


def peaks(device_kind: str, bench: Path = None) -> dict:
    table = load_json((bench or BENCH) / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table["devices"][device_kind]


def program_seed(seed: int) -> int:
    """The run's seed folded into what the program's int32 and numpy
    seeds hold, with room for the window's ``seed + i``."""
    return int(seed) % 2_000_000_000


def cache_dir() -> Path:
    """``JAX_COMPILATION_CACHE_DIR`` where it lies inside the checkout or
    inside the run's own ``HOME``, ``XDG_CACHE_HOME`` or ``TMPDIR``;
    otherwise (unset, or a path that other checkouts could share) the
    fixed directory in the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        path = Path(env).resolve()
        own = [ROOT] + [Path(os.environ[k]).resolve()
                        for k in ("HOME", "XDG_CACHE_HOME", "TMPDIR")
                        if os.environ.get(k)]
        if any(path.is_relative_to(d) and d != Path("/") for d in own):
            return path
    return ROOT / ".bench_cache" / "jax"


def setup_jax(chips: int, *, require_tpu: bool = True):
    """Import jax with the benchmark's compile cache; fail without the
    chips the cell asks for or without compiled Pallas kernels."""
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the program (src/repro) is not in {ROOT}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if require_tpu and os.environ.get("REPRO_BACKEND"):
        raise BenchError("REPRO_BACKEND is set; the benchmark runs the "
                         "platform's own kernel backend")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir()))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the cell's programs all stay cached: no eviction between runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    if not require_tpu:
        return jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    from repro.core.backend import default_backend, pallas_interpret
    if default_backend() != "pallas" or pallas_interpret():
        raise BenchError("the kernel backend does not resolve to compiled "
                         "Pallas")
    return jax


class CompileCounter:
    """Counts XLA backend compilations and jaxpr traces while active."""

    def __init__(self):
        self.compiles = 0
        self.traces = 0
        self.active = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if not self.active:
            return
        if event.endswith("backend_compile_duration"):
            self.compiles += 1
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1


def host_waits() -> tuple:
    """``(run-queue s, steal s)`` so far: the time this thread waited,
    runnable, for a CPU, and the time the hypervisor held all the
    machine's CPUs (summed over CPUs); zeros where Linux's ``/proc``
    does not give them."""
    try:
        with open("/proc/thread-self/schedstat") as f:
            runq = int(f.read().split()[1]) * 1e-9
        with open("/proc/stat") as f:
            steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return 0.0, 0.0
    return runq, steal


class GcPauses:
    """Host garbage-collector pauses while active: count, sum, longest."""

    def __init__(self):
        self.n, self.total_s, self.max_s = 0, 0.0, 0.0
        self.active, self._t = False, None
        import gc
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n, self.total_s = self.n + 1, self.total_s + d
            self.max_s, self._t = max(self.max_s, d), None


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers last on stderr, then the result line
    (with the compared numbers as its last key) last on stdout."""
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({**result, "checks": checks}))
    sys.stdout.flush()
