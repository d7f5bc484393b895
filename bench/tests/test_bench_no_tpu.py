"""Without a TPU the benchmark's command fails and prints no result."""
import os
import subprocess
import sys

from benchutil import ROOT


def test_no_tpu_fails_without_a_result_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "cnn4-fmnist.fedmrn-k10", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert not any(l.lstrip().startswith("{") for l in p.stdout.splitlines())
    assert "no TPU" in p.stderr


def test_backend_other_than_compiled_pallas_is_refused(monkeypatch):
    """A TPU whose kernel backend resolves to the jnp reference (or to
    interpreted Pallas) is refused like a missing chip."""
    import jax
    import pytest
    import harness
    from benchutil import jax_cache_flags_restored
    from repro.core import backend

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with jax_cache_flags_restored():
        for default, interpret in (("ref", False), ("pallas", True)):
            monkeypatch.setattr(backend, "default_backend", lambda: default)
            monkeypatch.setattr(backend, "pallas_interpret",
                                lambda: interpret)
            with pytest.raises(harness.BenchError, match="compiled Pallas"):
                harness.setup_jax(1)
        monkeypatch.setenv("REPRO_BACKEND", "ref")
        with pytest.raises(harness.BenchError, match="REPRO_BACKEND"):
            harness.setup_jax(1)


def test_without_the_program_fails_without_a_result_line(tmp_path):
    """A directory with only BENCHMARK.json and bench/ is refused."""
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "cnn4-fmnist.fedmrn-k10", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/repro" in p.stderr
