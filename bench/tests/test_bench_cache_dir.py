"""The compile cache stays with the run: ``JAX_COMPILATION_CACHE_DIR`` is
taken where it lies inside the checkout or the run's own ``HOME``,
``XDG_CACHE_HOME`` or ``TMPDIR``, and the checkout's fixed directory
otherwise."""
import pytest

import harness


@pytest.fixture
def run_dirs(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    monkeypatch.setattr(harness, "ROOT", root)
    for k in ("HOME", "XDG_CACHE_HOME", "TMPDIR"):
        monkeypatch.setenv(k, str(tmp_path / k.lower()))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    return tmp_path, root


def test_unset_gives_the_checkouts_fixed_directory(run_dirs):
    _, root = run_dirs
    assert harness.cache_dir() == root / ".bench_cache" / "jax"


@pytest.mark.parametrize("under", ["home", "xdg_cache_home", "tmpdir",
                                   "checkout"])
def test_a_directory_of_the_runs_own_is_taken(run_dirs, monkeypatch, under):
    tmp, _ = run_dirs
    want = tmp / under / "jax-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(want))
    assert harness.cache_dir() == want.resolve()


def test_a_shared_directory_is_not_taken(run_dirs, monkeypatch):
    tmp, root = run_dirs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "shared"))
    assert harness.cache_dir() == root / ".bench_cache" / "jax"
    monkeypatch.setenv("HOME", "/")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/var/cache/jax")
    assert harness.cache_dir() == root / ".bench_cache" / "jax"
