"""Kernel-backend selection for the masking/packing hot paths.

``core.masking`` and ``core.packing`` accept ``backend="ref" | "pallas" |
None``.  ``None`` auto-selects: the fused Pallas kernels on TPU, the pure
jnp reference elsewhere.  ``backend="pallas"`` on a CPU host runs the
kernels in interpret mode, which is what the bitwise ref-vs-pallas tests
rely on; :func:`pallas_interpret` is the one place that decides it.

Override order (most local wins): explicit argument > ``use_backend()``
context > ``REPRO_BACKEND`` env var > platform auto-detect.
"""
from __future__ import annotations

import os
from contextlib import contextmanager

import jax

BACKENDS = ("ref", "pallas")

_override: list = []


def _check(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of {BACKENDS}")
    return name


def default_backend() -> str:
    if _override:             # scoped context beats the process-wide env
        return _override[-1]
    env = os.environ.get("REPRO_BACKEND")
    if env:
        return _check(env)
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def resolve_backend(backend: str | None) -> str:
    if backend is None:
        return default_backend()
    return _check(backend)


def pallas_interpret() -> bool:
    """Whether ``pallas_call`` runs in interpret mode: only on a CPU host.

    On a TPU the kernels are compiled by Mosaic; any other platform has
    no Pallas path here and raises instead of interpreting silently.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas kernel path for platform {platform!r}; run on a TPU, "
        "or with backend='ref'")


@contextmanager
def use_backend(name: str):
    """Scoped default-backend override (tests, benchmarks)."""
    _override.append(_check(name))
    try:
        yield
    finally:
        _override.pop()
