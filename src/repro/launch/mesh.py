"""Production mesh construction (spec §MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module constant — importing this module never touches
jax device state.  v5e hardware constants for the roofline live here too.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Degenerate 1×1 mesh for CPU smoke runs of the sharded programs."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """TPU v5e per-chip roofline constants (target hardware)."""

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12     # FLOP/s
    hbm_bw: float = 819e9               # B/s
    ici_bw: float = 50e9                # B/s per link
    hbm_bytes: float = 16e9             # capacity


V5E = HardwareSpec()
