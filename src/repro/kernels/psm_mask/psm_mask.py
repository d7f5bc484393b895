"""Fused PSM (progressive stochastic masking) Pallas TPU kernel.

The PSM forward chain (Eq. 6/10 of the paper) is six elementwise ops —
prob = clip(u/n) → SM-Bernoulli → masked noise → clip(u, n) → PM-Bernoulli
→ select.  Executed as separate XLA ops this makes ~6 HBM round-trips over
tensors the size of the model; fused in one Pallas pass each element is
read once (u, n, two pre-drawn uniforms) and written once (û, mask).

Uniform randoms are generated OUTSIDE the kernel (jax.random, seeded — the
server must reproduce G(s) exactly, so RNG stays in the seeded-stream
world) and streamed in; the kernel fuses the arithmetic.

Layout: inputs are flattened to (R, 128·K) tiles; BlockSpec keeps
(BLOCK_R, BLOCK_C) tiles in VMEM — lane-dim multiples of 128 and sublane
multiples of 8, MXU/VPU aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_R = 64
BLOCK_C = 512
_EPS = 1e-30


def _psm_kernel(u_ref, n_ref, r_sm_ref, r_pm_ref, prog_ref,
                uhat_ref, mask_ref, *, mode: str):
    u = u_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    r_sm = r_sm_ref[...]
    r_pm = r_pm_ref[...]
    prog = prog_ref[0]

    safe_n = jnp.where(jnp.abs(n) < _EPS, _EPS, n)
    if mode == "binary":
        p = jnp.clip(u / safe_n, 0.0, 1.0)
        m = (r_sm < p)
        hat_sm = jnp.where(m, n, 0.0)
        lo = jnp.minimum(n, 0.0)
        hi = jnp.maximum(n, 0.0)
    else:  # signed
        p = jnp.clip((u + n) / (2.0 * safe_n), 0.0, 1.0)
        m = (r_sm < p)
        hat_sm = jnp.where(m, n, -n)
        hi = jnp.abs(n)
        lo = -hi
    bar = jnp.clip(u, lo, hi)
    gate = (r_pm < prog)
    uhat_ref[...] = jnp.where(gate, hat_sm, bar).astype(uhat_ref.dtype)
    mask_ref[...] = m.astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("mode", "interpret", "block_r",
                                    "block_c"))
def psm_fused(u: jax.Array, n: jax.Array, r_sm: jax.Array, r_pm: jax.Array,
              progress: jax.Array, *, mode: str = "binary",
              interpret: bool, block_r: int = BLOCK_R,
              block_c: int = BLOCK_C):
    """Fused PSM over 2-D tiles. All of u/n/r_sm/r_pm shaped (R, C).

    Returns (û, mask int8).  ``interpret=True`` runs the kernel body in
    Python (the CPU validation path); the scalar ``progress`` rides in
    SMEM.
    """
    R, C = u.shape
    br, bc = min(block_r, R), min(block_c, C)
    grid = (pl.cdiv(R, br), pl.cdiv(C, bc))
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    prog_arr = jnp.asarray(progress, jnp.float32).reshape(1)
    return pl.pallas_call(
        functools.partial(_psm_kernel, mode=mode),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((R, C), u.dtype),
                   jax.ShapeDtypeStruct((R, C), jnp.int8)],
        interpret=interpret, name="psm_fused",
    )(u, n, r_sm, r_pm, prog_arr)
