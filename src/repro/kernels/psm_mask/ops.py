"""Public op: fused PSM on arbitrary-shaped tensors (+ pytree variant).

``use_pallas=False`` runs the jnp oracle — bitwise-identical by
construction (same uniforms).  Whether the kernel is interpreted is not
the caller's choice: ``core.backend.pallas_interpret`` decides it from
the platform.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ...core.backend import pallas_interpret
from ..tiling import to_lane_tiles as _to_tiles
from .psm_mask import psm_fused
from .ref import psm_ref


def _draw_uniforms(key: jax.Array, shape):
    k_sm, k_pm = jax.random.split(key)
    r_sm = jax.random.uniform(k_sm, shape, jnp.float32)
    r_pm = jax.random.uniform(k_pm, shape, jnp.float32)
    return r_sm, r_pm


def _psm_from_uniforms(u, n, r_sm, r_pm, progress, *, mode, use_pallas):
    if not use_pallas:
        return psm_ref(u, n, r_sm, r_pm, progress, mode=mode)
    shape = u.shape
    ut, nelem = _to_tiles(u)
    nt, _ = _to_tiles(n)
    rs, _ = _to_tiles(r_sm)
    rp, _ = _to_tiles(r_pm)
    uhat, mask = psm_fused(ut, nt, rs, rp, progress, mode=mode,
                           interpret=pallas_interpret())
    return (uhat.reshape(-1)[:nelem].reshape(shape),
            mask.reshape(-1)[:nelem].reshape(shape))


def psm_apply(u: jax.Array, n: jax.Array, key: jax.Array, progress,
              *, mode: str = "binary", use_pallas: bool = True):
    """PSM on a tensor of any shape → (û, mask int8) with u's shape."""
    r_sm, r_pm = _draw_uniforms(key, u.shape)
    return _psm_from_uniforms(u, n, r_sm, r_pm, progress, mode=mode,
                              use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# STE-differentiable wrapper — what core.masking's backend dispatch calls.
#
# The fused kernel computes forward values only; local training
# differentiates through PSM, so we attach the exact VJP of the reference
# formula:
#   out = where(gate, hat_sm, bar),  hat_sm = u + stop_grad(n·m − u) (∂ = 1)
#   bar = clip(u, lo, hi)                               (∂ = clip's vjp)
# making backend="pallas" gradient-identical to backend="ref".
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _psm_ste_core(u, n, r_sm, r_pm, progress, mode):
    uhat, _ = _psm_from_uniforms(u, n, r_sm, r_pm, progress, mode=mode,
                                 use_pallas=True)
    return uhat


def _psm_ste_fwd(u, n, r_sm, r_pm, progress, mode):
    uhat = _psm_ste_core(u, n, r_sm, r_pm, progress, mode)
    gate = r_pm < jnp.asarray(progress, jnp.float32)
    return uhat, (u, n, gate)


def ste_clip_bwd(mode, u, n, gate, g):
    """Cotangent to ``u`` of ``where(gate, hat_sm, clip(u, lo(n), hi(n)))``.

    ``hat_sm`` carries the Eq.(9) straight-through ∂/∂u = 1; the ungated
    branch is the clip's exact VJP.  ``gate=None`` means progress ≡ 1
    (every element masked) → the cotangent is ``g`` unchanged.  Shared by
    the psm_mask and mask_uplink fused ops so their STE rules cannot
    drift apart.
    """
    if gate is None:
        return g
    if mode == "binary":
        lo = jnp.minimum(n, 0.0)
        hi = jnp.maximum(n, 0.0)
    else:
        hi = jnp.abs(n)
        lo = -hi
    _, clip_vjp = jax.vjp(lambda uu: jnp.clip(uu, lo, hi), u)
    zero = jnp.zeros_like(g)
    return jnp.where(gate, g, zero) + clip_vjp(jnp.where(gate, zero, g))[0]


def _psm_ste_bwd(mode, res, g):
    u, n, gate = res
    ct_u = ste_clip_bwd(mode, u, n, gate, g)
    return (ct_u, jnp.zeros_like(n), jnp.zeros_like(g), jnp.zeros_like(g),
            jnp.zeros((), jnp.float32))


_psm_ste_core.defvjp(_psm_ste_fwd, _psm_ste_bwd)


def psm_ste(u: jax.Array, n: jax.Array, key: jax.Array, progress,
            *, mode: str = "binary") -> jax.Array:
    """Differentiable PSM û via the fused kernel (STE gradients as ref)."""
    r_sm, r_pm = _draw_uniforms(key, u.shape)
    return _psm_ste_core(u, n, r_sm, r_pm,
                         jnp.asarray(progress, jnp.float32), mode)


def psm_apply_tree(u: Any, n: Any, key: jax.Array, progress,
                   *, mode: str = "binary", **kw):
    leaves_u, treedef = jax.tree_util.tree_flatten(u)
    leaves_n = jax.tree_util.tree_leaves(n)
    outs = []
    for i, (ul, nl) in enumerate(zip(leaves_u, leaves_n)):
        outs.append(psm_apply(ul, nl, jax.random.fold_in(key, i),
                              progress, mode=mode, **kw))
    uhat = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    mask = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return uhat, mask
