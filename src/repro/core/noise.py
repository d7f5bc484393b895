"""Seeded random-noise generation G(s) — the paper's noise generator.

The paper's clients draw noise from a generator ``G`` seeded with a scalar
``s`` that is later shipped to the server (8 bytes).  We realise ``s`` as a
``jax.random`` key derived deterministically from ``(base_seed, round,
client_id)`` via ``fold_in``; server-side regeneration is then *exact* by
construction (same fold chain), which is the property the paper relies on.

Supported distributions (paper §5.5): Uniform[-a, a], Gaussian N(0, a),
Bernoulli {-a, +a}.  Defaults follow the paper: uniform, a=1e-2 for binary
masks (FedMRN) and a=5e-3 for signed masks (FedMRNS).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any

DISTRIBUTIONS = ("uniform", "gauss", "bernoulli")


@dataclasses.dataclass(frozen=True)
class NoiseConfig:
    """Static description of G."""

    dist: str = "uniform"          # one of DISTRIBUTIONS
    alpha: float = 1e-2            # magnitude (paper tunes in {6.25e-4..2e-2})
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.dist not in DISTRIBUTIONS:
            raise ValueError(f"unknown noise dist {self.dist!r}")


def client_round_key(base_seed: int, round_idx, client_id) -> jax.Array:
    """The 'random seed s_k^t' of the paper, as a reproducible PRNG key.

    Only (base_seed, round_idx, client_id) — 3 small ints — determine the
    whole noise tensor pytree, so the uplink cost of 's' is O(1) as claimed.
    """
    key = jax.random.key(base_seed)
    key = jax.random.fold_in(key, round_idx)
    key = jax.random.fold_in(key, client_id)
    return key


def _leaf_noise(key: jax.Array, shape, cfg: NoiseConfig) -> jax.Array:
    if cfg.dist == "uniform":
        return jax.random.uniform(
            key, shape, cfg.dtype, minval=-cfg.alpha, maxval=cfg.alpha
        )
    if cfg.dist == "gauss":
        return cfg.alpha * jax.random.normal(key, shape, cfg.dtype)
    # bernoulli {-a, +a}
    bits = jax.random.bernoulli(key, 0.5, shape)
    return jnp.where(bits, cfg.alpha, -cfg.alpha).astype(cfg.dtype)


def gen_noise(key: jax.Array, tree: Pytree, cfg: NoiseConfig) -> Pytree:
    """Generate a noise pytree matching ``tree``'s shapes/dtypes.

    Each leaf gets an independent stream via ``fold_in(key, leaf_index)`` so
    the result is invariant to leaf sizes (no global offset bookkeeping) and
    identical between client and server.  Its device instructions carry
    the ``fed.noise`` name scope wherever the draw runs (client or server).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    noises = []
    with jax.named_scope("fed.noise"):
        for i, leaf in enumerate(leaves):
            lk = jax.random.fold_in(key, i)
            noises.append(
                _leaf_noise(lk, jnp.shape(leaf), cfg).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, noises)


def gen_noise_like_specs(key: jax.Array, specs: Pytree, cfg: NoiseConfig) -> Pytree:
    """Same as :func:`gen_noise` but from ShapeDtypeStructs (dry-run safe)."""
    return gen_noise(key, specs, cfg)
