"""Masking strategies: DM, SM, PM, PSM (paper §3.2) with STE backprop.

All functions are elementwise over arrays; pytree variants map them over
leaves with per-leaf folded keys.  Conventions:

- ``u``  — trainable copy of the model update (the only trainable variable).
- ``n``  — the predefined random noise G(s) (same shape as ``u``).
- binary mode: mask m ∈ {0,1}, masked noise û = n·m          (Eq. 6)
- signed mode: mask m ∈ {-1,1}, masked noise û = n·m         (Eq. 7)

Zero-noise guard: with continuous noise P(n=0)=0, but we still guard the
division so Bernoulli probabilities are always well defined.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .backend import resolve_backend

Pytree = Any
_EPS = 1e-30

MASK_MODES = ("binary", "signed")


def _safe_div(a, b):
    return a / jnp.where(jnp.abs(b) < _EPS, jnp.where(b < 0, -_EPS, _EPS), b)


# ---------------------------------------------------------------------------
# probabilities (Eq. 6 / Eq. 7)
# ---------------------------------------------------------------------------

def mask_prob_binary(u: jax.Array, n: jax.Array) -> jax.Array:
    """P[m=1] = clip(u/n, 0, 1).  Unbiased when u/n ∈ [0, 1]."""
    return jnp.clip(_safe_div(u, n), 0.0, 1.0)


def mask_prob_signed(u: jax.Array, n: jax.Array) -> jax.Array:
    """P[m=+1] = clip((u+n)/(2n), 0, 1).  Unbiased when u/n ∈ [-1, 1]."""
    return jnp.clip(_safe_div(u + n, 2.0 * n), 0.0, 1.0)


# ---------------------------------------------------------------------------
# mask sampling
# ---------------------------------------------------------------------------

def sample_mask(u, n, key, *, mode: str = "binary") -> jax.Array:
    """Bernoulli-sample the mask (SM); returns {0,1} or {-1,+1} as int8."""
    if mode == "binary":
        p = mask_prob_binary(u, n)
        return jax.random.bernoulli(key, p).astype(jnp.int8)
    elif mode == "signed":
        p = mask_prob_signed(u, n)
        b = jax.random.bernoulli(key, p)
        return jnp.where(b, jnp.int8(1), jnp.int8(-1))
    raise ValueError(f"unknown mask mode {mode!r}")


def deterministic_mask(u, n, *, mode: str = "binary") -> jax.Array:
    """DM baseline (paper §3.2.1): sign agreement, no sampling — biased."""
    if mode == "binary":
        return (jnp.sign(u) == jnp.sign(n)).astype(jnp.int8)
    elif mode == "signed":
        same = jnp.sign(u) * jnp.sign(n) >= 0
        return jnp.where(same, jnp.int8(1), jnp.int8(-1))
    raise ValueError(f"unknown mask mode {mode!r}")


# ---------------------------------------------------------------------------
# SM: stochastic masking with straight-through estimator (Eq. 8/9)
# ---------------------------------------------------------------------------

@jax.custom_jvp
def _ste(u, hat):
    """Forward = ``hat`` EXACTLY; gradient flows to ``u`` as identity.

    The textbook ``u + stop_gradient(hat - u)`` form re-derives ``hat``
    through two float additions and lands 1 ULP off for some elements —
    which breaks bitwise parity with the fused Pallas kernel (and the
    server-side n·m reconstruction).  A custom_jvp keeps the forward value
    untouched and the Eq.(9) straight-through gradient; the tangent rule
    is linear, so both forward- and reverse-mode autodiff work.
    """
    return hat


@_ste.defjvp
def _ste_jvp(primals, tangents):
    u, hat = primals
    t_u, _t_hat = tangents
    return hat, t_u


def stochastic_masking(u, n, key, *, mode: str = "binary") -> jax.Array:
    """û = S(u, n) = n ⊙ M(u, n) with ∂û/∂u = 1 (STE).

    Forward value is the masked random noise; the gradient flows to ``u``
    unchanged, per Eq.(9).
    """
    m = sample_mask(u, n, key, mode=mode)
    hat = n * m.astype(u.dtype)
    return _ste(u, hat)


def clip_to_noise(u, n, *, mode: str = "binary") -> jax.Array:
    """ū = clip(u, G(s)) (Eq. 10 text): binary → interval [0, n] (or [n, 0]);
    signed → [-|n|, |n|]."""
    if mode == "binary":
        lo = jnp.minimum(n, 0.0)
        hi = jnp.maximum(n, 0.0)
    else:
        hi = jnp.abs(n)
        lo = -hi
    return jnp.clip(u, lo, hi)


# ---------------------------------------------------------------------------
# PSM: progressive stochastic masking (Eq. 10, Algorithm 1 lines 15-18)
# ---------------------------------------------------------------------------

def progressive_stochastic_masking(
    u, n, key, *, progress, mode: str = "binary"
) -> jax.Array:
    """û = (1-P)⊙ū + P⊙S(u, n), P ~ Bern(progress); STE throughout.

    ``progress`` = τ/S ∈ [0,1]; at 1.0 every element is masked noise, which is
    what the final uplink transmits.
    """
    k_sm, k_pm = jax.random.split(key)
    hat_sm = stochastic_masking(u, n, k_sm, mode=mode)  # carries its own STE
    bar = clip_to_noise(u, n, mode=mode)                 # differentiable clip
    P = jax.random.bernoulli(k_pm, progress, jnp.shape(u))
    return jnp.where(P, hat_sm, bar)


def masked_noise_from_mask(n, m):
    """Reconstruct û = n ⊙ m given a {0,1}/{-1,1} mask (server side)."""
    return n * m.astype(n.dtype)


# ---------------------------------------------------------------------------
# pytree variants — one folded key per leaf
# ---------------------------------------------------------------------------

def _tree_keyed_map(fn, key: jax.Array, tree: Pytree, *rest: Pytree) -> Pytree:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rests = [jax.tree_util.tree_flatten(r)[0] for r in rest]
    out = []
    for i, leaf in enumerate(leaves):
        lk = jax.random.fold_in(key, i)
        out.append(fn(leaf, *(r[i] for r in rests), lk))
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_sample_mask(u: Pytree, n: Pytree, key, *, mode="binary") -> Pytree:
    return _tree_keyed_map(
        lambda ul, nl, k: sample_mask(ul, nl, k, mode=mode), key, u, n
    )


def tree_psm(u: Pytree, n: Pytree, key, *, progress, mode="binary",
             backend: str | None = None) -> Pytree:
    """PSM over a pytree, dispatched to the selected kernel backend.

    ``backend="pallas"`` routes each leaf through the fused Pallas kernel
    (``kernels/psm_mask``) — one HBM read/write instead of ~6 elementwise
    passes — with STE gradients identical to the reference path.  Both
    backends consume the same per-leaf folded key streams, so outputs are
    equal (and the pallas path is validated bitwise in interpret mode).
    """
    backend = resolve_backend(backend)
    if backend == "pallas":
        from ..kernels.psm_mask.ops import psm_ste
        return _tree_keyed_map(
            lambda ul, nl, k: psm_ste(ul, nl, k, progress, mode=mode),
            key, u, n,
        )
    return _tree_keyed_map(
        lambda ul, nl, k: progressive_stochastic_masking(
            ul, nl, k, progress=progress, mode=mode
        ),
        key, u, n,
    )


def tree_sample_mask_stacked(u: Pytree, n: Pytree, keys, *,
                             mode="binary") -> Pytree:
    """Client-stacked final-mask draw: row k of every leaf is exactly
    ``tree_sample_mask(u_k, n_k, keys[k])``.  The per-client ``fold_in``
    /uniform streams are counter-based, so vmapping them over the client
    axis reproduces the per-client calls bit for bit — this is the
    staged sampler the fused uplink is verified against.
    """
    return jax.vmap(
        lambda ul, nl, k: tree_sample_mask(ul, nl, k, mode=mode)
    )(u, n, keys)


def tree_bernoulli_stacked(probs: Pytree, keys) -> Pytree:
    """Client-stacked per-leaf Bernoulli draw (the FedPM uplink): row k of
    leaf i is ``bernoulli(fold_in(keys[k], i), probs_k_i)``."""
    return jax.vmap(
        lambda pt, k: _tree_keyed_map(
            lambda pl, lk: jax.random.bernoulli(lk, pl), k, pt)
    )(probs, keys)


class TreeUplink(NamedTuple):
    """One round's fused mask uplink over a client-stacked param tree.

    ``counts``/``wsum`` are FLAT ``(P,)`` buffers in ``tree_flat_layout``
    leaf order (split with ``packing.tree_split_flat``); ``words`` is the
    same ``(K, ceil(P/32))`` payload ``tree_pack_stacked`` produces.
    """

    words: jax.Array    # (K, ceil(P/32)) uint32 wire rows
    counts: jax.Array   # (P,) int32 Σ_k m_k (signed: Σ ±1)
    wsum: jax.Array     # (P,) f32 Σ_k w_k · v_k


def tree_mask_uplink(u: Pytree, n: Pytree, keys, weights, *, mode="binary",
                     wsum_values=True, probs=False,
                     backend: str | None = None) -> TreeUplink:
    """The whole uplink hot path in one pass: sample the final masks,
    bitpack them, and reduce the server-side count/weighted sums.

    Draws the SAME per-(client, leaf) uniform streams as
    :func:`tree_sample_mask_stacked` (``bernoulli(k, p)`` ≡
    ``uniform(k) < p``), so the packed words match the staged
    ``tree_sample_mask → tree_pack_stacked`` composition bit for bit.
    ``probs=True`` treats ``u`` as Bernoulli probabilities directly
    (FedPM; ``n`` ignored) and matches :func:`tree_bernoulli_stacked`.
    ``backend="pallas"`` runs the fused Pallas kernel (interpret mode
    on a CPU host); ``"ref"`` the single-program jnp oracle — neither ever
    materializes the f32 mask tree or an unpacked bit tensor.
    """
    from ..kernels.mask_uplink.ops import mask_uplink_fused

    backend = resolve_backend(backend)
    leaves_u = jax.tree_util.tree_leaves(u)
    leaves_n = None if probs else jax.tree_util.tree_leaves(n)
    K = leaves_u[0].shape[0]
    flats_u, flats_n, flats_r = [], [], []
    for i, ul in enumerate(leaves_u):
        shape = ul.shape[1:]
        lk = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
        r = jax.vmap(
            lambda k: jax.random.uniform(k, shape, jnp.float32))(lk)
        flats_r.append(r.reshape(K, -1))
        flats_u.append(ul.reshape(K, -1))
        if not probs:
            flats_n.append(leaves_n[i].reshape(K, -1))
    uf = jnp.concatenate(flats_u, axis=1)
    rf = jnp.concatenate(flats_r, axis=1)
    nf = None if probs else jnp.concatenate(flats_n, axis=1)
    out = mask_uplink_fused(uf, nf, rf, None, None, weights,
                            mode=("prob" if probs else mode),
                            wsum_values=wsum_values,
                            use_pallas=(backend == "pallas"))
    return TreeUplink(out.words, out.counts, out.wsum)


def tree_sm(u: Pytree, n: Pytree, key, *, mode="binary") -> Pytree:
    return _tree_keyed_map(
        lambda ul, nl, k: stochastic_masking(ul, nl, k, mode=mode), key, u, n
    )


def tree_masked_noise(n: Pytree, m: Pytree) -> Pytree:
    return jax.tree_util.tree_map(masked_noise_from_mask, n, m)
