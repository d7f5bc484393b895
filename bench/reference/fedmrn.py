"""Plain reference of one FedMRN round (arXiv 2408.03220, Algorithm 1).

Each picked client k keeps the global weights w frozen and trains an
update u (from 0) for S local SGD steps.  Every forward pass uses
progressive stochastic masking (PSM, Eq. 10): with probability
``progress = (tau + 1) / S`` an element of u is replaced by its masked
noise n * m, m ~ Bernoulli(clip(u / n, 0, 1)) (Eq. 6), otherwise by u
clipped to [min(n, 0), max(n, 0)]; the gradient reaches u straight
through the mask (Eq. 9).  After training the client draws its final
mask and ships it with the noise seed; the server adds
sum_k w_k n_k * m_k (Eq. 5), or with shared noise n * (sum_k m_k) / K.

Noise is uniform in [-alpha, alpha), keyed per (round, client) or, with
shared noise, per round.  The random streams are the ones the
federation's seed defines: noise ``fold_in(fold_in(key(seed), round),
noise_id)``, training ``fold_in(key(seed + 1), round * 1000 + client)``
with one ``fold_in`` per step and per leaf, and the final mask
``fold_in(train_key, S + 1)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from refround import client_batches, half_batch

_EPS = 1e-30


@jax.custom_jvp
def _ste(u, hat):
    """Forward value ``hat`` exactly; gradient to ``u`` unchanged."""
    return hat


@_ste.defjvp
def _ste_jvp(primals, tangents):
    return primals[1], tangents[0]


def _safe_div(a, b):
    return a / jnp.where(jnp.abs(b) < _EPS, jnp.where(b < 0, -_EPS, _EPS), b)


def _prob(u, n):
    return jnp.clip(_safe_div(u, n), 0.0, 1.0)


def _leaf_keyed(fn, key, *trees):
    leaves = [jax.tree_util.tree_leaves(t) for t in trees]
    treedef = jax.tree_util.tree_structure(trees[0])
    return jax.tree_util.tree_unflatten(treedef, [
        fn(jax.random.fold_in(key, i), *ls) for i, ls in
        enumerate(zip(*leaves))])


def noise_tree(key, like, alpha):
    return _leaf_keyed(
        lambda k, l: jax.random.uniform(k, l.shape, jnp.float32, -alpha,
                                        alpha), key, like)


def psm(u, n, key, progress):
    def leaf(k, ul, nl):
        k_sm, k_pm = jax.random.split(k)
        m = jax.random.uniform(k_sm, ul.shape) < _prob(ul, nl)
        hat = _ste(ul, nl * m.astype(jnp.float32))
        bar = jnp.clip(ul, jnp.minimum(nl, 0.0), jnp.maximum(nl, 0.0))
        gate = jax.random.uniform(k_pm, ul.shape) < progress
        return jnp.where(gate, hat, bar)
    return _leaf_keyed(leaf, key, u, n)


def final_mask(u, n, key):
    return _leaf_keyed(
        lambda k, ul, nl: jax.random.uniform(k, ul.shape) < _prob(ul, nl),
        key, u, n)


def make_round(fam, cfg, traffic, batch_seed, *, quant=None, fault=None):
    """``round(seed, w, r, picked, data) -> (new_w, losses (K, S))``,
    jitted.  ``quant`` runs the network in a lower precision (the
    control); ``fault`` plants "unchanged" or "half_batch"."""
    S, B = traffic["local_steps"], traffic["batch_size"]
    lr, alpha = traffic["lr"], traffic["noise_alpha"]
    shared = traffic["shared_noise"]
    loss = partial(fam.ref_loss, cfg, quant=quant)
    if fault == "half_batch":
        loss = half_batch(loss)

    def client(seed, w, r, cid, data):
        noise_id = jnp.int32(0) if shared else cid
        noise = noise_tree(jax.random.fold_in(jax.random.fold_in(
            jax.random.key(seed), r), noise_id), w, alpha)
        train_key = jax.random.fold_in(jax.random.key(seed + 1),
                                       r * 1000 + cid)
        xs, ys = client_batches(data, batch_seed, r, cid, S, B)

        def step(u, inp):
            tau, x, y = inp
            progress = (tau.astype(jnp.float32) + 1.0) / S
            k = jax.random.fold_in(train_key, tau)

            def f(u_):
                u_hat = psm(u_, noise, k, progress)
                return loss(jax.tree_util.tree_map(jnp.add, w, u_hat),
                            (x, y))

            l, g = jax.value_and_grad(f)(u)
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, u, g), l

        u0 = jax.tree_util.tree_map(jnp.zeros_like, w)
        u, losses = jax.lax.scan(step, u0, (jnp.arange(S), xs, ys))
        m = final_mask(u, noise, jax.random.fold_in(train_key, S + 1))
        return noise, m, losses

    @jax.jit
    def round_fn(seed, w, r, picked, data):
        noise, m, losses = jax.vmap(
            lambda c: client(seed, w, r, c, data))(picked)
        K = picked.shape[0]
        wn = jnp.ones((K,), jnp.float32) / K
        if traffic["int_mask_agg"]:
            # shared noise: n * (count / K), the count in the wire's int8
            agg = jax.tree_util.tree_map(
                lambda nl, ml: nl[0] * (jnp.sum(ml.astype(jnp.int32), 0)
                                        .astype(jnp.int8)
                                        .astype(jnp.float32) * wn[0]),
                noise, m)
        else:
            agg = jax.tree_util.tree_map(
                lambda nl, ml: jnp.sum(
                    wn.reshape((K,) + (1,) * (nl.ndim - 1))
                    * (nl * ml.astype(jnp.float32)), 0), noise, m)
        if fault == "unchanged":
            return w, losses
        return jax.tree_util.tree_map(jnp.add, w, agg), losses

    return round_fn


def first_grad(fam, cfg, traffic, batch_seed, seed, w, r, cid, data):
    """The gradient of client ``cid``'s first local step, leaf by leaf."""
    S, B = traffic["local_steps"], traffic["batch_size"]
    train_key = jax.random.fold_in(jax.random.key(seed + 1), r * 1000 + cid)
    noise_id = jnp.int32(0) if traffic["shared_noise"] else cid
    noise = noise_tree(jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed), r), noise_id), w, traffic["noise_alpha"])
    xs, ys = client_batches(data, batch_seed, r, cid, S, B)
    k = jax.random.fold_in(train_key, 0)

    def f(u_):
        u_hat = psm(u_, noise, k, 1.0 / S)
        return fam.ref_loss(cfg, jax.tree_util.tree_map(jnp.add, w, u_hat),
                            (xs[0], ys[0]))

    return jax.grad(f)(jax.tree_util.tree_map(jnp.zeros_like, w))
