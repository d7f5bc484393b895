"""Plain reference of one FedAvg round (McMahan et al., 2017; the
paper's baseline).

Each picked client runs S local SGD steps from the global weights and
uploads its float32 update; the server adds the weighted mean of the
updates, taken as one contraction over the client axis at JAX's default
precision (as the configuration states for every contraction).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from refround import client_batches, half_batch


def make_round(fam, cfg, traffic, batch_seed, *, quant=None, fault=None):
    """``round(seed, w, r, picked, data) -> (new_w, losses (K, S))``."""
    S, B, lr = traffic["local_steps"], traffic["batch_size"], traffic["lr"]
    loss = partial(fam.ref_loss, cfg, quant=quant)
    if fault == "half_batch":
        loss = half_batch(loss)

    def client(w, r, cid, data):
        xs, ys = client_batches(data, batch_seed, r, cid, S, B)

        def step(wk, inp):
            x, y = inp
            l, g = jax.value_and_grad(loss)(wk, (x, y))
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, wk, g), l

        wk, losses = jax.lax.scan(step, w, (xs, ys))
        return jax.tree_util.tree_map(jnp.subtract, wk, w), losses

    @jax.jit
    def round_fn(seed, w, r, picked, data):
        del seed                       # FedAvg draws nothing at random
        u, losses = jax.vmap(lambda c: client(w, r, c, data))(picked)
        K = picked.shape[0]
        leaves, treedef = jax.tree_util.tree_flatten(u)
        flat = jnp.concatenate([l.reshape(K, -1) for l in leaves], axis=1)
        wn = jnp.ones((K,), jnp.float32)
        mean = jnp.tensordot(wn / jnp.sum(wn), flat, axes=1)
        parts, off = [], 0
        for l in leaves:
            size = l[0].size
            parts.append(mean[off:off + size].reshape(l.shape[1:]))
            off += size
        agg = jax.tree_util.tree_unflatten(treedef, parts)
        if fault == "unchanged":
            return w, losses
        return jax.tree_util.tree_map(jnp.add, w, agg), losses

    return round_fn


def first_grad(fam, cfg, traffic, batch_seed, seed, w, r, cid, data):
    """The gradient of client ``cid``'s first local step, leaf by leaf."""
    xs, ys = client_batches(data, batch_seed, r, cid, traffic["local_steps"],
                            traffic["batch_size"])
    return jax.grad(lambda p: fam.ref_loss(cfg, p, (xs[0], ys[0])))(w)
