"""What every reference round shares: the clients' example lists, their
batch stream and the half-batch fault.

Batches follow the derivation a federated dataset keeps for every
(round, client): positions ``randint(fold_in(fold_in(key(batch_seed),
round), client), (S, B), 0, len)`` into the client's examples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def index_matrix(parts):
    """Client example lists as a wrap-padded ``(C, Lmax)`` matrix and
    their true lengths."""
    lens = np.array([len(p) for p in parts], np.int32)
    lmax = int(lens.max())
    idx = np.stack([np.resize(np.asarray(p, np.int64), lmax)
                    for p in parts]).astype(np.int32)
    return jnp.asarray(idx), jnp.asarray(lens)


def client_batches(data, batch_seed: int, r, cid, steps: int, batch: int):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(batch_seed), r), cid)
    pos = jax.random.randint(key, (steps, batch), 0, data["len"][cid])
    take = data["idx"][cid, pos]
    return data["x"][take], data["y"][take]


def half_batch(loss):
    """The fault "half of the batch left out": the mean over the rest."""
    def wrapped(p, batch):
        x, y = batch
        h = x.shape[0] // 2
        return loss(p, (x[:h], y[:h]))
    return wrapped
