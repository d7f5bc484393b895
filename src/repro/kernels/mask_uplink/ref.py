"""Pure-jnp oracle for the fused mask-uplink kernel (same uniforms).

Mirrors the kernel contract exactly — binary popcounts (the signed
Σ(±1) = 2c − K fix lives in ``ops``), little-endian word packing — on
the true unpadded (K, P) shapes.  This is also the single-program jnp fast path
the ``ref`` backend runs: one fused XLA program with no pack→unpack
round trip, versus the three-dispatch staged pipeline it replaces.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD = 32
_EPS = 1e-30


def uplink_ref(u, n, r_sm, r_pm=None, progress=None, weights=None, *,
               mode: str = "binary", wsum_values: bool = True,
               want_uhat: bool = False):
    K, P = u.shape
    u32 = u.astype(jnp.float32)
    if weights is None:
        weights = jnp.ones((K,), jnp.float32)
    uhat = None
    if mode == "prob":
        p = jnp.clip(u32, 0.0, 1.0)
        # materialize the mask ONCE: without the barrier XLA duplicates
        # the sample (div + clip + compare) into each of its consumers
        # (pack, popcount, weighted sum), which costs more than the
        # whole staged pipeline on CPU
        m = jax.lax.optimization_barrier(r_sm < p)
        v = jnp.where(m, 1.0, 0.0)
    else:
        n32 = n.astype(jnp.float32)
        safe_n = jnp.where(jnp.abs(n32) < _EPS, _EPS, n32)
        if mode == "binary":
            p = jnp.clip(u32 / safe_n, 0.0, 1.0)
            m = jax.lax.optimization_barrier(r_sm < p)
            hat_sm = jnp.where(m, n32, 0.0)
            lo = jnp.minimum(n32, 0.0)
            hi = jnp.maximum(n32, 0.0)
            v = hat_sm if wsum_values else jnp.where(m, 1.0, 0.0)
        else:  # signed
            p = jnp.clip((u32 + n32) / (2.0 * safe_n), 0.0, 1.0)
            m = jax.lax.optimization_barrier(r_sm < p)
            hat_sm = jnp.where(m, n32, -n32)
            hi = jnp.abs(n32)
            lo = -hi
            v = hat_sm if wsum_values else jnp.where(m, 1.0, -1.0)
        if want_uhat:
            bar = jnp.clip(u32, lo, hi)
            if r_pm is not None:
                gate = r_pm < jnp.asarray(progress, jnp.float32)
                uhat = jnp.where(gate, hat_sm, bar).astype(u.dtype)
            else:
                uhat = hat_sm.astype(u.dtype)

    bits = m.astype(jnp.uint32)
    pad = (-P) % WORD
    if pad:
        bits = jnp.pad(bits, [(0, 0), (0, pad)])
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    words = jnp.sum(bits.reshape(K, -1, WORD) << shifts[None, None, :],
                    axis=-1, dtype=jnp.uint32)
    # client-axis reductions as an unrolled row walk (K is a small static
    # shape): each step is a contiguous (P,) axpy, where both the strided
    # jnp.sum(axis=0) lowering and a (1,K)x(K,P) dot_general are several
    # times slower on CPU.  Counts stay exact ints — bitwise-same int32.
    counts = m[0].astype(jnp.int32)                       # binary popcount
    wsum = weights[0] * v[0]
    for k in range(1, K):
        counts = counts + m[k].astype(jnp.int32)
        wsum = wsum + weights[k] * v[k]
    return words, counts, wsum, uhat


def unpack_counts_ref(words: jax.Array) -> jax.Array:
    """(K, W) packed rows → (W·32,) int32 binary popcounts.

    Unrolled over the (small, static) client axis: each step unpacks one
    contiguous row — the broadcast-then-``sum(axis=0)`` form materializes
    the full (K, W, 32) bit tensor and reduces it strided.
    """
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    K = words.shape[0]
    acc = ((words[0][:, None] >> shifts[None, :])
           & jnp.uint32(1)).astype(jnp.int32)
    for k in range(1, K):
        acc = acc + (((words[k][:, None] >> shifts[None, :])
                      & jnp.uint32(1)).astype(jnp.int32))
    return acc.reshape(-1)
