"""1-bit mask packing — the wire format of the FedMRN uplink.

A mask tensor (values {0,1} or {-1,+1}) is flattened, padded to a multiple of
32, and packed little-endian into ``uint32`` words.  Signed masks map
-1 → bit 0, +1 → bit 1 (the paper's identity G⊙m_s = 2G⊙m − G makes the two
formats interconvertible).  Packing is what makes the collective/uplink cost
literally 1 bit per parameter — these arrays are what we all-gather across
the client axis in the sharded round and what the comm model counts.

Pure ``jnp`` (no host round-trip) so it stays inside jit/pjit programs.
"""
from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .backend import resolve_backend

Pytree = Any
WORD = 32


def packed_len(n_bits: int) -> int:
    return (n_bits + WORD - 1) // WORD


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack a {0,1}-valued array (any shape) into a 1-D uint32 array.

    bit i of word w corresponds to flat element w*32+i (little-endian).
    """
    flat = bits.reshape(-1).astype(jnp.uint32)
    n = flat.shape[0]
    pad = (-n) % WORD
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint32)])
    words = flat.reshape(-1, WORD)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return jnp.bitwise_or.reduce(words << shifts, axis=1)


def unpack_bits(words: jax.Array, n_bits: int) -> jax.Array:
    """Inverse of :func:`pack_bits`; returns {0,1} int8 of length ``n_bits``."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1)[:n_bits].astype(jnp.int8)


def pack_mask(mask: jax.Array, *, mode: str = "binary") -> jax.Array:
    """Pack a mask tensor ({0,1} binary or {-1,1} signed) to uint32 words."""
    if mode == "binary":
        bits = (mask > 0)
    elif mode == "signed":
        bits = (mask > 0)  # -1 → 0, +1 → 1
    else:
        raise ValueError(f"unknown mask mode {mode!r}")
    return pack_bits(bits)


def unpack_mask(words: jax.Array, n_bits: int, *, mode: str = "binary") -> jax.Array:
    bits = unpack_bits(words, n_bits)
    if mode == "binary":
        return bits
    return (2 * bits - 1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# pytree wire format: one packed uint32 vector for the whole parameter pytree
# ---------------------------------------------------------------------------

def tree_bit_sizes(tree: Pytree):
    """Per-leaf element counts (static)."""
    return [math.prod(jnp.shape(l)) or 1 for l in jax.tree_util.tree_leaves(tree)]


def tree_flat_layout(tree: Pytree):
    """``(leaves, treedef, sizes, offsets)`` of a pytree's flat layout.

    THE one definition of how leaf data maps into a flat wire buffer
    (leaf order, per-leaf element counts, start offsets) — every
    unpack/split path below and in ``fed/codecs.py`` derives from it, so
    a layout change cannot silently fork the wire formats.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [math.prod(jnp.shape(l)) or 1 for l in leaves]
    offsets, off = [], 0
    for sz in sizes:
        offsets.append(off)
        off += sz
    return leaves, treedef, sizes, offsets


def tree_split_flat(flat: jax.Array, like: Pytree, *,
                    leading: Tuple[int, ...] = ()) -> Pytree:
    """Split a flat ``(..., P)`` buffer back into ``like``-shaped leaves.

    ``leading`` names extra leading axes to preserve (e.g. ``(K,)`` for
    a client-stacked buffer); leaf dtypes are NOT cast — callers decide.
    """
    leaves, treedef, sizes, offsets = tree_flat_layout(like)
    out = [flat[..., off: off + sz].reshape(leading + tuple(jnp.shape(l)))
           for l, sz, off in zip(leaves, sizes, offsets)]
    return jax.tree_util.tree_unflatten(treedef, out)


# --- backend-dispatched row packing (the wire hot path) --------------------
#
# ``pack_rows``/``unpack_rows`` operate on a (rows, n_bits) {0,1} matrix —
# one row per client in the batched round — and dispatch to the Pallas
# bitpack kernel (``kernels/bitpack``) or the jnp reference.  Both produce
# the same little-endian uint32 words, so dispatch is value-transparent.

def pack_rows(bits: jax.Array, *, backend: str | None = None) -> jax.Array:
    """(R, n_bits) {0,1} → (R, ceil(n_bits/32)) uint32, little-endian."""
    backend = resolve_backend(backend)
    n_bits = bits.shape[-1]
    if backend == "pallas":
        from ..kernels.bitpack.ops import pack
        pad = (-n_bits) % WORD
        x = bits.astype(jnp.int8)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)])
        return pack(x, use_pallas=True)
    return pack_lastdim(bits)


def unpack_rows(words: jax.Array, n_bits: int,
                *, backend: str | None = None) -> jax.Array:
    """(R, W) uint32 → (R, n_bits) {0,1} int8; inverse of :func:`pack_rows`."""
    backend = resolve_backend(backend)
    if backend == "pallas":
        from ..kernels.bitpack.ops import unpack
        bits = unpack(words, use_pallas=True)
        return bits[..., :n_bits]
    return unpack_lastdim(words, n_bits)


def _tree_bits(mask_tree: Pytree) -> jax.Array:
    """Flatten a mask pytree to one {0,1} bool vector (leaf order)."""
    leaves = jax.tree_util.tree_leaves(mask_tree)
    return jnp.concatenate([(l > 0).reshape(-1) for l in leaves])


def tree_pack(mask_tree: Pytree, *, mode: str = "binary",
              backend: str | None = None) -> jax.Array:
    """Concatenate all leaves' bits into one padded uint32 payload."""
    del mode  # both modes store sign bit identically
    flat = _tree_bits(mask_tree)
    backend = resolve_backend(backend)
    if backend == "pallas":
        return pack_rows(flat[None, :], backend=backend).reshape(-1)
    return pack_bits(flat)


def tree_unpack(words: jax.Array, like: Pytree, *, mode: str = "binary",
                backend: str | None = None) -> Pytree:
    """Unpack one payload into a mask pytree shaped like ``like``."""
    total = sum(tree_flat_layout(like)[2])
    backend = resolve_backend(backend)
    if backend == "pallas":
        bits = unpack_rows(words[None, :], total, backend=backend)[0]
    else:
        bits = unpack_bits(words, total)
    if mode == "signed":
        bits = (2 * bits - 1).astype(jnp.int8)
    return tree_split_flat(bits, like)


def tree_pack_stacked(mask_tree: Pytree, *, mode: str = "binary",
                      backend: str | None = None) -> jax.Array:
    """Pack a client-stacked mask pytree (leading axis K on every leaf).

    Returns the (K, ceil(P/32)) uint32 payload matrix — row k is exactly
    ``tree_pack`` of client k's mask, but the whole batch is packed in one
    kernel launch, which is the uplink hot path of the batched round.
    """
    del mode
    leaves = jax.tree_util.tree_leaves(mask_tree)
    K = leaves[0].shape[0]
    flat = jnp.concatenate(
        [(l > 0).reshape(K, -1) for l in leaves], axis=1)
    return pack_rows(flat, backend=backend)


def tree_unpack_stacked(words: jax.Array, like: Pytree, *,
                        mode: str = "binary",
                        backend: str | None = None) -> Pytree:
    """Inverse of :func:`tree_pack_stacked`: (K, W) → stacked mask pytree."""
    total = sum(tree_flat_layout(like)[2])
    K = words.shape[0]
    bits = unpack_rows(words, total, backend=backend)
    if mode == "signed":
        bits = (2 * bits - 1).astype(jnp.int8)
    return tree_split_flat(bits, like, leading=(K,))


def tree_unpack_counts(words: jax.Array, like: Pytree, *,
                       mode: str = "binary",
                       dtype=jnp.int8,
                       backend: str | None = None) -> Pytree:
    """(K, W) packed rows → per-leaf integer mask-count sums ``Σ_k m_k``.

    The server side of the ``⌈log2(K+1)⌉``-bit mask wire format: unpack
    the K clients' rows and reduce over the client axis in the *integer*
    ``dtype`` (which must hold ±K), so that when the client axis is
    partitioned over a mesh the cross-client all-reduce moves integer
    words instead of f32.  Signed mode sums {-1,+1} values (range ±K).

    On the pallas backend the fused ``kernels/mask_uplink`` counts kernel
    reduces per word-block inside VMEM — the 32×-larger unpacked bit
    tensor never reaches HBM (ref unpacks then sums, same integers).
    """
    total = sum(tree_flat_layout(like)[2])
    backend = resolve_backend(backend)
    if backend == "pallas":
        from ..kernels.mask_uplink.ops import unpack_counts
        c = unpack_counts(words, use_pallas=True)[:total]
        if mode == "signed":
            c = 2 * c - words.shape[0]
        return tree_split_flat(c.astype(dtype), like)
    bits = unpack_rows(words, total, backend=backend)
    if mode == "signed":
        bits = (2 * bits - 1).astype(jnp.int8)
    return tree_split_flat(jnp.sum(bits, axis=0, dtype=dtype), like)


def tree_unpack_counts_apply(words: jax.Array, noise: Pytree, params: Pytree,
                             scale, *, mode: str = "binary",
                             backend: str | None = None) -> Pytree:
    """Aggregated count words → the updated global model, in one op:

        p  ←  (p + n ⊙ (scale · Σ_k m_k)).astype(p.dtype)

    with ``Σ_k m_k`` the per-element client count read straight off the
    (K, W) packed rows (signed mode: Σ ±1 via the 2c − K identity).  On
    the pallas backend this is one ``kernels/mask_uplink`` kernel pass —
    no unpacked bit tensor, no materialized count tree, no separate
    elementwise update sweep.  Equal to ``mix_add(params,
    noise ⊙ (scale · tree_unpack_counts(...)))`` leaf by leaf.
    """
    backend = resolve_backend(backend)
    from ..kernels.mask_uplink.ops import unpack_counts_apply
    K = words.shape[0]
    a, b = (2.0, float(-K)) if mode == "signed" else (1.0, 0.0)
    noise_flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(noise)])
    base_flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(params)])
    out = unpack_counts_apply(words, noise_flat, base_flat, scale, a, b,
                              use_pallas=(backend == "pallas"))
    upd = tree_split_flat(out, params)
    return jax.tree_util.tree_map(lambda p, o: o.astype(p.dtype),
                                  params, upd)


def pack_lastdim(bits: jax.Array) -> jax.Array:
    """Pack {0,1} bits along the LAST dim into uint32 words: (..., D) →
    (..., ceil(D/32)).

    Unlike :func:`pack_bits` this preserves leading dims — and therefore
    their shardings — which is what the sharded pod round needs: each model
    shard packs its own slice, so the packed payload stays model-sharded
    and the client-axis all-gather moves exactly 1 bit per parameter.
    """
    D = bits.shape[-1]
    pad = (-D) % WORD
    x = bits.astype(jnp.uint32)
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    x = x.reshape(x.shape[:-1] + (-1, WORD))
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return jnp.sum(x << shifts, axis=-1, dtype=jnp.uint32)


def unpack_lastdim(words: jax.Array, D: int) -> jax.Array:
    """Inverse of :func:`pack_lastdim`; returns {0,1} int8 (..., D)."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (-1,))
    return flat[..., :D].astype(jnp.int8)


def payload_bits(words: jax.Array) -> int:
    """Wire size of a packed payload in bits."""
    return int(words.size) * WORD


def tree_num_params(tree: Pytree) -> int:
    return sum(tree_bit_sizes(tree))
