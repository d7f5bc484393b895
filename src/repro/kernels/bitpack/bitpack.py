"""1-bit mask packing Pallas kernel — the FedMRN wire format, on-chip.

Packs a {0,1} mask (R, C) into little-endian 32-bit words (R, C/32): bit
j of word w is element 32·w + j.  Unpack is the mirror.

Mosaic refuses both the lane reshape ``(r, 4096) → (r, 128, 32)`` and
reductions over unsigned integers, so the bit arithmetic runs in int32
on lane-dense (rows, 128) chunks and is bitcast to uint32 at the
boundary (a sum of distinct powers of two is their OR, so bit 31 wrapping
into the int32 sign is exact under the bitcast):

  pack     per 128-lane chunk c: shift each lane's bit to position
           lane % 32, OR each 32-lane segment into its last lane with
           five rolls, rotate chunk c left by c lanes and OR the chunks
           together (lane 32q + 31 − c then holds word 4c + q, so no two
           words collide), and put the words in order with one lane
           gather.
  unpack   chunk c of the bits gathers word 4c + lane // 32 into every
           lane and shifts out bit lane % 32.

``pack_tile`` and ``unpack_chunk`` are the in-kernel helpers the
``mask_uplink`` kernels share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiling import pad_to_multiple

WORD = 32
LANES = 128
BLOCK_R = 8
BLOCK_W = LANES           # words per block → 4096 bits per row-block
BLOCK_BITS = BLOCK_W * WORD


def _lane_iota(rows: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)


def pack_tile(m: jax.Array) -> jax.Array:
    """(rows, 4096) bool/{0,1} → (rows, 128) int32 words, in-kernel."""
    rows, cols = m.shape
    assert cols == BLOCK_BITS, cols
    lane = _lane_iota(rows)
    shift = lane & (WORD - 1)
    seg_end = shift == WORD - 1
    acc = None
    for c in range(cols // LANES):
        v = m[:, c * LANES:(c + 1) * LANES].astype(jnp.int32) << shift
        for k in (1, 2, 4, 8, 16):     # lane 32q+31 ← OR of its segment
            v = v | pltpu.roll(v, k, 1)
        v = jnp.where(seg_end, v, 0)
        if c:
            v = pltpu.roll(v, LANES - c, 1)
        acc = v if acc is None else acc | v
    # lane 32q + 31 − c holds word 4c + q; gather word w from there
    src = WORD * (lane & 3) + (WORD - 1) - (lane >> 2)
    return jnp.take_along_axis(acc, src, axis=1)


def unpack_chunk(words: jax.Array, c) -> jax.Array:
    """Bits 128c … 128c+127 of each row of (rows, 128) int32 ``words`` as
    a (rows, 128) int32 {0,1} chunk, in-kernel (``c`` may be traced)."""
    lane = _lane_iota(words.shape[0])
    g = jnp.take_along_axis(words, (lane >> 5) + 4 * c, axis=1)
    return (g >> (lane & (WORD - 1))) & 1


def _pack_kernel(bits_ref, out_ref):
    out_ref[...] = pack_tile(bits_ref[...] != 0)


def _unpack_kernel(words_ref, out_ref):
    words = words_ref[...]
    for c in range(BLOCK_BITS // LANES):
        out_ref[:, c * LANES:(c + 1) * LANES] = (
            unpack_chunk(words, c).astype(out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pack_bits_pallas(bits: jax.Array, *, interpret: bool):
    """bits: (R, C) int8 {0,1} with C % 32 == 0 → (R, C//32) uint32."""
    R, C = bits.shape
    assert C % WORD == 0
    x = pad_to_multiple(pad_to_multiple(bits, BLOCK_R, 0), BLOCK_BITS, 1)
    Rp, Cp = x.shape
    words = pl.pallas_call(
        _pack_kernel,
        grid=(Rp // BLOCK_R, Cp // BLOCK_BITS),
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_BITS), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_W), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, Cp // WORD), jnp.int32),
        interpret=interpret, name="pack_bits",
    )(x)
    return jax.lax.bitcast_convert_type(words[:R, :C // WORD], jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_bits_pallas(words: jax.Array, *, interpret: bool):
    """words: (R, W) uint32 → (R, W*32) int8 {0,1}."""
    R, W = words.shape
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = pad_to_multiple(pad_to_multiple(w, BLOCK_R, 0), BLOCK_W, 1)
    Rp, Wp = w.shape
    bits = pl.pallas_call(
        _unpack_kernel,
        grid=(Rp // BLOCK_R, Wp // BLOCK_W),
        in_specs=[pl.BlockSpec((BLOCK_R, BLOCK_W), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((BLOCK_R, BLOCK_BITS), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, Wp * WORD), jnp.int8),
        interpret=interpret, name="unpack_bits",
    )(w)
    return bits[:R, :W * WORD]
