"""Fused mask-uplink Pallas TPU kernel — the paper's whole wire hot path.

The FedMRN uplink is: sample the 1-bit mask under PSM (Eq. 6/7/10),
bitpack it into uint32 words, and reduce the per-element mask counts the
server aggregates.  Run as separate kernels that chain makes three full
HBM round trips over model-sized tensors and materializes both the mask
tree and (server side) an unpacked bit tensor 32× the wire size.  Here
the whole chain is ONE ``pallas_call``: each (block_r, block_c) tile of
``u``/``n``/uniforms is read into VMEM once and leaves as

  words    (R, C/32) uint32      the packed wire payload rows
  counts   (1, C) int32          Σ_r m_r, accumulated across the row
                                 blocks of the grid
  wsum     (1, C) f32            Σ_r w_r · v_r ⊙ m_r, accumulated the
                                 same way (v = noise → Eq. 5 masked-noise
                                 sums, or v = ±1 → weighted mask sums)
  û        (R, C), optional      the PSM/STE forward value

— the {0,1} mask itself never touches HBM.  The server-side mirrors,
``unpack_counts`` and ``unpack_counts_apply``, go from aggregated words
straight to counts (and into ``base + noise ⊙ (mul·(a·c + b))``, the
global-model update) without materializing unpacked bits.

Uniforms are drawn OUTSIDE (seeded jax.random streams — the server must
reproduce G(s) exactly), like ``kernels/psm_mask``.  ``mode="prob"``
reads P[m=1] directly from ``u`` (FedPM sigmoid scores); the ``r_pm``
gate input is optional — omitted, the kernel is the progress=1 final
uplink draw.  Scalars (the PM progress, the apply's ``mul, a, b``) live
in SMEM.  Bits are packed and unpacked in int32 by the ``bitpack``
helpers and bitcast to uint32 outside the kernels.  Callers pad shapes
to block multiples (``kernels.tiling``) so the in-kernel reductions
never see out-of-bounds lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..bitpack.bitpack import BLOCK_BITS, LANES, WORD, pack_tile, unpack_chunk

BLOCK_R = 8               # sublane-aligned client rows per tile
BLOCK_C = BLOCK_BITS      # bits per tile = 128 words (lane-aligned)
_EPS = 1e-30
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _uplink_kernel(*refs, mode: str, with_gate: bool, want_uhat: bool,
                   wsum_values: bool):
    it = iter(refs)
    u_ref, n_ref, r_sm_ref = next(it), next(it), next(it)
    r_pm_ref = next(it) if with_gate else None
    w_ref = next(it)
    prog_ref = next(it) if with_gate else None
    words_ref, counts_ref, wsum_ref = next(it), next(it), next(it)
    uhat_ref = next(it) if want_uhat else None

    u = u_ref[...].astype(jnp.float32)
    n = n_ref[...].astype(jnp.float32)
    r_sm = r_sm_ref[...]
    if mode == "prob":
        p = jnp.clip(u, 0.0, 1.0)
        m = r_sm < p
        v = jnp.where(m, 1.0, 0.0)
    else:
        safe_n = jnp.where(jnp.abs(n) < _EPS, _EPS, n)
        if mode == "binary":
            p = jnp.clip(u / safe_n, 0.0, 1.0)
            m = r_sm < p
            hat_sm = jnp.where(m, n, 0.0)
            lo = jnp.minimum(n, 0.0)
            hi = jnp.maximum(n, 0.0)
            v = hat_sm if wsum_values else jnp.where(m, 1.0, 0.0)
        else:  # signed
            p = jnp.clip((u + n) / (2.0 * safe_n), 0.0, 1.0)
            m = r_sm < p
            hat_sm = jnp.where(m, n, -n)
            hi = jnp.abs(n)
            lo = -hi
            v = hat_sm if wsum_values else jnp.where(m, 1.0, -1.0)
    if want_uhat:
        bar = jnp.clip(u, lo, hi)
        if with_gate:
            gate = r_pm_ref[...] < prog_ref[0]
            uhat = jnp.where(gate, hat_sm, bar)
        else:                       # progress ≡ 1: every element is masked
            uhat = hat_sm
        uhat_ref[...] = uhat.astype(uhat_ref.dtype)

    words_ref[...] = pack_tile(m)

    # the client rows are the inner grid axis: the (1, bc) count/wsum
    # blocks stay resident across it and accumulate.  Binary popcounts
    # even in signed mode: Σ(±1) = 2c − K is an affine fix the wrapper
    # applies with the TRUE (unpadded) client count
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        wsum_ref[...] = jnp.zeros_like(wsum_ref)

    counts_ref[...] += jnp.sum(m.astype(jnp.int32), axis=0, keepdims=True)
    w = w_ref[...].astype(jnp.float32)              # (br, 1)
    wsum_ref[...] += jnp.sum(w * v, axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("mode", "wsum_values", "want_uhat",
                                    "interpret"))
def uplink_fused(u: jax.Array, n: jax.Array, r_sm: jax.Array,
                 r_pm, progress, weights: jax.Array, *,
                 mode: str = "binary", wsum_values: bool = True,
                 want_uhat: bool = False, interpret: bool):
    """One fused pass over (R, C) tiles; R, C must be block multiples.

    ``r_pm=None`` (with ``progress=None``) drops the PM gate input — the
    progress=1 uplink draw.  Returns ``(words, counts, wsum[, uhat])``
    with ``counts``/``wsum`` shaped (1, C), summed over all R rows.
    """
    R, C = u.shape
    br, bc = BLOCK_R, BLOCK_C
    assert R % br == 0 and C % bc == 0, (R, C, br, bc)
    with_gate = r_pm is not None
    grid = (C // bc, R // br)
    tile = pl.BlockSpec((br, bc), lambda j, i: (i, j))
    row_spec = pl.BlockSpec((1, bc), lambda j, i: (0, j))

    in_specs = [tile, tile, tile]
    args = [u, n, r_sm]
    if with_gate:
        in_specs.append(tile)
        args.append(r_pm)
    in_specs.append(pl.BlockSpec((br, 1), lambda j, i: (i, 0)))
    args.append(weights.reshape(R, 1))
    if with_gate:
        in_specs.append(_SMEM)
        args.append(jnp.asarray(progress, jnp.float32).reshape(1))

    out_specs = [pl.BlockSpec((br, bc // WORD), lambda j, i: (i, j)),
                 row_spec, row_spec]
    out_shape = [jax.ShapeDtypeStruct((R, C // WORD), jnp.int32),
                 jax.ShapeDtypeStruct((1, C), jnp.int32),
                 jax.ShapeDtypeStruct((1, C), jnp.float32)]
    if want_uhat:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((R, C), u.dtype))

    outs = pl.pallas_call(
        functools.partial(_uplink_kernel, mode=mode, with_gate=with_gate,
                          want_uhat=want_uhat, wsum_values=wsum_values),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name="uplink_fused",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*args)
    words = jax.lax.bitcast_convert_type(outs[0], jnp.uint32)
    return (words, *outs[1:])


# ---------------------------------------------------------------------------
# server side: aggregated words → counts (→ applied update), no bit tensor
# ---------------------------------------------------------------------------

def _chunk_counts(words, c):
    """Popcount over all client rows of bits 128c … 128c+127: (1, 128)."""
    return jnp.sum(unpack_chunk(words, c), axis=0, keepdims=True)


def _chunk_lanes(c):
    return pl.ds(pl.multiple_of(c * LANES, LANES), LANES)


def _counts_kernel(words_ref, counts_ref):
    words = words_ref[...]                           # (K, 128): all clients

    def chunk(c, carry):
        counts_ref[:, _chunk_lanes(c)] = _chunk_counts(words, c)
        return carry

    jax.lax.fori_loop(0, BLOCK_BITS // LANES, chunk, 0)


def _word_blocks(words: jax.Array):
    """(K, W) uint32 → int32 view, its grid and the (K, 128) block spec."""
    K, W = words.shape
    assert W % LANES == 0, (K, W)
    spec = pl.BlockSpec((K, LANES), lambda i: (0, i))
    return (jax.lax.bitcast_convert_type(words, jnp.int32), (W // LANES,),
            spec)


_BITS_SPEC = pl.BlockSpec((1, BLOCK_BITS), lambda i: (0, i))


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_counts_pallas(words: jax.Array, *, interpret: bool):
    """(K, W) packed rows → (1, W·32) int32 popcounts over all K rows."""
    w, grid, spec = _word_blocks(words)
    return pl.pallas_call(
        _counts_kernel, grid=grid, in_specs=[spec], out_specs=_BITS_SPEC,
        out_shape=jax.ShapeDtypeStruct((1, w.shape[1] * WORD), jnp.int32),
        interpret=interpret, name="unpack_counts",
    )(w)


def _counts_apply_kernel(sc_ref, words_ref, noise_ref, base_ref, out_ref):
    words = words_ref[...]                           # (K, 128): all clients
    mul, a, b = sc_ref[0], sc_ref[1], sc_ref[2]

    def chunk(c, carry):
        lanes = _chunk_lanes(c)
        # f32 popcount is exact for K < 2^24; a·c + b is the signed fix
        cnt = _chunk_counts(words, c).astype(jnp.float32)
        out_ref[:, lanes] = (base_ref[:, lanes]
                             + noise_ref[:, lanes] * (mul * (a * cnt + b)))
        return carry

    jax.lax.fori_loop(0, BLOCK_BITS // LANES, chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unpack_counts_apply_pallas(words: jax.Array, noise: jax.Array,
                               base: jax.Array, scalars: jax.Array, *,
                               interpret: bool):
    """words (K, W), noise/base (1, W·32), scalars (mul, a, b) →
    ``base + noise ⊙ (mul·(a·c + b))`` as (1, W·32) f32 — the Eq. (5)
    shared-noise server update straight from the wire words."""
    w, grid, spec = _word_blocks(words)
    return pl.pallas_call(
        _counts_apply_kernel, grid=grid,
        in_specs=[_SMEM, spec, _BITS_SPEC, _BITS_SPEC],
        out_specs=_BITS_SPEC,
        out_shape=jax.ShapeDtypeStruct((1, w.shape[1] * WORD), jnp.float32),
        interpret=interpret, name="unpack_counts_apply",
    )(scalars, w, noise, base)
