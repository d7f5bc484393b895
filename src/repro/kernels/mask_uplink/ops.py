"""Public ops: fused mask uplink on arbitrary (K, P) stacks (+ STE).

``use_pallas=False`` runs the jnp oracle — same uniforms, same math, so
the two routes agree bitwise on words/counts (and to reduction-order
rounding on the f32 weighted sums).  The oracle is itself ONE fused XLA
program, which is what the ``ref`` backend benchmarks against the staged
three-kernel pipeline.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...core.backend import pallas_interpret
from ..psm_mask.ops import ste_clip_bwd
from ..tiling import LANE, pad_to_multiple
from . import ref
from .mask_uplink import (BLOCK_C, BLOCK_R, WORD, unpack_counts_apply_pallas,
                          unpack_counts_pallas, uplink_fused)


def _packed_len(n_bits: int) -> int:
    return (n_bits + WORD - 1) // WORD


class UplinkOut(NamedTuple):
    """One round's fused uplink for a (K clients, P params) stack."""

    words: jax.Array            # (K, ceil(P/32)) uint32 wire rows
    counts: jax.Array           # (P,) int32 Σ_k m_k (signed: Σ ±1)
    wsum: jax.Array             # (P,) f32 Σ_k w_k · v_k
    uhat: Optional[jax.Array] = None   # (K, P) STE forward value


def _pad_all(arrs):
    """Pad (K, P) operands to the kernel's block multiples (zeros sample
    to mask bit 0, so padding never leaks into words/counts/wsum)."""
    out = []
    for a in arrs:
        if a is None:
            out.append(None)
            continue
        a = pad_to_multiple(a, BLOCK_R, axis=0)
        out.append(pad_to_multiple(a, BLOCK_C, axis=1))
    return out


def mask_uplink_fused(u: jax.Array, n: Optional[jax.Array], r_sm: jax.Array,
                      r_pm=None, progress=None, weights=None, *,
                      mode: str = "binary", wsum_values: bool = True,
                      want_uhat: bool = False,
                      use_pallas: bool = True) -> UplinkOut:
    """Sample → pack → count → weighted-sum, one pass over a (K, P) stack.

    ``mode="prob"`` reads P[m=1] directly from ``u`` (``n`` ignored);
    ``r_pm=None`` is the progress≡1 final-uplink draw.  Signed counts are
    the true Σ_k (±1) — the kernel's binary popcount with the 2c − K fix
    applied here, where K is the UNPADDED client count (padded rows would
    otherwise each contribute −1).
    """
    K, P = u.shape
    if n is None:
        n = u                                    # prob mode: unused operand
    if weights is None:
        weights = jnp.ones((K,), jnp.float32)
    if not use_pallas:
        words, c, wsum, uhat = ref.uplink_ref(
            u, n, r_sm, r_pm, progress, weights, mode=mode,
            wsum_values=wsum_values, want_uhat=want_uhat)
    else:
        up, np_, rs, rp = _pad_all([u, n, r_sm, r_pm])
        wp = pad_to_multiple(weights.astype(jnp.float32), BLOCK_R, axis=0)
        outs = uplink_fused(up, np_, rs, rp, progress, wp, mode=mode,
                            wsum_values=wsum_values, want_uhat=want_uhat,
                            interpret=pallas_interpret())
        words = outs[0][:K, :_packed_len(P)]
        c = outs[1][0, :P]
        wsum = outs[2][0, :P]
        uhat = outs[3][:K, :P] if want_uhat else None
    if mode == "signed":
        c = 2 * c - K
    return UplinkOut(words, c, wsum, uhat)


# ---------------------------------------------------------------------------
# STE-differentiable variant — gradient flows to ``u`` exactly as the
# staged tree_psm/psm_ste path (shared ste_clip_bwd), everything else 0.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _uplink_ste(u, n, r_sm, r_pm, progress, weights, mode, wsum_values,
                use_pallas):
    out = mask_uplink_fused(u, n, r_sm, r_pm, progress, weights, mode=mode,
                            wsum_values=wsum_values, want_uhat=True,
                            use_pallas=use_pallas)
    return out.words, out.counts, out.wsum, out.uhat


def _uplink_ste_fwd(u, n, r_sm, r_pm, progress, weights, mode, wsum_values,
                    use_pallas):
    out = _uplink_ste(u, n, r_sm, r_pm, progress, weights, mode,
                      wsum_values, use_pallas)
    gate = (None if r_pm is None
            else r_pm < jnp.asarray(progress, jnp.float32))
    return out, (u, n, gate)


def _uplink_ste_bwd(mode, wsum_values, use_pallas, res, cts):
    u, n, gate = res
    ct_u = ste_clip_bwd(mode, u, n, gate, cts[3])   # û cotangent only
    return (ct_u, jnp.zeros_like(n), jnp.zeros_like(u),
            None if gate is None else jnp.zeros_like(u),
            None if gate is None else jnp.zeros((), jnp.float32),
            jnp.zeros((u.shape[0],), jnp.float32))


_uplink_ste.defvjp(_uplink_ste_fwd, _uplink_ste_bwd)


def mask_uplink_ste(u, n, r_sm, r_pm=None, progress=None, weights=None, *,
                    mode: str = "binary", wsum_values: bool = True,
                    use_pallas: bool = True) -> UplinkOut:
    """:func:`mask_uplink_fused` with û emitted and STE gradients to ``u``
    (binary/signed only — FedPM's prob mode never differentiates the
    uplink draw)."""
    if mode == "prob":
        raise ValueError("mask_uplink_ste: prob mode has no STE gradient")
    if weights is None:
        weights = jnp.ones((u.shape[0],), jnp.float32)
    progress = (None if r_pm is None
                else jnp.asarray(progress, jnp.float32))
    return UplinkOut(*_uplink_ste(u, n, r_sm, r_pm, progress, weights, mode,
                                  wsum_values, use_pallas))


# ---------------------------------------------------------------------------
# server side
# ---------------------------------------------------------------------------

def _pad_words(words: jax.Array) -> jax.Array:
    return pad_to_multiple(pad_to_multiple(words, LANE, axis=1),
                           BLOCK_R, axis=0)


def unpack_counts(words: jax.Array, *, use_pallas: bool = True) -> jax.Array:
    """(K, W) packed rows → (W·32,) int32 binary popcounts, no bit tensor
    in HBM on the pallas route (each word block reduced in VMEM)."""
    W = words.shape[1]
    if not use_pallas:
        return ref.unpack_counts_ref(words)
    counts = unpack_counts_pallas(_pad_words(words),
                                  interpret=pallas_interpret())
    return counts[0, : W * WORD]


def unpack_counts_apply(words: jax.Array, noise: jax.Array, base: jax.Array,
                        mul, a, b, *, use_pallas: bool = True) -> jax.Array:
    """``base + noise ⊙ (mul·(a·c + b))`` with c the per-element popcount
    of ``words`` — the Eq. (5) shared-noise server update straight from
    the aggregated wire rows.  ``noise``/``base`` are flat (P,); binary
    counts use (a, b) = (1, 0), signed Σ(±1) uses (2, −K).
    """
    P = noise.shape[0]
    noise = noise.astype(jnp.float32)
    base = base.astype(jnp.float32)
    if not use_pallas:
        c = ref.unpack_counts_ref(words)[:P].astype(jnp.float32)
        return base + noise * (mul * (a * c + b))
    wp = _pad_words(words)
    Wp = wp.shape[1]
    noise_p = pad_to_multiple(noise, Wp * WORD).reshape(1, -1)
    base_p = pad_to_multiple(base, Wp * WORD).reshape(1, -1)
    scalars = jnp.stack([jnp.asarray(mul, jnp.float32),
                         jnp.asarray(a, jnp.float32),
                         jnp.asarray(b, jnp.float32)])
    out = unpack_counts_apply_pallas(wp, noise_p, base_p, scalars,
                                     interpret=pallas_interpret())
    return out[0, :P]
