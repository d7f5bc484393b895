"""RWKV6 wkv recurrence Pallas kernel.

Per (batch, head): state S ∈ R^{hd×hd} lives in VMEM for the whole
sequence; time steps stream through in registers:

    out_t = r_t · (S + u ⊙ (k_tᵀ v_t))
    S    ← w_t ⊙ S + k_tᵀ v_t

The HBM-resident time dimension is processed in one grid step per (b, h)
pair — each r/k/v/w element is read exactly once and S never leaves VMEM
(hd=64 ⇒ 16 KB fp32 state, far under the ~16 MB VMEM budget; block shapes
keep the (T, hd) panels lane-aligned at 64 ≤ 128 which Mosaic pads).

This is the TPU-native adaptation of RWKV's CUDA kernel: instead of one
thread per channel with warp-local state, one grid cell per (b, h) with
the state as a VMEM-resident matrix and the t-loop as a fori_loop of
rank-1 updates (outer products hit the MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, out_ref,
                s_out_ref):
    """Blocks: r/k/v/w/out (1,T,1,hd); u (1,hd); s0/s_out (1,1,hd,hd)."""
    T = r_ref.shape[1]
    u = u_ref[0, :].astype(jnp.float32)          # (hd,)
    s0 = s0_ref[0, 0].astype(jnp.float32)        # (hd, hd)

    def step(t, s):
        r = r_ref[0, t, 0, :].astype(jnp.float32)  # (hd,)
        k = k_ref[0, t, 0, :].astype(jnp.float32)
        v = v_ref[0, t, 0, :].astype(jnp.float32)
        w = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = k[:, None] * v[None, :]               # (hd, hd) rank-1
        out = r @ (s + u[:, None] * kv)            # (hd,)
        out_ref[0, t, 0, :] = out.astype(out_ref.dtype)
        return w[:, None] * s + kv

    s = jax.lax.fori_loop(0, T, step, s0)
    s_out_ref[0, 0] = s.astype(s_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_pallas(r, k, v, w, u, s0, *, interpret: bool):
    """r,k,v,w: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd).

    Returns (out (B, T, H, hd) f32, s_final (B, H, hd, hd) f32).
    Grid = (B, H); each cell owns its head's full sequence.
    """
    B, T, H, hd = r.shape
    seq_spec = pl.BlockSpec((1, T, 1, hd), lambda b, h: (b, 0, h, 0))
    u_spec = pl.BlockSpec((1, hd), lambda b, h: (h, 0))
    s_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h: (b, h, 0, 0))

    out, s_fin = pl.pallas_call(
        _wkv_kernel,
        grid=(B, H),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec, s_spec],
        out_specs=[seq_spec, s_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, H, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return out, s_fin
