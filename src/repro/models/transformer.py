"""Decoder-only transformer LM assembly (dense / MoE / VLM / SSM / RWKV).

Layers are *stacked* (every leaf has a leading L axis) and iterated with
``lax.scan`` + ``jax.checkpoint`` — the HLO contains each block body once,
which keeps 94-layer × 512-device compiles tractable and matches the
production remat policy.

The same assembly serves four arch types:
  dense   — GQA attention + SwiGLU
  moe     — GQA attention + top-k expert layer
  ssm     — Mamba2 or RWKV6 mixer (attention-free)
  vlm     — dense + M-RoPE positions + stub patch-embedding prefix
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import optimization_barrier

from ..configs.base import ModelConfig
from ..sharding import hints
from . import attention as attn_mod
from . import mamba2 as m2
from . import rwkv6 as rk
from .layers import (chunked_xent, embed, embedding_init, normal_init,
                     rmsnorm, rmsnorm_init, split_keys, swiglu, swiglu_init)
from .moe import moe_fwd, moe_init

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# per-layer init / forward
# ---------------------------------------------------------------------------

def _mixer_kind(cfg: ModelConfig) -> str:
    if cfg.arch_type == "ssm":
        return "mamba2" if cfg.ssm_state else "rwkv6"
    return "attn"


def layer_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = split_keys(key, 2)
    kind = _mixer_kind(cfg)
    p: Params = {"ln1": rmsnorm_init(cfg.d_model, cfg.dtype),
                 "ln2": rmsnorm_init(cfg.d_model, cfg.dtype)}
    if kind == "attn":
        p["attn"] = attn_mod.attn_init(
            k1, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            bias=cfg.qkv_bias, dtype=cfg.dtype)
    elif kind == "mamba2":
        dm = m2.dims(cfg.d_model, state=cfg.ssm_state,
                     head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                     d_conv=cfg.conv_kernel)
        p["mixer"] = m2.mamba2_init(k1, dm, cfg.dtype)
    else:  # rwkv6
        p["mixer"] = rk.time_mix_init(k1, cfg.d_model, cfg.dtype)
    if cfg.num_experts:
        p["moe"] = moe_init(k2, cfg.d_model, cfg.d_ff, cfg.num_experts,
                            cfg.dtype)
    elif kind == "rwkv6":
        p["mlp"] = rk.channel_mix_init(k2, cfg.d_model, cfg.d_ff, cfg.dtype)
    elif kind == "attn":
        p["mlp"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, cfg.dtype)
    # mamba2 blocks are mixer-only (norm + mixer), matching Mamba2 LMs —
    # unless the config gives d_ff, in which case add a SwiGLU (zamba2 style)
    if kind == "mamba2" and cfg.d_ff:
        p["mlp"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def layer_fwd(p: Params, h: jax.Array, cfg: ModelConfig, *,
              positions=None, inference: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence layer forward → (h, aux_loss)."""
    kind = _mixer_kind(cfg)
    aux = jnp.float32(0)
    x = rmsnorm(p["ln1"], h, cfg.norm_eps)
    if kind == "attn":
        mix = attn_mod.attention_fwd(
            p["attn"], x, n_heads=cfg.num_heads, n_kv=cfg.num_kv_heads,
            head_dim=cfg.head_dim, positions=positions,
            rope_theta=cfg.rope_theta, use_mrope=cfg.mrope,
            causal=True, window=cfg.sliding_window)
    elif kind == "mamba2":
        dm = m2.dims(cfg.d_model, state=cfg.ssm_state,
                     head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                     d_conv=cfg.conv_kernel)
        mix = m2.mamba2_fwd(p["mixer"], x, dm, cfg.norm_eps)
    else:
        mix, _, _ = rk.time_mix_fwd(p["mixer"], x, eps=cfg.norm_eps)
    h = h + mix
    x2 = rmsnorm(p["ln2"], h, cfg.norm_eps)
    if "moe" in p:
        out, aux = moe_fwd(p["moe"], x2, top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor,
                           aux_weight=cfg.router_aux_weight,
                           inference=inference)
    elif "mlp" in p and kind == "rwkv6":
        out, _ = rk.channel_mix_fwd(p["mlp"], x2)
    elif "mlp" in p:
        out = swiglu(p["mlp"], x2)
    else:
        out = jnp.zeros_like(h)
    return h + out, aux


# ---------------------------------------------------------------------------
# model init / forward / loss
# ---------------------------------------------------------------------------

def model_init(key, cfg: ModelConfig) -> Params:
    ke, kl, kh = split_keys(key, 3)
    layer_keys = jnp.stack(split_keys(kl, cfg.num_layers))
    layers = jax.vmap(lambda k: layer_init(k, cfg))(layer_keys)
    p = {"embed": embedding_init(ke, cfg.vocab_size, cfg.d_model, cfg.dtype),
         "final_ln": rmsnorm_init(cfg.d_model, cfg.dtype),
         "layers": layers}
    if not cfg.tie_embeddings:
        p["head"] = normal_init(kh, (cfg.d_model, cfg.vocab_size),
                                cfg.d_model ** -0.5, cfg.dtype)
    return p


def _head_matrix(p: Params, cfg: ModelConfig) -> jax.Array:
    return (p["embed"]["tok"].T if cfg.tie_embeddings else p["head"])


def _embed_inputs(p, cfg: ModelConfig, batch) -> Tuple[jax.Array, Any]:
    """Token (+ stub modality prefix) embedding → (h, positions)."""
    h = embed(p["embed"], batch["tokens"])
    if cfg.modality in ("vision", "audio") and "frontend_embeds" in batch:
        # STUB frontends (per spec): precomputed patch/frame embeddings are
        # prepended to the token sequence.
        h = jnp.concatenate(
            [batch["frontend_embeds"].astype(h.dtype), h], axis=1)
    B, S = h.shape[:2]
    if cfg.mrope:
        positions = batch.get("positions3")
        if positions is None:
            base = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            positions = jnp.broadcast_to(base[None], (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    return h, positions


def forward_hidden(p: Params, cfg: ModelConfig, batch, *,
                   inference: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """(B,S,d) final hidden states + accumulated aux loss."""
    h, positions = _embed_inputs(p, cfg, batch)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body_fn(h, layer_p):
        layer_p = optimization_barrier(layer_p)  # see decode_step
        h2, aux = layer_fwd(layer_p, h, cfg, positions=positions,
                            inference=inference)
        return h2, aux

    def scan_body(carry, layer_p):
        h, aux_sum = carry
        # checkpoint saves one h per layer — shard them over batch AND
        # d_model ('model' axis), else 94-layer stacks are O(100GB)/device.
        # The optimization_barrier pins the save to bf16: without it XLA
        # hoists the rmsnorm f32 upcast out of the loop and keeps a 2×-size
        # f32 copy of the whole stack.
        h = optimization_barrier(
            hints.hint_spec(h, {0: "batch", 2: "model"}))
        h2, aux = body_fn(h, layer_p)
        return (h2, aux_sum + aux), None

    (h, aux), _ = jax.lax.scan(scan_body, (h, jnp.float32(0)), p["layers"])
    return rmsnorm(p["final_ln"], h, cfg.norm_eps), aux


def loss_fn(p: Params, cfg: ModelConfig, batch) -> jax.Array:
    """Mean next-token cross-entropy (+ MoE aux)."""
    h, aux = forward_hidden(p, cfg, batch)
    labels = batch["labels"]
    if h.shape[1] != labels.shape[1]:
        # modality prefix (stub frontend) carries no labels
        h = h[:, h.shape[1] - labels.shape[1]:]
    return chunked_xent(h, _head_matrix(p, cfg), labels,
                        softcap=cfg.logit_softcap) + aux


# ---------------------------------------------------------------------------
# decode path
# ---------------------------------------------------------------------------

class DecodeCache(NamedTuple):
    layers: Any          # stacked per-layer cache pytree (leading L axis)
    step: jax.Array      # scalar int32


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> DecodeCache:
    kind = _mixer_kind(cfg)
    L = cfg.num_layers

    def one():
        if kind == "attn":
            return attn_mod.init_kv_cache(
                batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                window=cfg.sliding_window, dtype=dtype)
        if kind == "mamba2":
            dm = m2.dims(cfg.d_model, state=cfg.ssm_state,
                         head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                         d_conv=cfg.conv_kernel)
            return m2.init_mamba2_cache(batch, dm, dtype)
        return rk.init_rwkv_cache(batch, cfg.d_model, dtype)

    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (L,) + x.shape), one())
    return DecodeCache(stacked, jnp.zeros((), jnp.int32))


def decode_step(p: Params, cfg: ModelConfig, cache: DecodeCache,
                tokens: jax.Array) -> Tuple[jax.Array, DecodeCache]:
    """One-token step. tokens: (B, 1) → logits (B, 1, V)."""
    kind = _mixer_kind(cfg)
    h = embed(p["embed"], tokens)

    def body(h, inp):
        layer_p, layer_c = inp
        # barrier: XLA-CPU promotes bf16 dots to f32 and would otherwise
        # hoist the convert of the WHOLE stacked weight tensor out of the
        # layer loop (an f32 copy of all params — ~19 GB at 235b)
        layer_p, layer_c = optimization_barrier((layer_p, layer_c))
        x = rmsnorm(layer_p["ln1"], h, cfg.norm_eps)
        if kind == "attn":
            lc = attn_mod.KVCache(layer_c.k, layer_c.v, cache.step)
            mix, nc = attn_mod.decode_attention(
                layer_p["attn"], x, lc, n_heads=cfg.num_heads,
                n_kv=cfg.num_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, use_mrope=cfg.mrope,
                window=cfg.sliding_window)
        elif kind == "mamba2":
            dm = m2.dims(cfg.d_model, state=cfg.ssm_state,
                         head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand,
                         d_conv=cfg.conv_kernel)
            mix, nc = m2.mamba2_decode(layer_p["mixer"], x, layer_c, dm,
                                       cfg.norm_eps)
        else:
            mix, new_state, tm_x = rk.time_mix_fwd(
                layer_p["mixer"], x, state=layer_c.state,
                last_x=layer_c.tm_x, eps=cfg.norm_eps)
        if kind == "rwkv6":
            h = h + mix
            x2 = rmsnorm(layer_p["ln2"], h, cfg.norm_eps)
            out, cm_x = rk.channel_mix_fwd(layer_p["mlp"], x2,
                                           last_x=layer_c.cm_x)
            h = h + out
            return h, rk.RWKVLayerCache(new_state, tm_x, cm_x)
        h = h + mix
        x2 = rmsnorm(layer_p["ln2"], h, cfg.norm_eps)
        if "moe" in layer_p:
            out, _ = moe_fwd(layer_p["moe"], x2, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             aux_weight=cfg.router_aux_weight,
                             inference=True)
        elif "mlp" in layer_p:
            out = swiglu(layer_p["mlp"], x2)
        else:
            out = jnp.zeros_like(h)
        return h + out, nc

    h, new_layers = jax.lax.scan(body, h, (p["layers"], cache.layers))
    h = rmsnorm(p["final_ln"], h, cfg.norm_eps)
    logits = h.astype(jnp.float32) @ _head_matrix(p, cfg).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits, DecodeCache(new_layers, cache.step + 1)
