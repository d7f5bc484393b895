"""FedMRN client/server core — Algorithm 1 of the paper.

The client keeps the received global params ``w`` frozen, trains only the
update copy ``u`` (init 0), runs PSM in every forward pass, and finally ships
``(packed mask, seed)``.  The server regenerates each client's noise from its
seed and applies Eq.(5).

Everything is functional and jit-safe; the local loop is a ``lax.scan`` over
the (fixed-shape) stack of local batches, so a whole client update is one
XLA program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import masking, packing
from .noise import NoiseConfig, client_round_key, gen_noise

Pytree = Any
LossFn = Callable[[Pytree, Any], jax.Array]  # (params, batch) -> scalar


@dataclasses.dataclass(frozen=True)
class FedMRNConfig:
    """Static hyper-parameters of the FedMRN mechanism."""

    mask_mode: str = "binary"        # "binary" (FedMRN) | "signed" (FedMRNS)
    noise: NoiseConfig = NoiseConfig()
    use_sm: bool = True              # ablation: False → deterministic masking
    use_pm: bool = True              # ablation: False → progress ≡ 1
    error_feedback: bool = False     # beyond-paper: carry u − û residual
    lr: float = 0.1
    backend: str | None = None       # masking/packing kernels: ref | pallas

    def __post_init__(self):
        if self.mask_mode not in masking.MASK_MODES:
            raise ValueError(f"bad mask_mode {self.mask_mode!r}")


class ClientResult(NamedTuple):
    """What a FedMRN client sends (plus local diagnostics)."""

    packed_mask: jax.Array   # uint32 payload, 1 bit / param
    seed_key: jax.Array      # the PRNG key standing in for the scalar seed
    losses: jax.Array        # (S,) per-step local losses
    residual: Pytree         # u − û (zeros unless error_feedback)


def _tree_zeros_like(t: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.zeros_like, t)


def mix_add(p, u_hat):
    """w + û leaf-mix with the model's param dtype preserved (bf16-safe).

    The ONE definition of how updates meet params — engine aggregation and
    the pod program reuse it, so precision rules change in one place.
    """
    return (p.astype(jnp.float32) + u_hat).astype(p.dtype)


_mix_add = mix_add  # internal alias


def _masked_update(u, noise, key, *, progress, cfg: FedMRNConfig) -> Pytree:
    """The û actually used in the forward pass (Alg. 1 lines 15-18)."""
    if cfg.use_sm and cfg.use_pm:
        return masking.tree_psm(
            u, noise, key, progress=progress, mode=cfg.mask_mode,
            backend=cfg.backend,
        )
    if cfg.use_sm:  # SM only: every element masked every step
        return masking.tree_sm(u, noise, key, mode=cfg.mask_mode)
    # DM in place of SM (w.o. SM ablation); PM still gates if enabled
    def dm_leaf(ul, nl, k):
        m = masking.deterministic_mask(ul, nl, mode=cfg.mask_mode)
        hat = ul + jax.lax.stop_gradient(
            masking.masked_noise_from_mask(nl, m) - ul
        )
        if not cfg.use_pm:
            return hat
        P = jax.random.bernoulli(k, progress, jnp.shape(ul))
        bar = masking.clip_to_noise(ul, nl, mode=cfg.mask_mode)
        return jnp.where(P, hat, bar)

    return masking._tree_keyed_map(dm_leaf, key, u, noise)


def psm_local_train(
    loss_fn: LossFn,
    w_global: Pytree,
    batches: Pytree,           # leaves stacked along leading axis S
    noise: Pytree,
    train_key: jax.Array,
    *,
    cfg: FedMRNConfig,
    u0: Pytree | None = None,
) -> Tuple[Pytree, jax.Array]:
    """S local SGD steps on ``u`` with PSM forward (Alg. 1 lines 12-18).

    The shared local-training body of every FedMRN round program: the
    simulation engine vmaps it over a stacked client axis, the pod program
    runs it per mesh-client slice.  Returns (u_final, per-step losses).

    Its device instructions carry the ``fed.local_train`` name scope, and
    the masking and the mix with ``w_global`` carry ``fed.psm``, in the
    forward pass and (as ``transpose(jvp(fed.psm))``) in the backward.
    """
    num_steps = jax.tree_util.tree_leaves(batches)[0].shape[0]
    if u0 is None:
        u0 = _tree_zeros_like(w_global)

    def step(u, inp):
        tau, batch = inp
        progress = (tau + 1.0) / num_steps
        k = jax.random.fold_in(train_key, tau)

        def fwd(u_):
            with jax.named_scope("fed.psm"):
                u_hat = _masked_update(u_, noise, k, progress=progress,
                                       cfg=cfg)
                w_hat = jax.tree_util.tree_map(_mix_add, w_global, u_hat)
            return loss_fn(w_hat, batch)

        loss, grads = jax.value_and_grad(fwd)(u)
        u = jax.tree_util.tree_map(lambda a, g: a - cfg.lr * g, u, grads)
        return u, loss

    with jax.named_scope("fed.local_train"):
        taus = jnp.arange(num_steps, dtype=jnp.float32)
        return jax.lax.scan(step, u0, (taus, batches))


def sample_final_mask(
    u_final: Pytree,
    noise: Pytree,
    mask_key: jax.Array,
    *,
    cfg: FedMRNConfig,
) -> Pytree:
    """Final uplink masks M(u^{S+1}, G(s)) (Alg. 1 line 19)."""
    if cfg.use_sm:
        return masking.tree_sample_mask(u_final, noise, mask_key,
                                        mode=cfg.mask_mode)
    return jax.tree_util.tree_map(
        lambda ul, nl: masking.deterministic_mask(ul, nl,
                                                  mode=cfg.mask_mode),
        u_final, noise)


def final_mask_key(train_key: jax.Array, num_steps: int) -> jax.Array:
    """Key convention for the post-training mask draw."""
    return jax.random.fold_in(train_key, num_steps + 1)


def client_local_update(
    loss_fn: LossFn,
    w_global: Pytree,
    batches: Pytree,           # leaves stacked along leading axis S
    *,
    cfg: FedMRNConfig,
    base_seed: int,
    round_idx,
    client_id,
    train_key: jax.Array,
    init_residual: Pytree | None = None,
) -> ClientResult:
    """One ClientLocalUpdate (Alg. 1 lines 10-19)."""
    seed_key = client_round_key(base_seed, round_idx, client_id)
    noise = gen_noise(seed_key, w_global, cfg.noise)
    num_steps = jax.tree_util.tree_leaves(batches)[0].shape[0]

    u0 = None
    if cfg.error_feedback and init_residual is not None:
        # beyond-paper: warm-start u at last round's compression residual
        u0 = init_residual

    u_final, losses = psm_local_train(loss_fn, w_global, batches, noise,
                                      train_key, cfg=cfg, u0=u0)
    m = sample_final_mask(u_final, noise,
                          final_mask_key(train_key, num_steps), cfg=cfg)
    packed = packing.tree_pack(m, mode=cfg.mask_mode, backend=cfg.backend)

    u_hat = masking.tree_masked_noise(noise, m)
    residual = (jax.tree_util.tree_map(jnp.subtract, u_final, u_hat)
                if cfg.error_feedback else _tree_zeros_like(w_global))
    return ClientResult(packed, seed_key, losses, residual)


# ---------------------------------------------------------------------------
# plain FedAvg-style local training (for every post-training baseline)
# ---------------------------------------------------------------------------

def sgd_local_update(
    loss_fn: LossFn,
    w_global: Pytree,
    batches: Pytree,
    *,
    lr: float,
) -> Tuple[Pytree, jax.Array]:
    """Vanilla local SGD; returns (u = w_local − w_global, per-step losses).

    Its device instructions carry the ``fed.local_train`` name scope.
    """

    def step(w, batch):
        loss, grads = jax.value_and_grad(loss_fn)(w, batch)
        w = jax.tree_util.tree_map(lambda a, g: a - lr * g, w, grads)
        return w, loss

    with jax.named_scope("fed.local_train"):
        w_final, losses = jax.lax.scan(step, w_global, batches)
        u = jax.tree_util.tree_map(jnp.subtract, w_final, w_global)
    return u, losses


# ---------------------------------------------------------------------------
# server side
# ---------------------------------------------------------------------------

def server_decode_update(
    packed_mask: jax.Array,
    seed_key: jax.Array,
    like: Pytree,
    *,
    cfg: FedMRNConfig,
) -> Pytree:
    """Recover û = G(s) ⊙ m from the wire payload."""
    noise = gen_noise(seed_key, like, cfg.noise)
    m = packing.tree_unpack(packed_mask, like, mode=cfg.mask_mode,
                            backend=cfg.backend)
    return masking.tree_masked_noise(noise, m)


def server_aggregate(
    w_global: Pytree,
    results: Sequence[ClientResult],
    weights: Sequence[float] | jax.Array,
    *,
    cfg: FedMRNConfig,
) -> Pytree:
    """Eq.(5): w ← w + Σ p'_k G(s_k) ⊙ m_k (weights pre-normalised)."""
    weights = jnp.asarray(weights)
    weights = weights / jnp.sum(weights)
    agg = _tree_zeros_like(w_global)
    for wk, res in zip(weights, results):
        u_hat = server_decode_update(res.packed_mask, res.seed_key,
                                     w_global, cfg=cfg)
        agg = jax.tree_util.tree_map(lambda a, b: a + wk * b, agg, u_hat)
    # mix_add (not plain add): preserves param dtype, so bf16 models don't
    # drift to f32 round-over-round — same rule as the batched/scan engines
    return jax.tree_util.tree_map(_mix_add, w_global, agg)


def server_aggregate_updates(
    w_global: Pytree,
    updates: Sequence[Pytree],
    weights: Sequence[float] | jax.Array,
) -> Pytree:
    """FedAvg aggregation of float updates (Eq. 3)."""
    weights = jnp.asarray(weights)
    weights = weights / jnp.sum(weights)
    agg = _tree_zeros_like(w_global)
    for wk, u in zip(weights, updates):
        agg = jax.tree_util.tree_map(lambda a, b: a + wk * b, agg, u)
    return jax.tree_util.tree_map(_mix_add, w_global, agg)
