"""Algorithm plugin registry — every FL family behind one interface.

An :class:`Algorithm` packages everything the execution engines need to
know about one federated-learning family:

  init_state(cfg, params)       cross-round state pytree ({} if stateless)
  make_round_body(loss_fn, cfg, params)
                                -> seeded_round_body(seed, w, state,
                                       batches, picked, round_idx, weights)
                                   -> (new_w, new_state, losses[,
                                       wire_bits])
  codec(cfg, params)            -> the family's typed uplink wire format
                                   (an :class:`~repro.fed.codecs.
                                   UplinkCodec`): what the round body
                                   routes client outputs through
                                   (encode → stacked WireMsg →
                                   aggregate), and where engines read
                                   the measured comm cost
  validate(cfg)                 raise ValueError on a nonsense config

The round body is PURE and takes the experiment ``seed`` as a *traced*
int32 scalar (not a closure constant): that is what lets a multi-seed
sweep ``vmap`` the whole experiment program over a seed axis with one
compile (``fed.engine.make_sweep_program``).  The drivers in
``fed/engine.py`` bind ``seed = cfg.seed`` for ordinary single-seed runs,
so trajectories are unchanged.  The optional 4th output ``wire_bits``
is the round's K-client MEASURED uplink (summed encoded ``WireMsg``
buffer sizes — ``codec.round_bits(msg)``); engines fall back to the
codec's static report for legacy 3-tuple bodies.

Built-in families (extracted from the seed-era ``if/elif`` branches):

  fedmrn / fedmrns   PSM local training → MaskCodec (packed masks +
                     64-bit seed) → Eq.(5) via codec.aggregate
  fedavg             DenseCodec f32 updates, plus one registered
                     algorithm per post-training compressor (signsgd →
                     SignCodec, topk → SparseCodec, the rest roundtrip
                     in-body over DenseCodec transport)
  fedpm              supermask-as-weights baseline (Isik et al.) —
                     MaskCodec mask-frequency aggregation
  fedsparsify        magnitude-pruned weight upload → SparseCodec

Third-party algorithms register WITHOUT touching engine internals::

    from repro.fed import Algorithm, register_algorithm
    from repro.fed.codecs import DenseCodec, template_of

    register_algorithm(Algorithm(
        name="my_algo",
        make_round_body=my_builder,      # (loss_fn, cfg, params) -> body
        init_state=lambda cfg, p: {},
        codec=lambda cfg, p: DenseCodec(template_of(p), name="my_algo"),
    ))

and every engine (scan / batched / looped drivers), the pod path, the
Experiment API, examples, and benchmarks pick it up by name.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (FedMRNConfig, NoiseConfig, baseline_record,
                    client_round_key, final_mask_key,
                    gen_noise, make_compressor, mix_add, psm_local_train,
                    sample_final_mask, sgd_local_update, tree_masked_noise,
                    tree_num_params)
from ..core.compressors import REGISTRY as COMPRESSOR_REGISTRY
from ..core.masking import tree_bernoulli_stacked
from .codecs import (DenseCodec, MaskCodec, QuantCodec, SignCodec,
                     SparseCodec, UplinkCodec, min_count_dtype,
                     template_of)
from .privacy.dp import PrivacyConfig, check_privacy_support

Pytree = Any
RoundBody = Callable[..., Tuple[Pytree, Pytree, jax.Array]]
# the cohort tier's split round body: (stacked msg, agg weights, losses)
# out of one cohort's clients, and a server apply over the merged
# aggregate — see Algorithm.make_cohort_body
CohortBody = Tuple[UplinkCodec, Callable[..., Tuple[Any, jax.Array,
                                                    jax.Array]],
                   Callable[..., Tuple[Pytree, Pytree]]]


@dataclasses.dataclass(frozen=True)
class FLConfig:
    algorithm: str = "fedmrn"
    num_clients: int = 20
    clients_per_round: int = 5
    rounds: int = 30
    local_steps: int = 20
    batch_size: int = 32
    lr: float = 0.1
    seed: int = 0
    # fedmrn specifics (paper defaults: uniform, 1e-2 / 5e-3)
    noise_dist: str = "uniform"
    noise_alpha: float = 1e-2
    use_sm: bool = True
    use_pm: bool = True
    error_feedback: bool = False
    # beyond-paper: one shared noise G(s_t) per ROUND (instead of per
    # client).  Masks stay per-client, so the uplink is unchanged (1 bpp),
    # but Σ_k G(s_k)⊙m_k = G(s_t) ⊙ Σ_k m_k — the server aggregation
    # becomes an integer mask-count (popcount) scaled by one noise tensor,
    # and at pod scale the mask all-gather can become a ⌈log2(K+1)⌉-bit
    # integer all-reduce (a further ~3× cross-client traffic cut at K=16).
    shared_noise: bool = False
    # aggregate mask COUNTS in the minimal integer dtype holding
    # ⌈log2(K+1)⌉ bits instead of f32 (the pod-path wire format for mask
    # families — the cross-client all-reduce then moves int8/int16 words).
    # Requires uniform client weights (engines enforce) and a
    # count-aggregatable format (fedpm, or fedmrn with shared_noise).
    int_mask_agg: bool = False
    # baselines
    topk_frac: float = 0.03
    sparsify_frac: float = 0.03    # fedsparsify keeps top 3% of weights
    qsgd_bits: int = 2
    # client availability (ROADMAP 4(b)): a seeded per-round dropout
    # trace derived from the run seed; engines mask dropped clients out
    # of the aggregate (exactly the K−d survivors are averaged).
    availability: str = "always"   # "always" | "bernoulli" | "markov"
    dropout: float = 0.0           # drop prob / Markov stationary rate
    churn: float = 0.5             # markov: state-flip speed in (0, 1]
    # Ji et al. 2020 dynamic sampling: re-draw dropped scheduled clients
    # from the round's still-available spares before masking
    avail_resample: bool = False
    # distributed DP on the mask-count wire (fed/privacy/): clip each
    # client's count contribution, add one discrete noise draw to the
    # merged round count at finalize, account (ε, δ) per round at the
    # recorded participation.  Count-aggregatable mask families only
    # (fedmrn/fedmrns need shared_noise); requires uniform client
    # weights (engines enforce, same rule as int_mask_agg).
    privacy: Optional[PrivacyConfig] = None
    # kernel backend for masking/packing: "ref" | "pallas" | None (auto)
    backend: Optional[str] = None

    def fedmrn_config(self) -> FedMRNConfig:
        mode = "signed" if self.algorithm == "fedmrns" else "binary"
        return FedMRNConfig(
            mask_mode=mode,
            noise=NoiseConfig(dist=self.noise_dist, alpha=self.noise_alpha),
            use_sm=self.use_sm, use_pm=self.use_pm,
            error_feedback=self.error_feedback, lr=self.lr,
            backend=self.backend)

    def validate(self) -> None:
        """Generic sanity checks shared by every algorithm."""
        if self.rounds <= 0:
            raise ValueError(f"rounds must be positive, got {self.rounds}")
        if self.local_steps <= 0:
            raise ValueError(
                f"local_steps must be positive, got {self.local_steps}")
        if self.batch_size <= 0:
            raise ValueError(
                f"batch_size must be positive, got {self.batch_size}")
        if not 0 < self.clients_per_round <= self.num_clients:
            raise ValueError(
                f"clients_per_round={self.clients_per_round} must be in "
                f"[1, num_clients={self.num_clients}]")
        if self.availability not in ("always", "bernoulli", "markov"):
            raise ValueError(
                f"availability {self.availability!r} is not 'always', "
                "'bernoulli' or 'markov'")
        if not 0.0 <= self.dropout < 1.0:
            raise ValueError(
                f"dropout must be in [0, 1), got {self.dropout}")
        if not 0.0 < self.churn <= 1.0:
            raise ValueError(f"churn must be in (0, 1], got {self.churn}")
        check_privacy_support(self)


# ---------------------------------------------------------------------------
# the plugin interface + registry
# ---------------------------------------------------------------------------

def _no_state(cfg: FLConfig, params: Pytree) -> Dict[str, Pytree]:
    return {}


def _no_validate(cfg: FLConfig) -> None:
    return None


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """One pluggable FL family: round body + state + wire format.

    ``make_round_body(loss_fn, cfg, params)`` must return a PURE function

        body(seed, w, state, batches, picked, round_idx, weights)
            -> (new_w, new_state, losses[, wire_bits])
                                              # losses: (K, S) device array

    where ``seed`` is a (possibly traced) int32 scalar — derive every PRNG
    key from it (``jax.random.key(seed + c)`` / ``client_round_key``), not
    from ``cfg.seed``, or multi-seed sweeps silently reuse one stream.
    The optional ``wire_bits`` output is the round's measured K-client
    uplink (``codec.round_bits(stacked_msg)``) — engines substitute the
    codec's static report when a legacy body returns a 3-tuple.

    ``codec(cfg, params)`` returns the family's
    :class:`~repro.fed.codecs.UplinkCodec` — the typed wire format the
    round body routes client outputs through and the single source of
    comm accounting (``codec.wire_bits(params) -> CommRecord``); every
    algorithm MUST declare one (a plugin that only wants a cost report
    wraps it in a :class:`DenseCodec` ``record=`` override).
    """

    name: str
    make_round_body: Callable[[Callable, FLConfig, Pytree], RoundBody]
    codec: Optional[Callable[[FLConfig, Pytree], UplinkCodec]] = None
    init_state: Callable[[FLConfig, Pytree], Pytree] = _no_state
    validate: Callable[[FLConfig], None] = _no_validate
    # the streaming cohort tier's SPLIT round body (optional):
    #
    #   make_cohort_body(loss_fn, cfg, params)
    #       -> (codec,
    #           uplink(seed, w, state, batches, cids, weights, round_idx)
    #               -> (stacked WireMsg, agg_weights (Kc,), losses (Kc,S)),
    #           apply(seed, w, state, aggregate, round_idx,
    #                 n_valid=None)          # merged partial weight mass
    #               -> (new_w, new_state))   # (degraded-round engines
    #                                        #  pass it; fedpm's smoothing
    #                                        #  denominator needs it)
    #
    # The engine runs `uplink` once per cohort, folds the messages into
    # codec partials (codec.partial_aggregate / merge_partials), and
    # calls `apply` once per round on the finalized aggregate — the
    # trajectory must match make_round_body over the concatenated
    # client stack.  None → the family cannot stream (engines raise).
    make_cohort_body: Optional[
        Callable[[Callable, FLConfig, Pytree], CohortBody]] = None


ALGORITHMS: Dict[str, Algorithm] = {}


def register_algorithm(algo: Algorithm, *, overwrite: bool = False) -> Algorithm:
    """Add ``algo`` to the registry (raises on duplicate names)."""
    if not algo.name:
        raise ValueError("algorithm needs a non-empty name")
    if algo.codec is None:
        raise ValueError(
            f"algorithm {algo.name!r} must declare codec= (an UplinkCodec "
            "factory (cfg, params) -> UplinkCodec; see repro.fed.codecs)")
    if algo.name in ALGORITHMS and not overwrite:
        raise ValueError(
            f"algorithm {algo.name!r} already registered "
            "(pass overwrite=True to replace)")
    ALGORITHMS[algo.name] = algo
    return algo


def get_algorithm(name: str) -> Algorithm:
    try:
        return ALGORITHMS[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r} "
            f"(registered: {', '.join(sorted(ALGORITHMS))})") from None


def list_algorithms() -> Tuple[str, ...]:
    return tuple(sorted(ALGORITHMS))


def algorithm_codec(cfg: FLConfig, params: Pytree) -> UplinkCodec:
    """The registered algorithm's uplink codec for this config/model."""
    return get_algorithm(cfg.algorithm).codec(cfg, params)


def uplink_bits(cfg: FLConfig, params: Pytree) -> int:
    """Exact per-client uplink cost of one round (for history accounting).

    Measured from the codec's encoded buffer sizes (or its ``record``
    override when the wire buffers stand in for another format).
    """
    return int(algorithm_codec(cfg, params).wire_bits(params).uplink_bits)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _tree_zeros_like(t: Pytree) -> Pytree:
    return jax.tree_util.tree_map(jnp.zeros_like, t)


# ---------------------------------------------------------------------------
# per-client local updates for the baselines (shared with the looped engine)
# ---------------------------------------------------------------------------

def fedpm_local(loss_fn, w_init, scores, batches, *, lr, key, sample=True):
    """Train sigmoid-scores; weights = w_init ⊙ Bern(sigmoid(s)) with STE.

    ``sample=False`` skips the final uplink draw and returns the trained
    scores — the fused round body then hands ``sigmoid(scores)`` to
    ``MaskCodec.uplink_stacked``, which performs the SAME Bernoulli draw
    (identical key/uniform streams) inside the fused mask-uplink kernel.
    Its device instructions carry the ``fed.local_train`` name scope.
    """

    def masked_params(s, k):
        leaves, treedef = jax.tree_util.tree_flatten(s)
        w_leaves = jax.tree_util.tree_leaves(w_init)
        out = []
        for i, (sl, wl) in enumerate(zip(leaves, w_leaves)):
            prob = jax.nn.sigmoid(sl)
            m = jax.random.bernoulli(jax.random.fold_in(k, i), prob)
            m = prob + jax.lax.stop_gradient(m.astype(prob.dtype) - prob)
            out.append(wl * m)
        return jax.tree_util.tree_unflatten(treedef, out)

    def step(s, inp):
        tau, batch = inp
        k = jax.random.fold_in(key, tau)

        def fwd(s_):
            return loss_fn(masked_params(s_, k), batch)

        loss, g = jax.value_and_grad(fwd)(s)
        s = jax.tree_util.tree_map(lambda a, gi: a - lr * gi, s, g)
        return s, loss

    n = jax.tree_util.tree_leaves(batches)[0].shape[0]
    with jax.named_scope("fed.local_train"):
        s_final, losses = jax.lax.scan(step, scores,
                                       (jnp.arange(n), batches))
        if not sample:
            return s_final, losses
        # uplink: Bernoulli-sampled masks, one independent draw per leaf
        # (folding the leaf index keeps same-shaped leaves decorrelated)
        leaves, treedef = jax.tree_util.tree_flatten(s_final)
        mask_key = jax.random.fold_in(key, n + 1)
        masks = jax.tree_util.tree_unflatten(treedef, [
            jax.random.bernoulli(jax.random.fold_in(mask_key, i),
                                 jax.nn.sigmoid(sl)).astype(jnp.float32)
            for i, sl in enumerate(leaves)])
    return masks, losses


def fedsparsify_local(loss_fn, w, batches, *, lr, frac):
    w_new, losses = sgd_local_update(loss_fn, w, batches, lr=lr)
    w_new = jax.tree_util.tree_map(jnp.add, w, w_new)  # u → w_local

    def prune(x):
        flat = jnp.abs(x).reshape(-1)
        k = max(1, int(np.ceil(frac * flat.shape[0])))
        thresh = jax.lax.top_k(flat, k)[0][-1]
        return jnp.where(jnp.abs(x) >= thresh, x, 0.0)

    return jax.tree_util.tree_map(prune, w_new), losses


# ---------------------------------------------------------------------------
# built-in round bodies, one per algorithm family
# ---------------------------------------------------------------------------

def _fedmrn_codec(cfg: FLConfig, params: Pytree) -> MaskCodec:
    """Packed masks + the 64-bit noise seed — the paper's wire format."""
    mrn = cfg.fedmrn_config()
    return MaskCodec(
        template_of(params), name=cfg.algorithm, mode=mrn.mask_mode,
        noise=mrn.noise, shared_noise=cfg.shared_noise,
        count_dtype=(min_count_dtype(cfg.clients_per_round)
                     if cfg.int_mask_agg else None),
        backend=cfg.backend, privacy=cfg.privacy)


def _fedmrn_body(loss_fn, cfg: FLConfig, params: Pytree) -> RoundBody:
    mrn = cfg.fedmrn_config()
    ef = cfg.error_feedback
    codec = _fedmrn_codec(cfg, params)
    # DM masks and error-feedback residuals need the materialized mask
    # tree; everything else ships through the fused uplink, which samples
    # + packs + count-reduces in one kernel pass on the pallas backend
    # (and stays the staged legacy composition, bitwise, on ref)
    fused = mrn.use_sm and not ef

    def round_fn(seed, w, state, batches, picked, round_idx, weights):
        train_base = jax.random.key(seed + 1)

        def per_client(b, cid, r0):
            noise_id = jnp.int32(0) if cfg.shared_noise else cid
            seed_key = client_round_key(seed, round_idx, noise_id)
            noise = gen_noise(seed_key, w, mrn.noise)
            train_key = jax.random.fold_in(train_base,
                                           round_idx * 1000 + cid)
            u, losses = psm_local_train(loss_fn, w, b, noise, train_key,
                                        cfg=mrn, u0=r0 if ef else None)
            # step count from the batches, NOT cfg.local_steps — the mask
            # key must track the real S or parity with the looped
            # reference breaks when a caller varies steps per round
            num_steps = jax.tree_util.tree_leaves(b)[0].shape[0]
            mask_key = final_mask_key(train_key, num_steps)
            if fused:
                # the final draw happens inside codec.uplink_stacked
                return u, seed_key, mask_key, losses
            m = sample_final_mask(u, noise, mask_key, cfg=mrn)
            residual = (jax.tree_util.tree_map(
                jnp.subtract, u, tree_masked_noise(noise, m))
                if ef else None)
            return m, seed_key, losses, residual

        r0 = (jax.tree_util.tree_map(lambda r: r[picked],
                                     state["residuals"])
              if ef else jnp.zeros((picked.shape[0],)))

        if fused:
            # ---- uplink + server sum in ONE fused pass (Eq. 5) ---------
            u_stack, seed_keys, mask_keys, losses = jax.vmap(per_client)(
                batches, picked, r0)
            with jax.named_scope("fed.uplink"):
                msg, agg = codec.uplink_stacked(u_stack, seed_keys,
                                                mask_keys, weights,
                                                round_idx=round_idx)
            with jax.named_scope("fed.server"):
                new_w = jax.tree_util.tree_map(mix_add, w, agg)
            return new_w, state, losses, codec.round_bits(msg)

        masks, seed_keys, losses, residuals = jax.vmap(per_client)(
            batches, picked, r0)
        # ---- uplink: (packed masks, seeds) encoded in one kernel launch
        with jax.named_scope("fed.uplink"):
            msg = codec.encode_stacked({"mask": masks, "seed": seed_keys})
        # ---- server: the codec is the decode boundary — Eq. (5) --------
        with jax.named_scope("fed.server"):
            new_w = codec.aggregate_apply(msg, weights, w,
                                          round_idx=round_idx)

        new_state = state
        if ef:
            new_state = {"residuals": jax.tree_util.tree_map(
                lambda r, nr: r.at[picked].set(nr),
                state["residuals"], residuals)}
        return new_w, new_state, losses, codec.round_bits(msg)

    return round_fn


def _fedmrn_state(cfg: FLConfig, params: Pytree) -> Dict[str, Pytree]:
    if not cfg.error_feedback:
        return {}
    # Device-resident residual stack: num_clients × model size.  Keeps
    # the gather/scatter inside the round program (no host sync), at
    # the cost of a dense buffer — fine for simulation-scale client
    # counts; a cross-silo run with thousands of clients should shard
    # this stack or carry residuals host-side instead.
    return {"residuals": jax.tree_util.tree_map(
        lambda p: jnp.zeros((cfg.num_clients,) + p.shape, p.dtype),
        params)}


def _fedmrn_validate(cfg: FLConfig) -> None:
    if cfg.noise_alpha <= 0:
        raise ValueError(
            f"noise_alpha must be positive, got {cfg.noise_alpha}")
    if cfg.int_mask_agg and not cfg.shared_noise:
        raise ValueError(
            "int_mask_agg needs shared_noise for fedmrn: with per-client "
            "noise the server update Σ w'_k G(s_k)⊙m_k is not a function "
            "of mask counts")
    NoiseConfig(dist=cfg.noise_dist, alpha=cfg.noise_alpha)  # checks dist


def _fedmrn_cohort_body(loss_fn, cfg: FLConfig, params: Pytree) -> CohortBody:
    """Cohort-streaming split of the FedMRN round: PSM train + mask draw
    per cohort, Eq. (5) applied once on the merged codec partials."""
    if cfg.error_feedback:
        raise ValueError(
            "engine='cohort' streams cohorts through device memory; "
            "error_feedback keeps a C × P residual stack resident — run "
            "it on engine='scan'")
    mrn = cfg.fedmrn_config()
    codec = _fedmrn_codec(cfg, params)

    def uplink(seed, w, state, batches, cids, weights, round_idx):
        train_base = jax.random.key(seed + 1)

        def per_client(b, cid):
            noise_id = jnp.int32(0) if cfg.shared_noise else cid
            seed_key = client_round_key(seed, round_idx, noise_id)
            noise = gen_noise(seed_key, w, mrn.noise)
            train_key = jax.random.fold_in(train_base,
                                           round_idx * 1000 + cid)
            u, losses = psm_local_train(loss_fn, w, b, noise, train_key,
                                        cfg=mrn)
            num_steps = jax.tree_util.tree_leaves(b)[0].shape[0]
            mask_key = final_mask_key(train_key, num_steps)
            m = sample_final_mask(u, noise, mask_key, cfg=mrn)
            return m, seed_key, losses

        masks, seed_keys, losses = jax.vmap(per_client)(batches, cids)
        msg = codec.encode_stacked({"mask": masks, "seed": seed_keys})
        return msg, weights, losses

    def apply(seed, w, state, agg, round_idx, n_valid=None):
        return jax.tree_util.tree_map(mix_add, w, agg), state

    return codec, uplink, apply


# compressors whose quantization IS the codec's encode step (no in-body
# roundtrip): deterministic sign → SignCodec, magnitude top-k →
# SparseCodec, stochastic uniform quantizers → QuantCodec
_CODEC_COMPRESSORS = ("signsgd", "topk", "qsgd", "terngrad")


def _fedavg_family_codec(compressor_name: Optional[str]):
    """Codec factory for fedavg + every post-training compressor entry."""

    def factory(cfg: FLConfig, params: Pytree) -> UplinkCodec:
        t = template_of(params)
        if compressor_name is None:
            return DenseCodec(t, name="fedavg")
        if compressor_name == "signsgd":
            return SignCodec(t, name="signsgd", backend=cfg.backend)
        if compressor_name == "topk":
            return SparseCodec(t, name="topk", frac=cfg.topk_frac)
        if compressor_name == "qsgd":
            return QuantCodec(t, name="qsgd",
                              levels=(1 << cfg.qsgd_bits) - 1,
                              paper_bpp=float(cfg.qsgd_bits))
        if compressor_name == "terngrad":
            return QuantCodec(t, name="terngrad", levels=1,
                              paper_bpp=math.log2(3))
        # the remaining stochastic compressors roundtrip inside the body;
        # the f32 transport stands in for the quantized format, whose
        # true cost the record reports (exact + paper, comm.py §5.1.3)
        P = tree_num_params(params)
        L = len(jax.tree_util.tree_leaves(params))
        rec = baseline_record(compressor_name, P, L,
                              topk_frac=cfg.topk_frac,
                              qsgd_bits=cfg.qsgd_bits)
        return DenseCodec(t, name=compressor_name, record=rec)

    return factory


def _fedavg_family_body(compressor_name: Optional[str]):
    """Round-body builder for fedavg and every post-training compressor."""

    def build(loss_fn, cfg: FLConfig, params: Pytree) -> RoundBody:
        mrn = cfg.fedmrn_config()
        codec = _fedavg_family_codec(compressor_name)(cfg, params)
        compressor = (None if compressor_name is None
                      or compressor_name in _CODEC_COMPRESSORS else
                      make_compressor(compressor_name,
                                      topk_frac=cfg.topk_frac,
                                      qsgd_bits=cfg.qsgd_bits,
                                      noise=mrn.noise))

        def round_fn(seed, w, state, batches, picked, round_idx, weights):
            comp_base = jax.random.key(seed + 3)

            def per_client(b, cid):
                u, losses = sgd_local_update(loss_fn, w, b, lr=cfg.lr)
                ckey = jax.random.fold_in(comp_base,
                                          round_idx * 1000 + cid)
                if compressor is not None:
                    u = compressor.roundtrip(u, ckey)
                return u, ckey, losses

            updates, ckeys, losses = jax.vmap(per_client)(batches, picked)
            payload = {"value": updates}
            if codec.needs_key:
                # stochastic quantizers draw inside encode — same key
                # chain the in-body roundtrip used (ckeys dead-code
                # otherwise)
                payload["key"] = ckeys
            with jax.named_scope("fed.uplink"):
                msg = codec.encode_stacked(payload)
                agg = codec.aggregate(msg, weights)
            with jax.named_scope("fed.server"):
                new_w = jax.tree_util.tree_map(mix_add, w, agg)
            return new_w, state, losses, codec.round_bits(msg)

        return round_fn

    return build


def _fedavg_family_cohort_body(compressor_name: Optional[str]):
    """Cohort-tier builder for fedavg + the post-training compressors."""

    def build(loss_fn, cfg: FLConfig, params: Pytree) -> CohortBody:
        mrn = cfg.fedmrn_config()
        codec = _fedavg_family_codec(compressor_name)(cfg, params)
        compressor = (None if compressor_name is None
                      or compressor_name in _CODEC_COMPRESSORS else
                      make_compressor(compressor_name,
                                      topk_frac=cfg.topk_frac,
                                      qsgd_bits=cfg.qsgd_bits,
                                      noise=mrn.noise))

        def uplink(seed, w, state, batches, cids, weights, round_idx):
            comp_base = jax.random.key(seed + 3)

            def per_client(b, cid):
                u, losses = sgd_local_update(loss_fn, w, b, lr=cfg.lr)
                ckey = jax.random.fold_in(comp_base,
                                          round_idx * 1000 + cid)
                if compressor is not None:
                    u = compressor.roundtrip(u, ckey)
                return u, ckey, losses

            updates, ckeys, losses = jax.vmap(per_client)(batches, cids)
            payload = {"value": updates}
            if codec.needs_key:
                payload["key"] = ckeys
            return codec.encode_stacked(payload), weights, losses

        def apply(seed, w, state, agg, round_idx, n_valid=None):
            return jax.tree_util.tree_map(mix_add, w, agg), state

        return codec, uplink, apply

    return build


def _fedpm_codec(cfg: FLConfig, params: Pytree) -> MaskCodec:
    """Bernoulli-sampled masks, no noise seed: the server aggregate is
    the raw VOTE count (``normalize=False``; the body passes unit
    weights and applies the Beta(1,1) smoothing), integer-dtype when
    ``int_mask_agg``."""
    return MaskCodec(
        template_of(params), name="fedpm", mode="binary", normalize=False,
        count_dtype=(min_count_dtype(cfg.clients_per_round)
                     if cfg.int_mask_agg else None),
        backend=cfg.backend, privacy=cfg.privacy)


def fedpm_posterior(m_sum: Pytree, nv, *, clamp: bool):
    """Beta(1,1)-smoothed mask posterior + logit scores from a vote sum.

    ``clamp`` bounds the smoothed probability to the open interval the
    NOISELESS release spans, [1/(nv+2), (nv+1)/(nv+2)] — the DP count
    noise can push a raw sum below −1 or past nv+1, whose logit is NaN
    and would freeze training.  With ``clamp=False`` this is exactly the
    pre-privacy expression, bitwise.
    """
    probs = jax.tree_util.tree_map(lambda s: (s + 1.0) / (nv + 2.0), m_sum)
    if clamp:
        lo = 1.0 / (nv + 2.0)
        hi = (nv + 1.0) / (nv + 2.0)
        probs = jax.tree_util.tree_map(
            lambda p_: jnp.clip(p_, lo, hi), probs)
    scores = jax.tree_util.tree_map(
        lambda p_: jnp.log(p_ / (1 - p_)), probs)          # sigmoid^-1
    return probs, scores


def _fedpm_body(loss_fn, cfg: FLConfig, params: Pytree) -> RoundBody:
    noise_cfg = NoiseConfig(dist="uniform", alpha=0.1)
    codec = _fedpm_codec(cfg, params)

    def round_fn(seed, w, state, batches, picked, round_idx, weights):
        # frozen random init, regenerated from the traced seed: keeps the
        # body pure in `seed` so sweeps can vmap over it.  The expression
        # is loop-invariant inside the experiment scan (seed is a chunk
        # argument), and one RNG pass over the params is small next to a
        # round's K×S training steps either way.
        w_frozen = gen_noise(jax.random.key(seed), params, noise_cfg)
        key_base = jax.random.key(seed + 2)
        scores = state["scores"]

        def per_client(b, cid):
            ckey = jax.random.fold_in(key_base, round_idx * 1000 + cid)
            s_final, losses = fedpm_local(loss_fn, w_frozen, scores, b,
                                          lr=cfg.lr, key=ckey, sample=False)
            nb = jax.tree_util.tree_leaves(b)[0].shape[0]
            mask_key = jax.random.fold_in(ckey, nb + 1)
            probs_k = jax.tree_util.tree_map(jax.nn.sigmoid, s_final)
            return probs_k, mask_key, losses

        probs_k, mask_keys, losses = jax.vmap(per_client)(batches, picked)
        # ---- uplink: the fused mask draw + pack + vote count -----------
        # the posterior counts VOTES — one per surviving client,
        # ``client_weights`` magnitudes ignored (the original FedPM
        # rule): weighted counts could exceed K, push probs past 1 and
        # NaN the logit below.  A zero weight marks a DROPPED client
        # (availability trace) and casts no vote.
        votes = (weights > 0).astype(jnp.float32)
        with jax.named_scope("fed.uplink"):
            msg, m_sum = codec.uplink_stacked(probs_k, None, mask_keys,
                                              votes, probs=True,
                                              round_idx=round_idx)
        nv = jnp.sum(votes)
        # Beta(1,1)-posterior (Laplace-smoothed) mask-frequency estimate,
        # accumulated in f32 regardless of param dtype.  The raw nv-client
        # mean hits exactly 0/1 whenever all clients agree, and logit of
        # the clipped value (±9.2) saturates next round's sigmoid scores —
        # training freezes.  Smoothing bounds scores to |logit| ≤ ln(nv+1);
        # under privacy the noisy sum is additionally clamped back into
        # the noiseless release's span before the logit (NaN guard).
        with jax.named_scope("fed.server"):
            probs, new_scores = fedpm_posterior(
                m_sum, nv, clamp=cfg.privacy is not None)
            new_w = jax.tree_util.tree_map(
                lambda wf, pr: wf * (pr > 0.5), w_frozen, probs)
        return new_w, {"scores": new_scores}, losses, codec.round_bits(msg)

    return round_fn


def _fedpm_cohort_body(loss_fn, cfg: FLConfig, params: Pytree) -> CohortBody:
    """Cohort-streaming FedPM: per-cohort vote counts, Beta(1,1)-smoothed
    posterior applied once on the merged count."""
    noise_cfg = NoiseConfig(dist="uniform", alpha=0.1)
    codec = _fedpm_codec(cfg, params)

    def uplink(seed, w, state, batches, cids, weights, round_idx):
        w_frozen = gen_noise(jax.random.key(seed), params, noise_cfg)
        key_base = jax.random.key(seed + 2)
        scores = state["scores"]

        def per_client(b, cid):
            ckey = jax.random.fold_in(key_base, round_idx * 1000 + cid)
            s_final, losses = fedpm_local(loss_fn, w_frozen, scores, b,
                                          lr=cfg.lr, key=ckey, sample=False)
            nb = jax.tree_util.tree_leaves(b)[0].shape[0]
            mask_key = jax.random.fold_in(ckey, nb + 1)
            probs_k = jax.tree_util.tree_map(jax.nn.sigmoid, s_final)
            return probs_k, mask_key, losses

        probs_k, mask_keys, losses = jax.vmap(per_client)(batches, cids)
        # same Bernoulli draw (key/uniform streams) the fused uplink
        # performs; votes carry unit weight (original FedPM rule)
        masks = tree_bernoulli_stacked(probs_k, mask_keys)
        msg = codec.encode_stacked({"mask": masks})
        return msg, jnp.ones_like(weights), losses

    def apply(seed, w, state, m_sum, round_idx, n_valid=None):
        # the smoothing denominator is the number of VOTES aggregated —
        # under availability/quorum degradation the engines pass the
        # merged partial's weight mass (ones × valid) as ``n_valid``
        K = (jnp.float32(cfg.clients_per_round) if n_valid is None
             else n_valid)
        probs, new_scores = fedpm_posterior(m_sum, K,
                                            clamp=cfg.privacy is not None)
        w_frozen = gen_noise(jax.random.key(seed), params, noise_cfg)
        new_w = jax.tree_util.tree_map(
            lambda wf, pr: wf * (pr > 0.5), w_frozen, probs)
        return new_w, {"scores": new_scores}

    return codec, uplink, apply


def _fedsparsify_codec(cfg: FLConfig, params: Pytree) -> SparseCodec:
    return SparseCodec(template_of(params), name="fedsparsify",
                       frac=cfg.sparsify_frac)


def _fedsparsify_body(loss_fn, cfg: FLConfig, params: Pytree) -> RoundBody:
    codec = _fedsparsify_codec(cfg, params)

    def round_fn(seed, w, state, batches, picked, round_idx, weights):
        def per_client(b, cid):
            return fedsparsify_local(loss_fn, w, b, lr=cfg.lr,
                                     frac=cfg.sparsify_frac)

        w_locals, losses = jax.vmap(per_client)(batches, picked)
        # the pruned local WEIGHTS are the payload: top-k values+indices
        with jax.named_scope("fed.uplink"):
            msg = codec.encode_stacked({"value": w_locals})
            agg = codec.aggregate(msg, weights)
        with jax.named_scope("fed.server"):
            new_w = jax.tree_util.tree_map(lambda p, a: a.astype(p.dtype),
                                           w, agg)
        return new_w, state, losses, codec.round_bits(msg)

    return round_fn


def _fedsparsify_cohort_body(loss_fn, cfg: FLConfig,
                             params: Pytree) -> CohortBody:
    codec = _fedsparsify_codec(cfg, params)

    def uplink(seed, w, state, batches, cids, weights, round_idx):
        def per_client(b, cid):
            return fedsparsify_local(loss_fn, w, b, lr=cfg.lr,
                                     frac=cfg.sparsify_frac)

        w_locals, losses = jax.vmap(per_client)(batches, cids)
        return codec.encode_stacked({"value": w_locals}), weights, losses

    def apply(seed, w, state, agg, round_idx, n_valid=None):
        new_w = jax.tree_util.tree_map(lambda p, a: a.astype(p.dtype),
                                       w, agg)
        return new_w, state

    return codec, uplink, apply


# ---------------------------------------------------------------------------
# validation + built-in registration
# ---------------------------------------------------------------------------

def _frac_validate(field):
    def validate(cfg):
        v = getattr(cfg, field)
        if not 0 < v <= 1:
            raise ValueError(f"{field} must be in (0, 1], got {v}")
    return validate


def _qsgd_validate(cfg):
    if cfg.qsgd_bits < 1:
        raise ValueError(f"qsgd_bits must be >= 1, got {cfg.qsgd_bits}")


def _register_builtins() -> None:
    for name in ("fedmrn", "fedmrns"):
        register_algorithm(Algorithm(
            name=name, make_round_body=_fedmrn_body, codec=_fedmrn_codec,
            init_state=_fedmrn_state, validate=_fedmrn_validate,
            make_cohort_body=_fedmrn_cohort_body))
    register_algorithm(Algorithm(
        name="fedavg", make_round_body=_fedavg_family_body(None),
        codec=_fedavg_family_codec(None),
        make_cohort_body=_fedavg_family_cohort_body(None)))
    register_algorithm(Algorithm(
        name="fedpm", make_round_body=_fedpm_body, codec=_fedpm_codec,
        init_state=lambda cfg, p: {"scores": _tree_zeros_like(p)},
        make_cohort_body=_fedpm_cohort_body))
    register_algorithm(Algorithm(
        name="fedsparsify", make_round_body=_fedsparsify_body,
        codec=_fedsparsify_codec,
        validate=_frac_validate("sparsify_frac"),
        make_cohort_body=_fedsparsify_cohort_body))
    for comp in COMPRESSOR_REGISTRY:
        if comp == "none":
            continue
        register_algorithm(Algorithm(
            name=comp, make_round_body=_fedavg_family_body(comp),
            codec=_fedavg_family_codec(comp),
            validate=(_frac_validate("topk_frac") if comp == "topk"
                      else _qsgd_validate if comp == "qsgd"
                      else _no_validate),
            make_cohort_body=_fedavg_family_cohort_body(comp)))


_register_builtins()
