"""Registry-driven pod rounds — any FL algorithm as a single pjit program
on the production mesh (the paper's protocol mapped onto pod hardware,
DESIGN.md §3).

Clients = slices of one mesh axis ('pod' when multi-pod — cross-silo FL
between pods over the slow inter-pod links — else 'data').  The round is
the SAME pure body every simulation engine runs — whatever
:class:`~repro.fed.algorithms.Algorithm` is registered under the chosen
name builds it — lowered with the stacked client axis partitioned over
the client mesh axis:

  1. the body vmaps the K selected clients over the stacked axis; XLA
     partitions the vmapped dim over the client mesh axis, so clients
     train in parallel, tensor/ZeRO-parallel *within* their slice;
  2. each family's own uplink CODEC lowers under the mesh:
     :class:`~repro.fed.codecs.MaskCodec` families (fedmrn/fedmrns,
     fedpm) aggregate mask bits — when the codec is count-aggregatable
     (fedpm, or fedmrn with ``shared_noise``, the pod default) and the
     round weights are uniform, ``make_pod_round`` switches the config
     to ``int_mask_agg``: the server sum Σ_k m_k is reduced in the
     minimal integer dtype holding ``⌈log2(K+1)⌉`` bits
     (``codecs.min_count_dtype``), so the cross-client all-reduce moves
     int8/int16 mask counts instead of f32 — a ≥4× collective-byte cut
     at simulation K, verified against the compiled HLO in
     ``tests/test_sharded_engine.py``; dense-codec families (fedavg +
     compressors, fedsparsify) all-reduce f32 updates;
  3. cross-round state (EF residuals, fedpm scores) flows through the
     ``state`` pytree exactly as on the scan engine.

XLA's SPMD partitioner splits the round body over the client axis, and
it cannot partition a Mosaic kernel, so the pod program always runs the
jnp (``ref``) kernel backend; an explicit ``backend="pallas"`` is
refused.

Because the pod program and the simulation engines share one round body,
pod trajectories are ≡ the scan engine's at fixed seed/schedule/batches
(``tests/test_sharded_engine.py`` asserts it to 1e-6 on 8 fake CPU
devices) — there is no pod-only algorithm fork left to drift.  On a TPU
mesh the partitioned convolutions round differently from the one-chip
program, and FedMRN's mask thresholds amplify that, so there the weights
are bitwise equal only when the client updates are (``chip_smoke.py
--chips 4`` checks both).

``PodRoundSpec(rounds=R)`` lowers an R-round ``lax.scan`` over the round
body — the pod-path mirror of the simulation engine's multi-round
experiment program — reusing one batch stream across rounds (dry-run
semantics, for probing multi-round HLO and collective totals).  All
hyper-parameters come from the spec's :class:`FLConfig` — the same
config object every other engine consumes — so pod train/noise keys are
derived by the registered algorithm itself, never duplicated here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..sharding.rules import param_shardings
from .algorithms import (ALGORITHMS, Algorithm, FLConfig, get_algorithm,
                         register_algorithm)
from .codecs import MaskCodec
from .engine import normalize_round_outputs

Pytree = Any

# the dry-run probe trains S=2 local steps (linear in FLOPs, enough to
# exercise the scan) — everything else keeps the FLConfig defaults
POD_PROBE_CONFIG = FLConfig(local_steps=2)


@dataclasses.dataclass(frozen=True)
class PodRoundSpec:
    """What the pod program runs: an :class:`FLConfig` + fusion depth.

    ``config`` is the SAME config type every simulation engine takes —
    local steps, lr, noise, seed, backend, shared_noise all live there
    and are interpreted by the registered algorithm (no pod-side
    duplicate defaults).  ``rounds > 1`` fuses a multi-round ``lax.scan``
    over the round body into one dispatch (same fusion as the scan
    engine), with per-round keys; the batch stream is reused across
    rounds (dry-run semantics).
    """

    config: FLConfig = POD_PROBE_CONFIG
    rounds: int = 1

    def resolved(self, algorithm: Union[str, Algorithm, None]) -> FLConfig:
        """The config with the ``make_pod_round`` algorithm applied."""
        if algorithm is None:
            return self.config
        name = (algorithm.name if isinstance(algorithm, Algorithm)
                else algorithm)
        return dataclasses.replace(self.config, algorithm=name)


def client_axis_of(mesh) -> str:
    return "pod" if "pod" in mesh.shape else "data"


def pod_param_shardings(p_specs: Pytree, mesh, *, num_layers: int,
                        encoder_layers: int = 0) -> Pytree:
    """Param shardings for the pod round: ZeRO minus the client axis.

    Params must NOT be zero-sharded over the client axis (each client
    needs the full model in its slice), so ZeRO uses the remaining data
    axes only.
    """
    client_axis = client_axis_of(mesh)
    fsdp = tuple(a for a in ("pod", "data")
                 if a in mesh.shape and a != client_axis)
    return param_shardings(p_specs, mesh, num_layers=num_layers,
                           encoder_layers=encoder_layers, zero=bool(fsdp),
                           fsdp_axes=fsdp)


def pod_batch_specs(batch_specs: Dict[str, Any], num_clients: int,
                    local_steps: int) -> Dict[str, Any]:
    """Split a global-batch spec into per-client local streams.

    ``(B, ...)`` → ``(K, S, b_local, ...)`` with ``b_local = B // (K·S)``
    (floor, min 1) — the round bodies' input contract: a stacked client
    axis of S-step local batch stacks.
    """
    def split(s):
        B = s.shape[0]
        b_local = max(1, B // (num_clients * local_steps))
        return jax.ShapeDtypeStruct(
            (num_clients, local_steps, b_local) + s.shape[1:], s.dtype)

    return {k: split(v) for k, v in batch_specs.items()}


def _replicated(mesh, tree: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


def _state_shardings(mesh, state_specs: Pytree, cfg: FLConfig,
                     client_axis: str) -> Pytree:
    """Client-stacked state leaves shard over the client axis; the rest
    (fedpm scores, any global pytree) replicate.

    A hint, not a contract: leaves whose leading dim is the client count
    (EF residual stacks) are the only ones that grow with clients.
    """
    D = mesh.shape[client_axis]

    def shard_one(s):
        shape = jnp.shape(s)
        if len(shape) >= 1 and shape[0] == cfg.num_clients \
                and shape[0] % D == 0:
            return NamedSharding(mesh, P(client_axis))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(shard_one, state_specs)


def make_pod_round(
    algorithm: Union[str, Algorithm, None],
    mesh,
    spec: PodRoundSpec = PodRoundSpec(),
    *,
    loss_fn: Callable[[Pytree, Any], jax.Array],
    p_specs: Pytree,
    p_shard: Optional[Pytree] = None,
    batch_specs: Pytree,
    client_weights: Optional[Any] = None,
    int_mask_agg: Optional[bool] = None,
) -> Tuple[Callable, Tuple, Tuple]:
    """Lower any registered algorithm's round body as a pod program.

    Returns ``(step, arg_specs, in_shardings)`` for ``jit`` + ``lower``:

      step(w, state, batches, picked, round_idx)
          -> (new_w, new_state, losses)

    ``batches`` is the stacked-client pytree the round bodies consume —
    ``(K, S, B, ...)`` leaves with ``K = cfg.clients_per_round`` — and is
    sharded over the client mesh axis (which must divide K).  ``picked``
    is the ``(K,)`` int32 client-id vector (``arange(K)`` for the probe,
    a schedule row for trajectory runs), ``round_idx`` a scalar int32.
    With ``spec.rounds > 1`` the round body is scanned ``rounds`` times
    starting at ``round_idx`` (losses gain a leading round axis) and the
    same ``batches`` feed every round — a cost/sharding probe, not
    training.

    ``p_shard`` defaults to fully-replicated params (fine for tests /
    small models); pass :func:`pod_param_shardings` for the production
    ZeRO layout.  ``client_weights`` (one float per ``cfg.num_clients``)
    reproduces the simulation engines' weighted aggregation — the round
    weights are gathered as ``weights_all[picked]``, exactly like the
    scan engine's chunk body; None means uniform.  State specs are
    derived from the algorithm's own ``init_state`` via ``eval_shape`` —
    nothing is materialised here.

    ``int_mask_agg`` controls the mask-count wire format on the server
    side: ``None`` (default) auto-enables the ``⌈log2(K+1)⌉``-bit
    integer aggregate whenever the algorithm's codec is a
    count-aggregatable :class:`~repro.fed.codecs.MaskCodec` and the
    weights are uniform; ``False`` forces the f32 reference aggregation
    (the benchmark baseline); ``True`` requires a count-aggregatable
    family and uniform weights (raises otherwise).

    Like :class:`~repro.fed.api.ExperimentSpec`, an unregistered
    :class:`Algorithm` instance auto-registers; an instance whose name is
    taken by a DIFFERENT plugin raises instead of silently running the
    registered one.
    """
    if isinstance(algorithm, Algorithm):
        existing = ALGORITHMS.get(algorithm.name)
        if existing is None:
            register_algorithm(algorithm)
        elif existing is not algorithm:
            raise ValueError(
                f"algorithm name {algorithm.name!r} is already registered "
                "by a different plugin")
    cfg = spec.resolved(algorithm)
    algo = get_algorithm(cfg.algorithm)
    if (cfg.int_mask_agg or int_mask_agg) and client_weights is not None:
        raise ValueError(
            "int_mask_agg requires uniform client weights "
            "(client_weights=None)")
    if cfg.backend == "pallas":
        raise ValueError(
            "make_pod_round partitions the round body with XLA's SPMD "
            "partitioner, which cannot split a Mosaic kernel over the "
            "client axis; leave FLConfig.backend unset (the pod program "
            "runs the jnp kernels)")
    cfg = dataclasses.replace(cfg, backend="ref")
    if cfg.privacy is not None:
        # the DP release is defined over the five simulation engines'
        # partial/finalize chain; the pod lowering has no parity oracle
        # for the noisy count wire yet, so refuse rather than emit an
        # unaudited release
        raise ValueError(
            "privacy= is not supported by make_pod_round — run DP "
            "experiments on engine='scan', 'batched', 'looped', "
            "'cohort' or 'service'")
    codec = algo.codec(cfg, p_specs)
    count_ok = (isinstance(codec, MaskCodec) and codec.count_aggregatable)
    if int_mask_agg is None:
        # pod default: mask families whose server sum is a pure count
        # (fedpm, fedmrn with shared noise) aggregate in the minimal
        # integer dtype holding ⌈log2(K+1)⌉ bits — the cross-client
        # all-reduce then moves int8/int16 mask counts instead of f32;
        # an explicit cfg.int_mask_agg is honoured (and validated below)
        int_mask_agg = (cfg.int_mask_agg
                        or (client_weights is None and count_ok))
    if int_mask_agg and not count_ok:
        # must fail loudly: a dense codec never reads the flag, so the
        # caller would silently measure the ordinary f32 all-reduce
        raise ValueError(
            f"int_mask_agg=True but {cfg.algorithm!r}'s codec "
            f"({type(codec).__name__}) is not a count-aggregatable "
            "MaskCodec (needs mask uplink, and shared_noise for fedmrn)")
    if bool(int_mask_agg) != cfg.int_mask_agg:
        cfg = dataclasses.replace(cfg, int_mask_agg=bool(int_mask_agg))
    cfg.validate()
    algo.validate(cfg)

    client_axis = client_axis_of(mesh)
    D = mesh.shape[client_axis]
    K = cfg.clients_per_round
    if K % D:
        raise ValueError(
            f"clients_per_round={K} must be divisible by the client mesh "
            f"axis {client_axis!r} (size {D})")
    for k, leaf in jax.tree_util.tree_leaves_with_path(batch_specs):
        if jnp.shape(leaf)[0] != K:
            raise ValueError(
                f"batch leaf {k} has leading dim {jnp.shape(leaf)[0]}, "
                f"expected the stacked client axis K={K} "
                "(see pod_batch_specs)")

    round_body = algo.make_round_body(loss_fn, cfg, p_specs)
    state_specs = jax.eval_shape(lambda p: algo.init_state(cfg, p), p_specs)
    seed = jnp.int32(cfg.seed)
    if client_weights is None:
        weights_all = jnp.ones((cfg.num_clients,), jnp.float32)
    else:
        cw = [float(x) for x in client_weights]
        if len(cw) != cfg.num_clients:
            # must fail here: weights_all[picked] inside jit would
            # silently CLAMP out-of-range client ids instead of raising
            raise ValueError(
                f"client_weights has {len(cw)} entries, cfg expects "
                f"{cfg.num_clients}")
        weights_all = jnp.asarray(cw, jnp.float32)

    def step(w, state, batches, picked, round_idx):
        weights = weights_all[picked]
        if spec.rounds == 1:
            w, state, losses, _ = normalize_round_outputs(
                round_body(seed, w, state, batches, picked, round_idx,
                           weights), 0.0)
            return w, state, losses

        def body(carry, r):
            w_c, state_c = carry
            w_c, state_c, losses, _ = normalize_round_outputs(
                round_body(seed, w_c, state_c, batches, picked, r,
                           weights), 0.0)
            return (w_c, state_c), losses

        rs = round_idx + jnp.arange(spec.rounds, dtype=jnp.int32)
        (w, state), losses = jax.lax.scan(body, (w, state), rs)
        return w, state, losses            # losses: (rounds, K, S)

    if p_shard is None:
        p_shard = _replicated(mesh, p_specs)
    b_shard = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(client_axis)), batch_specs)
    s_shard = _state_shardings(mesh, state_specs, cfg, client_axis)

    arg_specs = (p_specs, state_specs, batch_specs,
                 jax.ShapeDtypeStruct((K,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
    in_shardings = (p_shard, s_shard, b_shard,
                    NamedSharding(mesh, P(client_axis)),
                    NamedSharding(mesh, P()))
    return step, arg_specs, in_shardings
