"""Declarative Experiment API — specs in, typed results out.

The user-facing surface of the federated engine stack:

  :class:`ExperimentSpec`   what to run — algorithm (registry name or
                            :class:`~repro.fed.algorithms.Algorithm`
                            plugin), :class:`FLConfig`, a device-resident
                            :class:`~repro.data.FederatedDataset`, and
                            model refs (``loss_fn`` + optional
                            ``eval_apply`` from which the on-device eval
                            program is auto-wired over the test split).
  :class:`Experiment`       the facade: ``run()`` executes the spec on
                            any engine (scan by default — one jitted
                            program per chunk) and returns a frozen
                            :class:`RunResult`; ``sweep()`` runs a
                            multi-seed axis as ONE vmapped program
                            (S seeds resident per dispatch, one compile)
                            with a host-loop fallback, optionally crossed
                            with a config ``grid``, returning a
                            :class:`SweepResult`.
  :class:`RunResult`        typed per-run trajectories (acc / loss /
                            uplink bits / schedule / wall time) with an
                            engine-independent ``to_history()`` dict whose
                            key schema (:data:`HISTORY_KEYS`) is identical
                            across scan / batched / looped.

Example::

    spec = ExperimentSpec(loss_fn=cnn_loss, params=params, data=ds,
                          config=FLConfig(algorithm="fedmrn", rounds=30),
                          eval_apply=cnn_apply, eval_every=5)
    exp = Experiment(spec)
    result = exp.run()                        # RunResult, scan engine
    sweep = exp.sweep(seeds=8)                # one vmapped program
    mean, std = sweep.point.mean_std()

``run()`` names its host phases for the profiler with
``jax.profiler.TraceAnnotation`` spans, which record nothing unless a
profiler trace is running: ``fed.run.schedule`` (client schedule and its
copy to the device) and ``fed.run.dispatch`` (the chunk calls; at the
first call also trace, lower and compile) on the scan engine, and on
every engine ``fed.run.readback`` (the one device-to-host read of the
round metrics) and ``fed.run.result`` (building the :class:`RunResult`).

Compiled scan/sweep programs are cached on the :class:`Experiment`
(keyed by config with the seed normalised out — the seed is a *traced*
argument), so repeated scan ``run()``/``sweep()`` calls and host-loop
sweep fallbacks never pay a second compile.  The batched/looped
reference engines rebuild their per-round programs each ``run()`` call
(they exist for parity and benchmarks, not repeated driving).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from functools import partial
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import tree_num_params
from ..core.comm import CommRecord
from ..core.evaluation import make_eval_program
from ..data.federated import CohortedDataset, FederatedDataset
from .algorithms import (ALGORITHMS, Algorithm, FLConfig, algorithm_codec,
                         get_algorithm, register_algorithm, uplink_bits)
from .availability import (AvailabilityTrace, check_engine_support,
                           make_availability, require_survivors)
from .codecs import UplinkCodec
from .engine import (eval_round_indices, make_client_schedule,
                     make_cohort_engine, make_seeded_experiment_program,
                     make_sharded_sweep_program, make_sweep_program,
                     sweep_device_count)

Pytree = Any

ENGINES = ("scan", "cohort", "service", "batched", "looped")

# engine="cohort" shards the population into cohorts of this many clients
# when the caller passes neither a CohortedDataset nor cohort_size=
DEFAULT_COHORT_SIZE = 256

# The engine-independent history schema: every engine's to_history() dict
# has EXACTLY these keys (golden-tested in tests/test_experiment_api.py).
HISTORY_KEYS = frozenset({
    "algorithm", "engine", "acc", "round", "local_loss",
    "uplink_bits_per_client", "uplink_bits_round", "params",
    "participation_round", "schedule", "num_dispatches", "wall_s",
    "final_acc", "dp_epsilon", "dp_delta",
})


# ---------------------------------------------------------------------------
# typed results
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RunResult:
    """One experiment's trajectories — frozen, engine-independent.

    ``num_dispatches`` counts the jitted round/chunk programs the driver
    dispatched: ⌈R/chunk⌉ for scan, R for batched, R·K for looped.
    """

    algorithm: str
    engine: str
    config: FLConfig
    seed: int
    eval_rounds: Tuple[int, ...]
    acc: Tuple[float, ...]                 # one entry per eval round
    local_loss: Tuple[float, ...]          # one entry per round
    uplink_bits_round: Tuple[float, ...]   # measured K-client wire bits
    #   per round: summed encoded WireMsg buffer sizes, same on every
    #   engine (NOT a precomputed estimate)
    uplink_bits_per_client: int
    num_params: int
    schedule: np.ndarray                   # (R, K) int32 client selection
    num_dispatches: int
    wall_s: float
    participation_round: Tuple[int, ...] = ()   # surviving clients per
    #   round; K everywhere unless an availability trace / fault plan
    #   degraded a round
    dp_epsilon: Tuple[float, ...] = ()     # cumulative (ε, δ)-DP spend
    #   after each round, accounted at the TRUE recorded participation;
    #   all-inf when cfg.privacy is None
    dp_delta: float = 0.0                  # the δ the ε column is at

    @property
    def final_acc(self) -> float:
        return self.acc[-1]

    @property
    def total_uplink_bits(self) -> float:
        return float(sum(self.uplink_bits_round))

    def to_history(self) -> Dict[str, Any]:
        """The legacy ``run_federated`` history dict (unified schema)."""
        return {
            "algorithm": self.algorithm,
            "engine": self.engine,
            "acc": list(self.acc),
            "round": list(self.eval_rounds),
            "local_loss": list(self.local_loss),
            "uplink_bits_per_client": self.uplink_bits_per_client,
            "uplink_bits_round": list(self.uplink_bits_round),
            "params": self.num_params,
            "participation_round": [int(p)
                                    for p in self.participation_round],
            "schedule": self.schedule,
            "num_dispatches": self.num_dispatches,
            "wall_s": self.wall_s,
            "final_acc": self.final_acc,
            "dp_epsilon": [float(e) for e in self.dp_epsilon],
            "dp_delta": float(self.dp_delta),
        }

    @classmethod
    def from_history(cls, cfg: FLConfig, engine: str,
                     hist: Mapping[str, Any]) -> "RunResult":
        return cls(
            algorithm=hist["algorithm"], engine=engine, config=cfg,
            seed=cfg.seed,
            eval_rounds=tuple(int(r) for r in hist["round"]),
            acc=tuple(float(a) for a in hist["acc"]),
            local_loss=tuple(float(x) for x in hist["local_loss"]),
            uplink_bits_round=tuple(float(b)
                                    for b in hist["uplink_bits_round"]),
            uplink_bits_per_client=int(hist["uplink_bits_per_client"]),
            num_params=int(hist["params"]),
            schedule=np.asarray(hist["schedule"]),
            num_dispatches=int(hist["num_dispatches"]),
            wall_s=float(hist["wall_s"]),
            participation_round=tuple(
                int(p) for p in hist.get(
                    "participation_round",
                    [cfg.clients_per_round] * cfg.rounds)),
            dp_epsilon=tuple(float(e) for e in hist.get("dp_epsilon", ())),
            dp_delta=float(hist.get("dp_delta", 0.0)))


def dp_epsilon_schedule(cfg: FLConfig, participation: Sequence[int],
                        num_params: int) -> Tuple[
                            Tuple[float, ...], float]:
    """Cumulative (ε, δ) spend per round at the TRUE participation.

    ``num_params`` is the dimension of the released count vector (the
    model's parameter count) — the accountant normalizes by the L2
    sensitivity at ``cfg.privacy.adjacency`` (Δ·√num_params for the
    default client adjacency).  Accounts every round at the
    participation actually recorded — availability dropouts and
    quorum-degraded service rounds spend LESS budget (smaller sampling
    fraction q = survivors / num_clients).  NOTE conditioning on
    realized dropouts assumes availability is independent of client
    data (true for the built-in traces/fault plans); pass the scheduled
    ``[clients_per_round] * rounds`` for the conditioning-free bound.
    Returns ``((inf,)*R, 0.0)`` when ``cfg.privacy`` is None.
    """
    if cfg.privacy is None:
        return (math.inf,) * len(tuple(participation)), 0.0
    from .privacy import dp_mask_mode, round_epsilons
    eps = round_epsilons(cfg.privacy, participation, cfg.num_clients,
                         dp_mask_mode(cfg.algorithm), num_params)
    return tuple(float(e) for e in eps), cfg.privacy.delta


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """All seeds of one grid point: per-seed runs + aggregate views."""

    overrides: Tuple[Tuple[str, Any], ...]   # config fields this point sets
    seeds: Tuple[int, ...]
    runs: Tuple[RunResult, ...]              # one per seed, same order

    @property
    def eval_rounds(self) -> Tuple[int, ...]:
        return self.runs[0].eval_rounds

    @property
    def acc(self) -> np.ndarray:             # (S, n_eval)
        return np.stack([np.asarray(r.acc) for r in self.runs])

    @property
    def local_loss(self) -> np.ndarray:      # (S, R)
        return np.stack([np.asarray(r.local_loss) for r in self.runs])

    @property
    def final_acc(self) -> np.ndarray:       # (S,)
        return np.asarray([r.final_acc for r in self.runs])

    def mean_std(self) -> Tuple[float, float]:
        fa = self.final_acc
        return float(fa.mean()), float(fa.std())

    def summary_row(self) -> Dict[str, Any]:
        mean, std = self.mean_std()
        return {**dict(self.overrides), "seeds": len(self.seeds),
                "final_acc_mean": round(mean, 4),
                "final_acc_std": round(std, 4)}


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """A (grid ×) multi-seed sweep: per-seed trajectories + mean±std."""

    points: Tuple[SweepPoint, ...]
    seeds: Tuple[int, ...]
    vmapped: bool          # True: seeds ran as ONE vmapped program/point
    wall_s: float
    devices: int = 1       # >1: seed axis shard_map'd over this many devices

    def summary(self) -> List[Dict[str, Any]]:
        return [p.summary_row() for p in self.points]

    # ---- single-point conveniences (the seeds-only sweep) -------------

    @property
    def point(self) -> SweepPoint:
        if len(self.points) != 1:
            raise ValueError(
                f"sweep has {len(self.points)} grid points; index "
                ".points explicitly")
        return self.points[0]

    @property
    def runs(self) -> Tuple[RunResult, ...]:
        return self.point.runs

    @property
    def acc(self) -> np.ndarray:
        return self.point.acc

    @property
    def final_acc(self) -> np.ndarray:
        return self.point.final_acc


# ---------------------------------------------------------------------------
# the declarative spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """Everything an experiment needs, declared up front.

    ``algorithm`` defaults to ``config.algorithm``; pass a registry name
    to override it, or an :class:`Algorithm` instance to run a plugin
    (auto-registered if its name is free).  Eval wiring, in precedence
    order: an explicit pure ``eval_program`` (params -> scalar metric);
    else ``eval_apply`` (params, x) -> logits, auto-wired into a batched
    on-device eval program over the dataset's test split; else — for the
    host-loop engines only — a Python ``eval_fn``.

    ``data`` is a device-resident :class:`FederatedDataset` (every
    engine) or a host-resident :class:`CohortedDataset` (the streaming
    ``engine="cohort"`` only — the other engines need the whole
    population device-resident).
    """

    loss_fn: Callable[[Pytree, Any], jax.Array]
    params: Pytree
    data: Union[FederatedDataset, CohortedDataset]
    config: FLConfig
    algorithm: Optional[Union[str, Algorithm]] = None
    eval_program: Optional[Callable[[Pytree], jax.Array]] = None
    eval_apply: Optional[Callable[[Pytree, jax.Array], jax.Array]] = None
    eval_fn: Optional[Callable[[Pytree], float]] = None
    eval_batch_size: int = 256
    eval_every: int = 1
    client_weights: Optional[Tuple[float, ...]] = None
    # explicit availability trace; None derives one from the config's
    # availability/dropout/churn knobs (still None for "always")
    availability: Optional[AvailabilityTrace] = None

    def __post_init__(self):
        if self.client_weights is not None:
            object.__setattr__(self, "client_weights",
                               tuple(float(w) for w in self.client_weights))

    def resolved_config(self) -> FLConfig:
        """The config with any spec-level algorithm override applied."""
        cfg = self.config
        if self.algorithm is None:
            return cfg
        name = (self.algorithm.name if isinstance(self.algorithm, Algorithm)
                else self.algorithm)
        return dataclasses.replace(cfg, algorithm=name)


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

class Experiment:
    """Run / sweep an :class:`ExperimentSpec` on any engine."""

    def __init__(self, spec: ExperimentSpec):
        if not isinstance(spec.data, (FederatedDataset, CohortedDataset)):
            raise ValueError(
                "ExperimentSpec.data must be a device-resident "
                "FederatedDataset (see repro.data.make_federated_dataset) "
                "or a host-resident CohortedDataset for engine='cohort'; "
                "legacy host batch callbacks only work through the "
                "deprecated run_federated shim")
        self.spec = spec
        self.cfg = spec.resolved_config()
        if isinstance(spec.algorithm, Algorithm):
            existing = ALGORITHMS.get(spec.algorithm.name)
            if existing is None:
                register_algorithm(spec.algorithm)
            elif existing is not spec.algorithm:
                raise ValueError(
                    f"algorithm name {spec.algorithm.name!r} is already "
                    "registered by a different plugin")
        self.algorithm = get_algorithm(self.cfg.algorithm)
        self.cfg.validate()
        self.algorithm.validate(self.cfg)
        if spec.data.num_clients != self.cfg.num_clients:
            raise ValueError(
                f"dataset has {spec.data.num_clients} clients, cfg expects "
                f"{self.cfg.num_clients}")
        if (spec.client_weights is not None
                and len(spec.client_weights) != self.cfg.num_clients):
            raise ValueError(
                f"client_weights has {len(spec.client_weights)} entries, "
                f"cfg expects {self.cfg.num_clients}")
        self._programs: Dict[Any, Tuple[Callable, Pytree, Pytree]] = {}
        self._eval_prog: Optional[Callable] = None
        self._runners: Dict[Any, Any] = {}   # cohort/service engine cache
        self._cohorted: Dict[int, CohortedDataset] = {}   # per cohort size
        self.service_report = None   # last engine="service" wire report

    # ---- the wire format ----------------------------------------------

    def codec(self) -> UplinkCodec:
        """The algorithm's typed uplink codec for this spec's model —
        the same object the round bodies route payloads through."""
        return algorithm_codec(self.cfg, self.spec.params)

    def comm_record(self) -> CommRecord:
        """The codec's cost report: measured uplink bits (summed encoded
        ``WireMsg`` buffer sizes), the paper-style figure, and the f32
        downlink.  With ``config.privacy`` set, carries the PLANNED
        (ε, δ) after ``cfg.rounds`` full-participation rounds (a run's
        actual spend — at true participation — lives on the RunResult).
        """
        rec = self.codec().wire_bits(self.spec.params)
        if self.cfg.privacy is None:
            return rec
        eps, delta = dp_epsilon_schedule(
            self.cfg, [self.cfg.clients_per_round] * self.cfg.rounds,
            tree_num_params(self.spec.params))
        return dataclasses.replace(rec, dp_epsilon=eps[-1], dp_delta=delta)

    # ---- eval wiring --------------------------------------------------

    def eval_program(self) -> Optional[Callable[[Pytree], jax.Array]]:
        """The pure on-device eval program (auto-wired from the dataset).

        Built once and cached — auto-wiring wrap-pads a device copy of
        the whole test split, which should not be paid per run/grid point.
        """
        spec = self.spec
        if spec.eval_program is not None:
            return spec.eval_program
        if spec.eval_apply is not None:
            if self._eval_prog is None:
                if spec.data.x_test is None:
                    raise ValueError(
                        "eval_apply given but the dataset has no test "
                        "split; pass x_test/y_test to "
                        "make_federated_dataset or an explicit "
                        "eval_program")
                self._eval_prog = make_eval_program(
                    spec.eval_apply, spec.data.x_test, spec.data.y_test,
                    batch_size=spec.eval_batch_size)
            return self._eval_prog
        return None

    def _host_eval_fn(self) -> Callable[[Pytree], float]:
        if self.spec.eval_fn is not None:
            return self.spec.eval_fn
        prog = self.eval_program()
        if prog is None:
            raise ValueError("need eval_fn or eval_program")
        jitted = jax.jit(prog)
        return lambda p: float(jitted(p))

    # ---- availability --------------------------------------------------

    def _availability(self, cfg: FLConfig,
                      seed: Optional[int] = None
                      ) -> Optional[AvailabilityTrace]:
        """The run's availability trace: the spec's explicit trace, else
        one derived from the config knobs (None when always-available)."""
        if self.spec.availability is not None:
            return self.spec.availability
        return make_availability(cfg, seed)

    def _degrade_schedule(self, cfg: FLConfig, engine: str,
                          schedule: np.ndarray,
                          trace: Optional[AvailabilityTrace]):
        """Apply a trace to a schedule: optional dynamic resampling, the
        ``(R, K)`` valid mask, per-round participation.  Returns
        ``(schedule, valid, participation)`` — ``(schedule, None, None)``
        when no trace applies (the bitwise-invariant path)."""
        if trace is None:
            return schedule, None, None
        check_engine_support(cfg, trace, engine)
        if cfg.avail_resample:
            schedule = trace.resample_schedule(schedule, cfg.seed)
        valid = trace.valid_for(schedule)
        require_survivors(valid, resample_hint=cfg.avail_resample)
        participation = valid.sum(axis=1).astype(np.int64)
        return schedule, valid, participation

    # ---- program cache ------------------------------------------------

    def _program(self, kind: str, cfg: FLConfig, devices: int = 1):
        """Build-or-fetch the (seed-polymorphic) chunk/sweep program.

        The cache key normalises the seed out: seeds are traced arguments,
        so one compiled program serves every seed of a sweep AND every
        ``run(seed=...)`` override.  ``devices`` keys the sharded sweep
        variants (the mesh shape is baked into the program).
        """
        key = (kind, devices, dataclasses.replace(cfg, seed=0),
               self.spec.eval_every, self.spec.client_weights)
        if key not in self._programs:
            if kind == "sweep_sharded":
                maker = partial(make_sharded_sweep_program, devices=devices)
            elif kind == "sweep":
                maker = make_sweep_program
            else:
                maker = make_seeded_experiment_program
            prog = self.eval_program()
            if prog is None:
                raise ValueError(
                    "engine='scan' folds eval into the program and needs a "
                    "pure eval_program (params -> metric); pass "
                    "eval_program or eval_apply to ExperimentSpec (build "
                    "one with repro.core.make_eval_program)")
            self._programs[key] = maker(
                self.spec.loss_fn, cfg, self.spec.params, self.spec.data,
                eval_program=prog, eval_every=self.spec.eval_every,
                client_weights=self.spec.client_weights)
        return self._programs[key]

    # ---- run ----------------------------------------------------------

    def run(self, *, engine: str = "scan", seed: Optional[int] = None,
            chunk: Optional[int] = None,
            cohort_size: Optional[int] = None,
            prefetch: bool = True,
            service: Optional[Any] = None) -> RunResult:
        """Execute the spec once; returns a frozen :class:`RunResult`.

        ``engine="scan"`` (default) fuses the whole experiment into
        ⌈R/chunk⌉ jitted dispatches; ``"cohort"`` streams a
        larger-than-HBM population through the device cohort by cohort
        (``cohort_size`` clients staged at a time, default
        min(num_clients, 256); ``prefetch=False`` disables the
        double-buffered host→device overlap); ``"service"`` spawns a
        loopback HTTP coordinator plus K client threads that exchange
        framed ``WireMsg`` bytes (``service=`` takes a
        :class:`repro.fed.service.ServiceConfig` — sync barrier or async
        staleness-weighted rounds; the measured wire accounting lands on
        ``Experiment.service_report``); ``"batched"`` dispatches one
        program per round; ``"looped"`` is the per-client reference
        loop.  ``seed`` overrides ``config.seed`` without rebuilding
        programs.
        """
        if engine not in ENGINES:
            raise ValueError(f"unknown engine {engine!r} (one of {ENGINES})")
        if service is not None and engine != "service":
            raise ValueError(
                f"service= only applies to engine='service', not "
                f"{engine!r}")
        if engine == "cohort":
            cfg = self.cfg if seed is None else dataclasses.replace(
                self.cfg, seed=int(seed))
            return self._run_cohort(cfg, cohort_size, prefetch)
        if cohort_size is not None:
            raise ValueError(
                f"cohort_size= only applies to engine='cohort', not "
                f"{engine!r}")
        if engine == "service":
            cfg = self.cfg if seed is None else dataclasses.replace(
                self.cfg, seed=int(seed))
            return self._run_service(cfg, service)
        if isinstance(self.spec.data, CohortedDataset):
            raise ValueError(
                f"engine={engine!r} needs the whole population "
                "device-resident (a FederatedDataset); a CohortedDataset "
                "only runs on engine='cohort'")
        cfg = self.cfg if seed is None else dataclasses.replace(
            self.cfg, seed=int(seed))
        if engine == "scan":
            return self._run_scan(cfg, chunk)
        return self._run_host_loop(cfg, engine)

    def _run_scan(self, cfg: FLConfig, chunk: Optional[int]) -> RunResult:
        run_chunk, state0, metrics0 = self._program("seeded", cfg)
        chunk = cfg.rounds if chunk is None else max(1, int(chunk))
        chunk = min(chunk, cfg.rounds)
        with TraceAnnotation("fed.run.schedule"):
            schedule = make_client_schedule(cfg)
            schedule, valid, participation = self._degrade_schedule(
                cfg, "scan", schedule, self._availability(cfg))
            sched_dev = jnp.asarray(schedule, jnp.int32)
            valid_dev = None if valid is None else jnp.asarray(valid,
                                                               jnp.float32)
            seed_dev = jnp.int32(cfg.seed)
        w, state, metrics = self.spec.params, state0, metrics0
        t0 = time.time()
        dispatches = 0
        with TraceAnnotation("fed.run.dispatch"):
            for r0 in range(0, cfg.rounds, chunk):
                n = min(chunk, cfg.rounds - r0)
                if valid_dev is None:
                    w, state, metrics = run_chunk(
                        seed_dev, w, state, metrics, jnp.int32(r0),
                        sched_dev[r0:r0 + n], n_rounds=n)
                else:
                    w, state, metrics = run_chunk(
                        seed_dev, w, state, metrics, jnp.int32(r0),
                        sched_dev[r0:r0 + n], valid_dev[r0:r0 + n],
                        n_rounds=n)
                dispatches += 1
        # the ONLY device→host reads of the whole experiment
        result = self._result_from_metrics(
            cfg, "scan", metrics, schedule, dispatches, time.time() - t0,
            participation=participation)
        return result

    def _cohorted_data(self, cohort_size: Optional[int]) -> CohortedDataset:
        """The spec's data as a CohortedDataset (converted + cached)."""
        if isinstance(self.spec.data, CohortedDataset):
            if cohort_size is not None:
                raise ValueError(
                    "cohort_size= conflicts with a pre-built "
                    "CohortedDataset — the shard layout is fixed at "
                    "construction (make_cohorted_dataset / .cohorted)")
            return self.spec.data
        size = (min(self.spec.data.num_clients, DEFAULT_COHORT_SIZE)
                if cohort_size is None else int(cohort_size))
        if size not in self._cohorted:
            self._cohorted[size] = self.spec.data.cohorted(size)
        return self._cohorted[size]

    def _run_cohort(self, cfg: FLConfig, cohort_size: Optional[int],
                    prefetch: bool) -> RunResult:
        """The streaming cohort engine, through the same RunResult path.

        The runner cache is keyed like :meth:`_program` (seed normalised
        out — ``CohortRunner.run`` takes the seed at call time), plus the
        cohort layout; ``prefetch`` is a run-time toggle, not a cache key.
        """
        data = self._cohorted_data(cohort_size)
        prog = self.eval_program()
        if prog is None:
            raise ValueError(
                "engine='cohort' folds eval into its jitted dispatch "
                "sequence and needs a pure eval_program (params -> "
                "metric); pass eval_program or eval_apply to "
                "ExperimentSpec")
        key = ("cohort", id(data), dataclasses.replace(cfg, seed=0),
               self.spec.eval_every, self.spec.client_weights)
        if key not in self._runners:
            self._runners[key] = make_cohort_engine(
                self.spec.loss_fn, cfg, self.spec.params, data,
                eval_program=prog, eval_every=self.spec.eval_every,
                client_weights=self.spec.client_weights)
        runner = self._runners[key]
        t0 = time.time()
        trace = self._availability(cfg)
        if trace is None:
            metrics, schedule, dispatches = runner.run(seed=cfg.seed,
                                                       prefetch=prefetch)
            participation = None
        else:
            schedule, valid, participation = self._degrade_schedule(
                cfg, "cohort", make_client_schedule(cfg), trace)
            metrics, schedule, dispatches = runner.run(
                seed=cfg.seed, schedule=schedule, prefetch=prefetch,
                valid=valid)
        return self._result_from_metrics(
            cfg, "cohort", metrics, schedule, dispatches, time.time() - t0,
            participation=participation)

    def _run_service(self, cfg: FLConfig, service) -> RunResult:
        """The wire-true coordinator engine (loopback HTTP, ISSUE 8).

        The runner (jitted client step + server aggregation programs)
        is cached like the cohort runner; ``service`` — a
        :class:`repro.fed.service.ServiceConfig` — is a run-time knob
        (transport + sync/async round semantics), never a cache key.
        The run's measured wire accounting (:class:`ServiceReport`,
        incl. the MEASURED downlink ``CommRecord``) lands on
        ``self.service_report``.
        """
        from .service import make_service_engine
        prog = self.eval_program()
        if prog is None:
            raise ValueError(
                "engine='service' evaluates on the coordinator and "
                "needs a pure eval_program (params -> metric); pass "
                "eval_program or eval_apply to ExperimentSpec")
        key = ("service", dataclasses.replace(cfg, seed=0),
               self.spec.eval_every, self.spec.client_weights)
        if key not in self._runners:
            self._runners[key] = make_service_engine(
                self.spec.loss_fn, cfg, self.spec.params, self.spec.data,
                eval_program=prog, eval_every=self.spec.eval_every,
                client_weights=self.spec.client_weights)
        runner = self._runners[key]
        t0 = time.time()
        trace = self._availability(cfg)
        if trace is None:
            metrics, schedule, dispatches = runner.run(seed=cfg.seed,
                                                       service=service)
        else:
            schedule, valid, _ = self._degrade_schedule(
                cfg, "service", make_client_schedule(cfg), trace)
            metrics, schedule, dispatches = runner.run(
                seed=cfg.seed, service=service, schedule=schedule,
                valid=valid, local_steps=trace.local_steps)
        self.service_report = runner.report
        # the coordinator's measured per-round uplink counts — faults
        # and quorum-degraded rounds show up here, not just trace drops
        participation = (list(self.service_report.participation)
                         if self.service_report.participation else None)
        return self._result_from_metrics(
            cfg, "service", metrics, schedule, dispatches,
            time.time() - t0, participation=participation)

    def _result_from_metrics(self, cfg, engine, metrics, schedule,
                             dispatches, wall_s,
                             participation=None) -> RunResult:
        with TraceAnnotation("fed.run.readback"):
            host = jax.device_get(metrics)
        with TraceAnnotation("fed.run.result"):
            loss, acc, bits = host["loss"], host["acc"], host["uplink_bits"]
            rounds = eval_round_indices(cfg, self.spec.eval_every)
            if participation is None:
                participation = [cfg.clients_per_round] * cfg.rounds
            dp_eps, dp_delta = dp_epsilon_schedule(
                cfg, participation, tree_num_params(self.spec.params))
            return RunResult(
                algorithm=cfg.algorithm, engine=engine, config=cfg,
                seed=cfg.seed, eval_rounds=tuple(rounds),
                acc=tuple(float(acc[r]) for r in rounds),
                local_loss=tuple(float(x) for x in loss),
                uplink_bits_round=tuple(float(b) for b in bits),
                uplink_bits_per_client=uplink_bits(cfg, self.spec.params),
                num_params=tree_num_params(self.spec.params),
                schedule=schedule, num_dispatches=dispatches,
                wall_s=wall_s,
                participation_round=tuple(int(p) for p in participation),
                dp_epsilon=dp_eps, dp_delta=dp_delta)

    def _run_host_loop(self, cfg: FLConfig, engine: str) -> RunResult:
        from .simulation import _run_batched          # no import cycle:
        from .looped import run_federated_looped      # lazy, one-way
        schedule = make_client_schedule(cfg)
        schedule, valid, _ = self._degrade_schedule(
            cfg, engine, schedule, self._availability(cfg))
        batch_fn = self.spec.data.batch_fn(steps=cfg.local_steps,
                                           batch=cfg.batch_size)
        eval_fn = self._host_eval_fn()
        cw = (list(self.spec.client_weights)
              if self.spec.client_weights is not None else None)
        runner = (run_federated_looped if engine == "looped"
                  else _run_batched)
        hist = runner(self.spec.loss_fn, self.spec.params, batch_fn,
                      eval_fn, cfg, schedule=schedule,
                      eval_every=self.spec.eval_every, client_weights=cw,
                      valid=valid)
        result = RunResult.from_history(cfg, engine, hist)
        dp_eps, dp_delta = dp_epsilon_schedule(
            cfg, result.participation_round, result.num_params)
        return dataclasses.replace(result, dp_epsilon=dp_eps,
                                   dp_delta=dp_delta)

    # ---- sweep --------------------------------------------------------

    def sweep(self, seeds: Union[int, Sequence[int]] = 4, *,
              grid: Optional[Mapping[str, Sequence[Any]]] = None,
              vmapped: bool = True,
              sharding: Optional[str] = None,
              devices: Optional[int] = None,
              chunk: Optional[int] = None) -> SweepResult:
        """Run a multi-seed (× config-grid) sweep.

        ``seeds`` is either a count (seeds ``cfg.seed .. cfg.seed+S-1``)
        or an explicit sequence.  With ``vmapped=True`` (default) the S
        seeds of each grid point run as ONE vmapped scan program — one
        compile, S experiments resident per dispatch; ``vmapped=False``
        host-loops a single seed-polymorphic compiled program (the
        fallback, and the baseline the sweep benchmark compares against).
        ``sharding="devices"`` additionally spreads the seed axis over
        the local devices via ``shard_map`` (S/D seeds vmapped per
        device, still one compile, no collectives); ``devices`` pins the
        mesh size (default: the largest divisor of S that fits the
        machine — 1 degenerates to the plain vmapped program).  ``grid``
        maps FLConfig field names to value lists; the grid cross product
        is host-looped (axes like batch size change shapes, and closure
        constants like lr live outside the traced argument set), with
        seeds vmapped/sharded *within* each point.
        """
        if isinstance(self.spec.data, CohortedDataset):
            raise ValueError(
                "sweep() runs the vmapped scan programs, which need the "
                "whole population device-resident (a FederatedDataset); "
                "host-loop engine='cohort' runs via run() per seed")
        if sharding not in (None, "none", "devices"):
            raise ValueError(
                f"unknown sharding {sharding!r} (None or 'devices')")
        sharded = sharding == "devices"
        if sharded and not vmapped:
            raise ValueError(
                "sharding='devices' shards the vmapped program; it cannot "
                "combine with vmapped=False")
        if devices is not None and not sharded:
            raise ValueError(
                "devices= only applies to sharding='devices' — without it "
                "the argument would be silently ignored")
        if isinstance(seeds, (int, np.integer)):
            if seeds <= 0:
                raise ValueError(f"need at least one seed, got {seeds}")
            seed_list = tuple(self.cfg.seed + i for i in range(int(seeds)))
        else:
            seed_list = tuple(int(s) for s in seeds)
            if not seed_list:
                raise ValueError("need at least one seed")
        grid = dict(grid or {})
        for field in grid:
            if field not in {f.name for f in dataclasses.fields(FLConfig)}:
                raise ValueError(f"unknown FLConfig field {field!r} in grid")
        if "seed" in grid:
            raise ValueError(
                "the seed axis is not a grid field — pass seeds=[...] "
                "(a 'seed' grid would be silently shadowed by it)")
        points = [dict(zip(grid, vals))
                  for vals in itertools.product(*grid.values())] or [{}]

        if sharded:
            n_dev = (sweep_device_count(len(seed_list)) if devices is None
                     else int(devices))
            if n_dev < 1 or len(seed_list) % n_dev:
                raise ValueError(
                    f"{len(seed_list)} seeds do not divide over "
                    f"{n_dev} devices (pick devices dividing the seed "
                    "count, or omit it for auto)")
        else:
            n_dev = 1

        t0 = time.time()
        out = []
        for overrides in points:
            cfg = dataclasses.replace(self.cfg, **overrides)
            cfg.validate()
            get_algorithm(cfg.algorithm).validate(cfg)
            if cfg.num_clients != self.spec.data.num_clients:
                # must fail here: in-program client_idx[cid] gathers would
                # silently CLAMP out-of-range client ids, not raise
                raise ValueError(
                    f"grid point {overrides} sets num_clients="
                    f"{cfg.num_clients} but the dataset has "
                    f"{self.spec.data.num_clients} clients")
            runs = (self._sweep_point_vmapped(cfg, seed_list, chunk,
                                              devices=n_dev)
                    if vmapped else
                    self._sweep_point_host(cfg, seed_list, chunk))
            out.append(SweepPoint(
                overrides=tuple(sorted(overrides.items())),
                seeds=seed_list, runs=tuple(runs)))
        return SweepResult(points=tuple(out), seeds=seed_list,
                           vmapped=vmapped, wall_s=time.time() - t0,
                           devices=n_dev)

    def _sweep_point_vmapped(self, cfg: FLConfig, seeds: Tuple[int, ...],
                             chunk: Optional[int],
                             devices: int = 1) -> List[RunResult]:
        S = len(seeds)
        kind = "sweep_sharded" if devices > 1 else "sweep"
        run_sweep, state0, metrics0 = self._program(kind, cfg, devices)
        per_seed = [make_client_schedule(cfg, s) for s in seeds]
        traces = [self._availability(cfg, s) for s in seeds]
        valids = None
        participations = None
        if any(t is not None for t in traces):
            # each seed keeps its own trace (seed-salted like the
            # schedules) — the (S, R, K) valid mask rides the same vmap
            valids, participations = [], []
            for i, s in enumerate(seeds):
                cfg_s = dataclasses.replace(cfg, seed=s)
                sched, valid, part = self._degrade_schedule(
                    cfg_s, "scan", per_seed[i], traces[i])
                if valid is None:                    # mixed grids: pad
                    valid = np.ones(sched.shape, np.float32)
                    part = np.full((cfg.rounds,), cfg.clients_per_round,
                                   np.int64)
                per_seed[i] = sched
                valids.append(valid)
                participations.append(part)
            valids = np.stack(valids)                           # (S, R, K)
        schedules = np.stack(per_seed)                          # (S, R, K)
        sched_dev = jnp.asarray(schedules, jnp.int32)
        valid_dev = (None if valids is None
                     else jnp.asarray(valids, jnp.float32))
        seeds_dev = jnp.asarray(seeds, jnp.int32)

        def bcast(t):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    jnp.asarray(x)[None], (S,) + jnp.shape(x)), t)

        w, state, metrics = (bcast(self.spec.params), bcast(state0),
                             bcast(metrics0))
        n_chunk = cfg.rounds if chunk is None else max(1, int(chunk))
        n_chunk = min(n_chunk, cfg.rounds)
        t0 = time.time()
        dispatches = 0
        for r0 in range(0, cfg.rounds, n_chunk):
            n = min(n_chunk, cfg.rounds - r0)
            if valid_dev is None:
                w, state, metrics = run_sweep(
                    seeds_dev, w, state, metrics, jnp.int32(r0),
                    sched_dev[:, r0:r0 + n], n_rounds=n)
            else:
                w, state, metrics = run_sweep(
                    seeds_dev, w, state, metrics, jnp.int32(r0),
                    sched_dev[:, r0:r0 + n], valid_dev[:, r0:r0 + n],
                    n_rounds=n)
            dispatches += 1
        wall = time.time() - t0
        loss = np.asarray(metrics["loss"])                      # (S, R)
        acc = np.asarray(metrics["acc"])
        bits = np.asarray(metrics["uplink_bits"])
        rounds = eval_round_indices(cfg, self.spec.eval_every)
        bpc = uplink_bits(cfg, self.spec.params)
        n_params = tree_num_params(self.spec.params)
        return [RunResult(
            algorithm=cfg.algorithm, engine="scan",
            config=dataclasses.replace(cfg, seed=s), seed=s,
            eval_rounds=tuple(rounds),
            acc=tuple(float(acc[i, r]) for r in rounds),
            local_loss=tuple(float(x) for x in loss[i]),
            uplink_bits_round=tuple(float(b) for b in bits[i]),
            uplink_bits_per_client=bpc, num_params=n_params,
            schedule=schedules[i], num_dispatches=dispatches,
            wall_s=wall / S,
            participation_round=tuple(
                int(p) for p in (
                    [cfg.clients_per_round] * cfg.rounds
                    if participations is None else participations[i])),
            # NOTE: at fixed dp_seed all seeds of a vmapped sweep share
            # one noise realization per round (the DP stream is keyed on
            # (dp_seed, round) only) — the accountant is per-run either
            # way, so the ε schedule below is exact per seed
            dp_epsilon=dp_epsilon_schedule(
                cfg, ([cfg.clients_per_round] * cfg.rounds
                      if participations is None
                      else participations[i]), n_params)[0],
            dp_delta=(cfg.privacy.delta
                      if cfg.privacy is not None else 0.0),
        ) for i, s in enumerate(seeds)]

    def _sweep_point_host(self, cfg: FLConfig, seeds: Tuple[int, ...],
                          chunk: Optional[int]) -> List[RunResult]:
        """Fallback: S sequential dispatches of ONE seeded program."""
        return [self._run_scan(dataclasses.replace(cfg, seed=s), chunk)
                for s in seeds]
