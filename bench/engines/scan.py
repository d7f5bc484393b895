"""Runner of the scan engine: ``Experiment.run(engine="scan")``.

One call is one experiment of the mix's ``rounds_per_call`` rounds in
one dispatch (a ``lax.scan`` over the round body), ending in its own
device-to-host read of the round metrics.  Set-up builds the
``Experiment`` and drives its compiled program once from the seed (the
first call, whose rounds the reference follows); the window then calls
``Experiment.run(seed=s + i)`` on that same object until the time is up.
Each call's round-0 loss is kept: the reference follows round 0 of the
first ``check_calls`` calls (the mix's count) once the window has closed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import harness
import traffic_gen


class Cell:
    def __init__(self, ctx: dict, seed: int):
        from repro.data import make_federated_dataset
        from repro.fed import Experiment, ExperimentSpec, FLConfig
        self.ctx, self.cfg, self.tr = ctx, ctx["config"], ctx["traffic"]
        cfg, tr = self.cfg, self.tr
        self.fam = harness.family(cfg)
        self.seed = harness.program_seed(seed)
        self.R = tr["rounds_per_call"]
        x, y, xt, yt = self.fam.make_data(cfg)
        parts = traffic_gen.partition(tr, np.asarray(y), cfg["data_seed"])
        self.data = {"x": x, "y": y, "x_test": xt, "y_test": yt,
                     "parts": parts}
        ds = make_federated_dataset(x, y, parts, x_test=xt, y_test=yt,
                                    batch_seed=cfg["data_seed"])
        self.params = self.fam.init_params(cfg, self.seed)
        loss_fn, eval_apply = self.fam.program_fns(cfg)
        self.flcfg = FLConfig(
            algorithm=tr["algorithm"], num_clients=tr["num_clients"],
            clients_per_round=tr["clients_per_round"], rounds=self.R,
            local_steps=tr["local_steps"], batch_size=tr["batch_size"],
            lr=tr["lr"], seed=self.seed, noise_alpha=tr["noise_alpha"],
            shared_noise=tr["shared_noise"],
            int_mask_agg=tr["int_mask_agg"])
        self.exp = Experiment(ExperimentSpec(
            loss_fn=loss_fn, params=self.params, data=ds, config=self.flcfg,
            eval_apply=eval_apply, eval_every=tr["eval_every"],
            eval_batch_size=tr["eval_batch_size"]))
        self.eval_rounds = [r for r in range(self.R)
                            if r % tr["eval_every"] == 0 or r == self.R - 1]
        self._ref_fns = {}
        self.calls = []        # (seed, round-0 loss) of each later call
        self.finite = True     # every later call's losses finite
        self.call_s = []       # wall, host CPU, run-queue and steal s a call

    # ---- the checked first call ------------------------------------------

    def first_call(self, seed=None, params=None):
        """Drive the compiled program through the first call, as
        ``Experiment.run`` dispatches it (same program, same argument
        types), and keep what it produced: ``Experiment.run`` returns
        the round metrics but not the weights, which the check needs."""
        import dataclasses
        from repro.fed.engine import make_client_schedule
        seed = self.seed if seed is None else seed
        params = self.params if params is None else params
        run_chunk, state0, metrics0 = self.exp._program("seeded",
                                                        self.exp.cfg)
        cfg = dataclasses.replace(self.exp.cfg, seed=seed)
        sched = jnp.asarray(make_client_schedule(cfg), jnp.int32)
        w, _, metrics = run_chunk(jnp.int32(seed), params, state0, metrics0,
                                  jnp.int32(0), sched[0:self.R],
                                  n_rounds=self.R)
        loss = np.asarray(metrics["loss"])
        acc = np.asarray(metrics["acc"])
        return {"seed": seed, "w0": self.fam.to_host(params),
                "w": {"call": self.fam.to_host(w)},
                "loss": [float(v) for v in loss],
                "acc": {r: float(acc[r]) for r in self.eval_rounds},
                "finite": bool(np.all(np.isfinite(loss)))}

    # ---- the window --------------------------------------------------------

    def _run(self):
        """One ``Experiment.run`` with the next seed; keeps its round-0
        loss and whether its losses are finite."""
        seed = self.seed + 1 + len(self.calls)
        res = self.exp.run(seed=seed)
        self.calls.append((seed, float(res.local_loss[0])))
        self.finite &= bool(np.all(np.isfinite(res.local_loss)))

    def window(self, seconds: float):
        """Call ``Experiment.run`` until ``seconds`` have passed; returns
        ``(rounds, wall seconds, calls)``."""
        calls = 0
        t0 = time.perf_counter()
        while True:
            t, c = time.perf_counter(), time.process_time()
            q, st = harness.host_waits()
            self._run()
            q1, st1 = harness.host_waits()
            self.call_s.append((time.perf_counter() - t,
                                time.process_time() - c, q1 - q, st1 - st))
            calls += 1
            wall = time.perf_counter() - t0
            if wall >= seconds:
                return calls * self.R, wall, calls

    def traced_window(self, annotate):
        calls = self.tr["trace_calls"]
        t0 = time.perf_counter()
        for _ in range(calls):
            with annotate("Experiment.run"):
                self._run()
        return calls * self.R, time.perf_counter() - t0

    def checked_calls(self) -> list:
        """The calls after the first that the check follows: the window's
        first ``check_calls - 1``, topped up by further calls of the same
        program where the window made fewer (a traced window)."""
        n = self.tr.get("check_calls", 1) - 1
        while len(self.calls) < n:
            self._run()
        return self.calls[:n]

    def hlo_text(self) -> str:
        """The compiled round program's HLO (from the compile cache)."""
        run_chunk, state0, metrics0 = self.exp._program("seeded",
                                                        self.exp.cfg)
        sched = jnp.zeros((self.R, self.tr["clients_per_round"]), jnp.int32)
        return run_chunk.lower(jnp.int32(self.seed), self.params, state0,
                               metrics0, jnp.int32(0), sched,
                               n_rounds=self.R).compile().as_text()

    def release(self):
        self.exp = None

    # ---- work counts -------------------------------------------------------

    def flops(self, rounds: int) -> float:
        """Model FLOPs of ``rounds`` rounds: forward and backward of every
        local step (3x forward) and the forward of every evaluation."""
        tr, fwd = self.tr, self.fam.forward_flops(self.cfg)
        train = (3 * fwd * tr["clients_per_round"] * tr["local_steps"]
                 * tr["batch_size"])
        evals = len(self.eval_rounds) * rounds / self.R
        return train * rounds + evals * fwd * self.cfg["n_test"]

    # ---- the reference -----------------------------------------------------

    def reference(self, first: dict, quant=None, fault=None):
        """The plain reference over the first call's rounds, from the same
        initial weights, seed and inputs."""
        from refround import index_matrix
        ref = harness.reference(self.tr["algorithm"])
        idx, lens = index_matrix(self.data["parts"])
        data = {"x": self.data["x"], "y": self.data["y"], "idx": idx,
                "len": lens}
        round_fn = self._ref_fn(("round", quant, fault), lambda: (
            ref.make_round(self.fam, self.cfg, self.tr,
                           self.cfg["data_seed"], quant=quant, fault=fault)))
        rows = traffic_gen.schedule_rows(first["seed"], self.tr["num_clients"],
                                         self.tr["clients_per_round"], self.R)
        w = jax.tree_util.tree_map(jnp.asarray, first["w0"])
        seed = jnp.int32(first["seed"])
        loss, acc = [], {}
        for r in range(self.R):
            w, losses = round_fn(seed, w, jnp.int32(r), jnp.asarray(rows[r]),
                                 data)
            loss.append(float(jnp.mean(losses[:, -1])))
            if r in self.eval_rounds:
                predict = self._ref_fn(("predict", quant), lambda: (
                    self.fam.ref_predictor(self.cfg, quant)))
                acc[r] = self.fam.ref_accuracy(predict, self.data["x_test"],
                                               self.data["y_test"], w)
        calls_loss0 = []
        for seed_i, _ in first.get("calls", []):
            row = traffic_gen.schedule_rows(
                seed_i, self.tr["num_clients"], self.tr["clients_per_round"],
                1)[0]
            _, losses = round_fn(jnp.int32(seed_i), jax.tree_util.tree_map(
                jnp.asarray, first["w0"]), jnp.int32(0), jnp.asarray(row),
                data)
            calls_loss0.append(float(jnp.mean(losses[:, -1])))
        grads = ref.first_grad(self.fam, self.cfg, self.tr,
                               self.cfg["data_seed"], seed,
                               jax.tree_util.tree_map(jnp.asarray,
                                                      first["w0"]),
                               jnp.int32(0), jnp.int32(rows[0][0]), data)
        return {"w": {"call": self.fam.to_host(w)}, "loss": loss, "acc": acc,
                "calls_loss0": calls_loss0,
                "grad_norms": np.array([float(jnp.linalg.norm(g)) for g in
                                        jax.tree_util.tree_leaves(grads)])}

    def _ref_fn(self, key, make):
        """The reference's jitted functions, made once per cell (a new
        jitted function would compile again)."""
        if key not in self._ref_fns:
            self._ref_fns[key] = make()
        return self._ref_fns[key]

    def compare(self, first: dict, ref: dict) -> dict:
        return check.numbers(first, ref, check.counted_leaves(
            ref["grad_norms"]))
