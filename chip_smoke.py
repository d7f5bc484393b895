"""Smoke run of FedMRN training on a TPU, through the normal entry point.

    python chip_smoke.py              # one chip: the scan engine
    python chip_smoke.py --chips 4    # four chips: the pod round only

One chip: the paper's 4-conv CNN at FMNIST shape (28x28x1, 10 classes,
channels 64-64-128-128) trains 3 FedMRN rounds of 10 sampled clients out
of 100 (non-IID-2, 10 local steps of batch 64) through
``ExperimentSpec`` -> ``Experiment.run(engine="scan")`` -> ``MaskCodec``,
in two configurations:

  fedmrn                 per-client noise: ``psm_fused`` + ``uplink_fused``
                         (the kernel's weighted sums are Eq. 5)
  fedmrn shared+int      shared noise, integer counts: the same kernels,
                         the server update from the kernel's counts

Each is checked against the same run on the jnp reference kernels
(``FLConfig(backend="ref")``) at the same seed, and the compiled round
program must hold every expected Mosaic kernel.  The server step that
reads only the wire words (``MaskCodec.aggregate_apply``, as the looped
engine and the error-feedback round run it) must compile to
``unpack_counts_apply`` and agree with the reference.  Then every
main-path kernel is called directly at the smoke's size and must match
its jnp oracle bit for bit.

``--chips 4``: ``make_pod_round`` (fedmrn, shared noise, integer counts,
K=8 clients over a 4-chip client mesh) runs 3 rounds against the scan
engine on one chip at the same seed, on the same (jnp) kernels, twice:
with a linear probe loss, whose client updates are the same elementwise
values in both programs, the weights must agree bit for bit; with the
CNN's loss, the per-round loss must stay within the band its chaotic
training allows.  The compiled pod program must reduce the integer mask
counts with an all-reduce over the four chips.

Timings printed here are a smoke, not a benchmark.  The last line of
standard output is the JSON result; any failure exits non-zero without
printing it.  Set ``JAX_COMPILATION_CACHE_DIR`` to choose where compiled
programs are cached (default: ``.jax_cache/`` next to this script).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ROUNDS = 3
# per-round mean loss between two programs of the same training: pallas
# vs ref kernels, or the pod vs the scan engine.  Each pair is fused and
# partitioned differently by XLA, so the convolutions round differently,
# and 30 local steps of stochastic mask thresholds make the training
# chaotic: on a v5e, a 1-ulp change of the initial parameters moves the
# per-round loss by up to 0.147.  This bound only catches gross
# divergence (a wrong kernel or collective, NaN); exactness is checked
# per kernel and, on four chips, with the linear probe loss.
LOSS_ATOL = 0.3


class SmokeError(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeError(msg)


def _setup_jax():
    """Import jax with the compile cache set; fail unless on a TPU."""
    _check((ROOT / "src" / "repro").is_dir(),
           f"the repro package is not next to this script ({ROOT / 'src'})")
    _check(not os.environ.get("REPRO_BACKEND"),
           "REPRO_BACKEND is set; the smoke must run the platform's own "
           "kernel backend")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    dev = jax.devices()[0]
    _check(dev.platform == "tpu",
           f"no TPU: jax.devices()[0].platform is {dev.platform!r}")
    from repro.core.backend import default_backend, pallas_interpret
    _check(default_backend() == "pallas" and not pallas_interpret(),
           f"kernel backend resolves to {default_backend()!r}, not "
           "compiled pallas")
    return jax


def make_task(seed: int, *, n: int = 12000, n_test: int = 2000,
              num_clients: int = 100, channels=(64, 64, 128, 128)):
    """The smoke's data and model: ``(loss_fn, eval_apply, params, ds)``."""
    import jax
    from repro.data import (make_federated_dataset, make_image_task,
                            make_partition)
    from repro.models.cnn import cnn_apply, cnn_init, cnn_loss
    task = make_image_task(seed, n=n, hw=28, n_classes=10)
    n_train = n - n_test
    parts = make_partition("noniid2", seed, task.y[:n_train], num_clients)
    ds = make_federated_dataset(task.x[:n_train], task.y[:n_train], parts,
                                x_test=task.x[n_train:],
                                y_test=task.y[n_train:], batch_seed=seed)
    params = cnn_init(jax.random.key(seed), n_classes=10, channels=channels,
                      in_ch=1, hw=28)
    return cnn_loss, cnn_apply, params, ds


def smoke_config(seed: int, **overrides):
    from repro.fed import FLConfig
    kw = dict(algorithm="fedmrn", num_clients=100, clients_per_round=10,
              rounds=ROUNDS, local_steps=10, batch_size=64, seed=seed)
    return FLConfig(**{**kw, **overrides})


def run_config(cfg, loss_fn, eval_apply, params, ds):
    """Train ``cfg`` on the platform's kernels and on the jnp reference;
    returns ``(result, ref_result, compiled HLO text, timings)``."""
    import dataclasses
    import jax.numpy as jnp
    from repro.fed import Experiment, ExperimentSpec
    from repro.fed.engine import make_client_schedule

    def experiment(c):
        return Experiment(ExperimentSpec(loss_fn=loss_fn, params=params,
                                         data=ds, config=c,
                                         eval_apply=eval_apply))

    exp = experiment(cfg)
    # the scan program Experiment.run dispatches, compiled ahead of time
    # with run()'s own argument types so run() reuses the executable
    run_chunk, state0, metrics0 = exp._program("seeded", exp.cfg)
    sched = jnp.asarray(make_client_schedule(cfg), jnp.int32)
    t0 = time.perf_counter()
    lowered = run_chunk.lower(jnp.int32(cfg.seed), params, state0, metrics0,
                              jnp.int32(0), sched, n_rounds=cfg.rounds)
    t1 = time.perf_counter()
    hlo = lowered.compile().as_text()
    t2 = time.perf_counter()
    res = exp.run()
    t3 = time.perf_counter()
    res = exp.run()
    t4 = time.perf_counter()
    ref = experiment(dataclasses.replace(cfg, backend="ref")).run()
    return res, ref, hlo, (t1 - t0, t2 - t1, t3 - t2,
                           (t4 - t3) / cfg.rounds)


def check_against_ref(name, res, ref):
    dl = max(abs(a - b) for a, b in zip(res.local_loss, ref.local_loss))
    print(f"[{name}] vs ref: uplink bits/round "
          f"{_fmt(res.uplink_bits_round, 0)} ref "
          f"{_fmt(ref.uplink_bits_round, 0)}; max |d loss| {dl:.3g} "
          f"(<= {LOSS_ATOL}); loss {_fmt(res.local_loss)} ref "
          f"{_fmt(ref.local_loss)}; acc {_fmt(res.acc)} ref {_fmt(ref.acc)}")
    _check(res.uplink_bits_round == ref.uplink_bits_round,
           f"[{name}] uplink bits differ from ref")
    _check(all(map(math.isfinite, res.local_loss + res.acc)),
           f"[{name}] non-finite loss or accuracy")
    _check(dl <= LOSS_ATOL,
           f"[{name}] per-round loss differs from ref by {dl} > {LOSS_ATOL}")


def kernel_checks(seed: int, K: int = 10, P: int = 261_066):
    """Every main-path kernel against its jnp oracle at the smoke's
    (K, P): packed words, counts, masks, û and the server update
    bitwise; the f32 weighted sums within their summation error,
    2·K·eps·Σ_k |w_k v_k| for the summed values v (noise, ±1 or 1).
    Returns the names of the checks made."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.mask_uplink import ops as mops
    from repro.kernels.psm_mask.ops import psm_apply
    ks = jax.random.split(jax.random.key(seed), 5)
    u = 0.01 * jax.random.normal(ks[0], (K, P))
    n = 0.01 * jax.random.normal(ks[1], (K, P))
    r = jax.random.uniform(ks[2], (K, P))
    r_pm = jax.random.uniform(ks[3], (K, P))
    w = jnp.linspace(0.5, 1.5, K)
    wsum_tol = (2 * K * np.finfo(np.float32).eps
                * np.abs(np.asarray(w)[:, None] * np.asarray(n)).sum(0))
    done, bad = [], []

    def same(name, pal, ref):
        done.append(name)
        if not np.array_equal(np.asarray(pal), np.asarray(ref)):
            bad.append(name)

    for mode in ("binary", "signed"):
        for r_gate, prog in ((None, None), (r_pm, 0.6)):
            tag = f"uplink_fused {mode}" + (" gated" if prog else "")
            pal, ref = (mops.mask_uplink_fused(
                u, n, r, r_gate, prog, w, mode=mode, want_uhat=True,
                use_pallas=use) for use in (True, False))
            for field in ("words", "counts", "uhat"):
                same(f"{tag} {field}", getattr(pal, field),
                     getattr(ref, field))
            done.append(f"{tag} wsum")
            if np.any(np.abs(np.asarray(pal.wsum) - np.asarray(ref.wsum))
                      > wsum_tol):
                bad.append(f"{tag} wsum")
        pal, ref = (psm_apply(u, n, ks[4], 0.6, mode=mode, use_pallas=use)
                    for use in (True, False))
        same(f"psm_fused {mode} uhat", pal[0], ref[0])
        same(f"psm_fused {mode} mask", pal[1], ref[1])
    probs = jax.nn.sigmoid(100.0 * u)
    pal, ref = (mops.mask_uplink_fused(probs, None, r, weights=w,
                                       mode="prob", use_pallas=use)
                for use in (True, False))
    same("uplink_fused prob words", pal.words, ref.words)
    same("uplink_fused prob counts", pal.counts, ref.counts)
    # prob mode sums w_k · m_k with m in {0, 1}: error 2·K·eps·Σ_k |w_k|
    done.append("uplink_fused prob wsum")
    if np.any(np.abs(np.asarray(pal.wsum) - np.asarray(ref.wsum))
              > 2 * K * np.finfo(np.float32).eps * np.abs(np.asarray(w)).sum()):
        bad.append("uplink_fused prob wsum")
    words = ref.words
    same("unpack_counts", mops.unpack_counts(words, use_pallas=True),
         mops.unpack_counts(words, use_pallas=False))
    for a, b in ((1.0, 0.0), (2.0, -float(K))):
        same(f"unpack_counts_apply a={a:g}", *(mops.unpack_counts_apply(
            words, n[0], u[1], 0.1, a, b, use_pallas=use)
            for use in (True, False)))
    _check(not bad, f"kernels differ from their jnp oracle: {bad}")
    return done


def server_apply_check(name, cfg, params, seed: int):
    """The server step that reads only the wire words, jitted as the
    looped engine jits it: ``MaskCodec.aggregate_apply`` turns one round's
    stacked uplink into the updated model.  On the pallas backend it must
    compile to ``unpack_counts_apply`` and agree with the reference codec
    to rounding; a wrong count would move a weight by a whole noise
    value times the client weight."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.fed.algorithms import get_algorithm
    from repro.sharding.hlo_analysis import mosaic_kernels
    algo = get_algorithm(cfg.algorithm)
    codec = algo.codec(cfg, params)
    ref_codec = algo.codec(dataclasses.replace(cfg, backend="ref"), params)
    K = cfg.clients_per_round
    ks = jax.random.split(jax.random.key(seed + 1), 3)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    scores = treedef.unflatten([
        0.01 * jax.random.normal(jax.random.fold_in(ks[0], i),
                                 (K,) + x.shape)
        for i, x in enumerate(leaves)])
    seed_keys = jnp.broadcast_to(ks[1], (K,))      # shared noise: one seed
    mask_keys = jax.random.split(ks[2], K)
    weights = jnp.ones((K,), jnp.float32)
    msg, _ = jax.jit(ref_codec.uplink_stacked)(scores, seed_keys, mask_keys,
                                               weights)
    apply = jax.jit(codec.aggregate_apply)
    found = mosaic_kernels(apply.lower(msg, weights, params).compile()
                           .as_text())
    print(f"[{name}] server step from the wire words: compiled kernels: "
          f"{', '.join(sorted(found))}")
    _check("unpack_counts_apply" in found,
           f"[{name}] unpack_counts_apply missing from the compiled "
           "server step")
    got = apply(msg, weights, params)
    want = jax.jit(ref_codec.aggregate_apply)(msg, weights, params)
    eps = np.finfo(np.float32).eps
    over = moved = 0
    dw = 0.0
    for g, r, p in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (got, want, params))):
        g, r, p = np.asarray(g), np.asarray(r), np.asarray(p)
        d = np.abs(g - r)
        dw = max(dw, float(d.max()))
        over += int(np.sum(d > 2 * eps * (np.abs(p) + np.abs(r - p))))
        moved += int(np.sum(r != p))
    print(f"[{name}] server step vs ref: max |d w| {dw:.3g}, {over} "
          f"elements beyond 2 ulp of |w| + |update| ({moved} weights "
          "updated)")
    _check(moved > 0 and over == 0, f"[{name}] server step differs from "
                                    "ref, or updates nothing")


def one_chip(seed: int):
    import jax
    from repro.sharding.hlo_analysis import mosaic_kernels
    loss_fn, eval_apply, params, ds = make_task(seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"model: cnn channels=(64, 64, 128, 128) input 28x28x1 "
          f"classes=10 params={n_params}")
    configs = [
        ("fedmrn", smoke_config(seed)),
        ("fedmrn shared+int", smoke_config(seed, shared_noise=True,
                                           int_mask_agg=True)),
    ]
    want = {"psm_fused", "uplink_fused"}
    for name, cfg in configs:
        res, ref, hlo, (lower_s, compile_s, first_s, round_s) = run_config(
            cfg, loss_fn, eval_apply, params, ds)
        found = mosaic_kernels(hlo)
        print(f"[{name}] compiled kernels: {', '.join(sorted(found))}")
        _check(want <= found,
               f"[{name}] expected kernels missing from the compiled "
               f"round program: {sorted(want - found)}")
        print(f"[{name}] smoke timing, not a benchmark: trace+lower "
              f"{lower_s:.2f} s, compile {compile_s:.2f} s, first run "
              f"{first_s:.2f} s, steady {round_s:.4f} s/round")
        check_against_ref(name, res, ref)
    server_apply_check(*configs[1], params, seed)
    done = kernel_checks(seed)
    print(f"kernels vs jnp oracle at K=10 P=261066, bitwise (wsum within "
          f"its f32 summation error): {len(done)} checks passed")


def _fmt(xs, digits: int = 6) -> str:
    return "[" + ", ".join(f"{x:.{digits}f}" for x in xs) + "]"


def linear_probe_loss(params, seed: int, scale: float = 5e-3):
    """``Σ d·w`` for a fixed random ``d``, whatever the batch: every client's
    gradient is ``d``, so local training is elementwise arithmetic that
    any partitioning of the round program computes identically."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    d = treedef.unflatten([jnp.asarray(scale * rng.standard_normal(x.shape),
                                       x.dtype) for x in leaves])

    def loss(w, batch):
        del batch
        return sum(jnp.sum(a * b) for a, b in zip(
            jax.tree_util.tree_leaves(d), jax.tree_util.tree_leaves(w)))
    return loss


def pod_vs_scan(loss_fn, cfg, params, ds, devs):
    """``cfg.rounds`` rounds of ``make_pod_round`` over a 4-chip client
    mesh and of the scan engine on one chip, same seed and schedule.
    Returns ``(pod weights, pod losses, scan weights, scan losses, scan
    run, compiled pod HLO, pod compile s, pod run s)``; ``scan run(p)``
    reruns the scan engine from initial parameters ``p``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.fed.algorithms import get_algorithm
    from repro.fed.engine import make_client_schedule, make_experiment_program
    from repro.fed.sharded import PodRoundSpec, make_pod_round

    K = cfg.clients_per_round
    schedule = jnp.asarray(make_client_schedule(cfg), jnp.int32)
    # the pod program lowers the jnp kernels (XLA cannot partition a
    # Mosaic kernel), so its one-chip reference is the scan engine on them
    run_chunk, state0, metrics0 = make_experiment_program(
        loss_fn, dataclasses.replace(cfg, backend="ref"), params, ds)

    def scan_run(p):
        w, _, metrics = run_chunk(p, state0, metrics0, jnp.int32(0),
                                  schedule, n_rounds=cfg.rounds)
        return w, [float(x) for x in np.asarray(metrics["loss"])]

    w_scan, scan_losses = scan_run(params)

    mesh = Mesh(np.array(devs[:4]).reshape(4, 1), ("data", "model"))
    gather = jax.jit(lambda r, p: ds.gather_batches(
        r, p, steps=cfg.local_steps, batch=cfg.batch_size))
    b0 = gather(jnp.int32(0), jnp.arange(K, dtype=jnp.int32))

    def specs(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), tree)

    step, arg_specs, in_sh = make_pod_round(
        cfg.algorithm, mesh, PodRoundSpec(config=cfg), loss_fn=loss_fn,
        p_specs=specs(params), batch_specs=specs(b0))
    t0 = time.perf_counter()
    pod_step = jax.jit(step, in_shardings=in_sh).lower(*arg_specs).compile()
    compile_s = time.perf_counter() - t0
    w = params
    state = get_algorithm(cfg.algorithm).init_state(cfg, params)
    pod_losses = []
    t1 = time.perf_counter()
    for r in range(cfg.rounds):
        args = jax.device_put(
            (w, state, gather(jnp.int32(r), schedule[r]), schedule[r],
             jnp.int32(r)), in_sh)
        w, state, losses = pod_step(*args)
        pod_losses.append(float(jnp.mean(losses[:, -1])))
    run_s = time.perf_counter() - t1
    return (w, pod_losses, w_scan, scan_losses, scan_run,
            pod_step.as_text(), compile_s, run_s)


def four_chips(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.sharding.hlo_analysis import integer_allreduces

    devs = jax.devices()
    _check(len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}")
    cnn_loss, _, params, ds = make_task(seed)
    cfg = smoke_config(seed, shared_noise=True, int_mask_agg=True,
                       clients_per_round=8)
    leaves = jax.tree_util.tree_leaves

    def max_dw(a, b):
        return max(float(jnp.max(jnp.abs(x - y)))
                   for x, y in zip(leaves(a), leaves(b)))

    # exact: identical client updates, so identical masks, counts and
    # weights unless the wire, the all-reduce or the apply is wrong
    w_pod, _, w_scan, _, _, hlo, _, _ = pod_vs_scan(
        linear_probe_loss(params, seed), cfg, params, ds, devs)
    n_diff = sum(int(np.sum(np.asarray(x) != np.asarray(y)))
                 for x, y in zip(leaves(w_pod), leaves(w_scan)))
    moved = sum(int(np.sum(np.asarray(x) != np.asarray(p)))
                for x, p in zip(leaves(w_scan), leaves(params)))
    n = sum(x.size for x in leaves(params))
    print(f"[pod] linear probe loss, {cfg.rounds} rounds: weights differing "
          f"from the scan engine's: {n_diff} of {n} ({moved} moved from "
          f"the initial weights)")
    _check(moved > n // 10, "linear probe: too few weights moved to test "
                            "the mask counts")
    _check(n_diff == 0, "linear probe: the pod round's weights differ from "
                        "the scan engine's")
    dtype = "s8"            # K=8 counts fit int8
    ars = [shape for shape, group in integer_allreduces(hlo, dtype)
           if group == 4]
    print(f"[pod] compiled: {dtype} count all-reduce over 4 chips: {ars}")
    _check(ars, f"no {dtype} count all-reduce over the 4 chips in the "
                "compiled pod program")

    # the CNN: chaotic training, so a band, next to the scan engine's own
    # spread under a 1-ulp change of the initial weights
    (w_pod, pod_losses, w_scan, scan_losses, scan_run, _, compile_s,
     run_s) = pod_vs_scan(cnn_loss, cfg, params, ds, devs)
    _, ulp_losses = scan_run(jax.tree_util.tree_map(
        lambda x: jnp.nextafter(x, jnp.inf), params))
    dl = max(abs(a - b) for a, b in zip(pod_losses, scan_losses))
    dl_ulp = max(abs(a - b) for a, b in zip(ulp_losses, scan_losses))
    print(f"[pod] cnn smoke timing, not a benchmark: compile "
          f"{compile_s:.2f} s, {cfg.rounds} rounds {run_s:.2f} s")
    print(f"[pod] cnn vs scan on one chip: max |d w| "
          f"{max_dw(w_pod, w_scan):.3g}, max |d loss| {dl:.3g} (<= "
          f"{LOSS_ATOL}; scan vs itself from 1-ulp-shifted weights "
          f"{dl_ulp:.3g}); pod loss {_fmt(pod_losses)} scan "
          f"{_fmt(scan_losses)}")
    _check(all(map(math.isfinite, pod_losses)), "pod: non-finite loss")
    _check(dl <= LOSS_ATOL,
           f"pod per-round loss differs from the scan engine's by {dl} > "
           f"{LOSS_ATOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        jax = _setup_jax()
        devs = jax.devices()
        print(f"device: platform={devs[0].platform} "
              f"kind={devs[0].device_kind} count={len(devs)}")
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeError as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
