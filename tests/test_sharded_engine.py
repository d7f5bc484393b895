"""Multi-device engine tier (ISSUE 4): the registry-driven pod round
reproduces the scan engine's trajectories for every algorithm family, and
``sharding="devices"`` sweeps reproduce the vmapped sweep per seed.

Run standalone (``make test-sharded`` / the CI ``test-multidevice`` job)
this file forces 8 fake CPU devices so the client mesh axis and the seed
mesh genuinely partition; inside the full tier-1 suite jax is already
initialised with 1 device and every test adapts (the programs are the
same — only the mesh extents shrink).
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
# ^ only effective when this module is the first jax import of the process

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.data import (make_federated_dataset, make_image_task,
                        make_partition)
from repro.fed import (ALGORITHMS, Algorithm, Experiment, ExperimentSpec,
                       FLConfig, make_client_schedule, register_algorithm,
                       sweep_device_count)
from repro.fed.algorithms import get_algorithm
from repro.fed.engine import make_experiment_program
from repro.fed.sharded import (PodRoundSpec, client_axis_of, make_pod_round,
                               pod_batch_specs)
from repro.models.cnn import mlp_apply, mlp_init, mlp_loss

KEY = jax.random.key(0)
NDEV = jax.device_count()


def _pod_mesh():
    """A (data, model) mesh over everything available: (4, 2) on the 8
    fake CI devices, (1, 1) degenerate inside the single-device suite."""
    auto = {"axis_types": (AxisType.Auto,) * 2}
    if NDEV >= 8:
        return jax.make_mesh((4, 2), ("data", "model"), **auto)
    if NDEV >= 2:
        return jax.make_mesh((NDEV, 1), ("data", "model"), **auto)
    return jax.make_mesh((1, 1), ("data", "model"), **auto)


def _setup(algorithm, rounds=3, **cfg_kw):
    task = make_image_task(0, n=400, hw=8, n_classes=4, noise=0.5)
    parts = make_partition("iid", 0, task.y, 8)
    params = mlp_init(KEY, d_in=64, d_hidden=32, n_classes=4)
    cfg = FLConfig(algorithm=algorithm, num_clients=8, clients_per_round=8,
                   rounds=rounds, local_steps=2, batch_size=16, lr=0.1,
                   noise_alpha=3e-2, **cfg_kw)
    ds = make_federated_dataset(task.x, task.y, parts, batch_seed=7,
                                x_test=task.x[:128], y_test=task.y[:128])
    return mlp_loss, params, ds, cfg


def _specs_of(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), tree)


def _pod_program(cfg, loss_fn, params, ds, rounds_fused=1,
                 client_weights=None, int_mask_agg=None):
    """(jitted pod step, batch gather fn, initial state) on _pod_mesh."""
    mesh = _pod_mesh()
    gather = jax.jit(lambda r, p: ds.gather_batches(
        r, p, steps=cfg.local_steps, batch=cfg.batch_size))
    b0 = gather(jnp.int32(0), jnp.arange(cfg.clients_per_round,
                                         dtype=jnp.int32))
    step, arg_specs, in_sh = make_pod_round(
        cfg.algorithm, mesh, PodRoundSpec(config=cfg, rounds=rounds_fused),
        loss_fn=loss_fn, p_specs=_specs_of(params),
        batch_specs=_specs_of(b0), client_weights=client_weights,
        int_mask_agg=int_mask_agg)
    algo = get_algorithm(cfg.algorithm)
    return (jax.jit(step, in_shardings=in_sh), gather,
            algo.init_state(cfg, params))


def _assert_trees_close(a, b, atol):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol)


# ---------------------------------------------------------------------------
# the acceptance criterion: pod round body ≡ scan engine, every family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm, overrides", [
    ("fedmrn", {}),
    ("fedmrn", {"error_feedback": True}),
    ("fedmrn", {"shared_noise": True}),   # the pod default for mask families
    ("fedavg", {}),
    ("fedpm", {}),
])
def test_pod_round_matches_scan_engine(algorithm, overrides):
    """R host-driven pod rounds (registry body under the client×data
    mesh, per-round gathered batches + schedule) reproduce the scan
    engine's fused experiment program to 1e-6 — same body, same keys."""
    loss_fn, params, ds, cfg = _setup(algorithm, **overrides)
    schedule = jnp.asarray(make_client_schedule(cfg), jnp.int32)

    run_chunk, state0, metrics0 = make_experiment_program(
        loss_fn, cfg, params, ds)
    w_ref, _, metrics = run_chunk(params, state0, metrics0, jnp.int32(0),
                                  schedule, n_rounds=cfg.rounds)

    pod_step, gather, state = _pod_program(cfg, loss_fn, params, ds)
    w = params
    pod_losses = []
    for r in range(cfg.rounds):
        batches = gather(jnp.int32(r), schedule[r])
        w, state, losses = pod_step(w, state, batches, schedule[r],
                                    jnp.int32(r))
        assert losses.shape == (cfg.clients_per_round, cfg.local_steps)
        pod_losses.append(float(jnp.mean(losses[:, -1])))

    _assert_trees_close(w_ref, w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(metrics["loss"]), pod_losses,
                               atol=1e-5)


def test_pod_client_weights_match_scan_engine():
    """Non-uniform client weights gather as weights_all[picked] on the pod
    path exactly as in the scan engine's chunk body."""
    loss_fn, params, ds, cfg = _setup("fedmrn")
    cw = tuple(float(i + 1) for i in range(cfg.num_clients))
    schedule = jnp.asarray(make_client_schedule(cfg), jnp.int32)

    run_chunk, state0, metrics0 = make_experiment_program(
        loss_fn, cfg, params, ds, client_weights=cw)
    w_ref, _, _ = run_chunk(params, state0, metrics0, jnp.int32(0),
                            schedule, n_rounds=cfg.rounds)

    pod_step, gather, state = _pod_program(cfg, loss_fn, params, ds,
                                           client_weights=cw)
    w = params
    for r in range(cfg.rounds):
        w, state, _ = pod_step(w, state, gather(jnp.int32(r), schedule[r]),
                               schedule[r], jnp.int32(r))
    _assert_trees_close(w_ref, w, atol=1e-6)

    with pytest.raises(ValueError, match="client_weights"):
        _pod_program(cfg, loss_fn, params, ds, client_weights=(1.0, 2.0))


def test_pod_algorithm_instance_resolution():
    """An Algorithm instance auto-registers; a name collision with a
    different plugin raises instead of silently running the builtin."""
    loss_fn, params, ds, cfg = _setup("fedmrn", rounds=1)
    mesh = _pod_mesh()
    b_specs = _specs_of(ds.gather_batches(
        jnp.int32(0), jnp.arange(cfg.clients_per_round, dtype=jnp.int32),
        steps=cfg.local_steps, batch=cfg.batch_size))
    imposter = dataclasses.replace(get_algorithm("fedavg"), name="fedmrn")
    with pytest.raises(ValueError, match="different plugin"):
        make_pod_round(imposter, mesh, PodRoundSpec(config=cfg),
                       loss_fn=loss_fn, p_specs=_specs_of(params),
                       batch_specs=b_specs)
    fresh = dataclasses.replace(get_algorithm("fedavg"), name="pod_inline")
    try:
        make_pod_round(fresh, mesh, PodRoundSpec(config=cfg),
                       loss_fn=loss_fn, p_specs=_specs_of(params),
                       batch_specs=b_specs)
        assert "pod_inline" in ALGORITHMS
    finally:
        ALGORITHMS.pop("pod_inline", None)


def test_pod_multiround_scan_matches_host_loop():
    """PodRoundSpec(rounds=R) — the fused in-program scan — equals R
    single-round pod dispatches fed the same batch stream (the probe's
    reuse semantics), cross-round state included."""
    loss_fn, params, ds, cfg = _setup("fedmrn", rounds=3,
                                      error_feedback=True)
    picked = jnp.arange(cfg.clients_per_round, dtype=jnp.int32)

    fused_step, gather, state_f = _pod_program(cfg, loss_fn, params, ds,
                                               rounds_fused=cfg.rounds)
    batches = gather(jnp.int32(0), picked)
    w_f, state_f, losses_f = fused_step(params, state_f, batches, picked,
                                        jnp.int32(0))
    assert losses_f.shape == (cfg.rounds, cfg.clients_per_round,
                              cfg.local_steps)

    single_step, _, state = _pod_program(cfg, loss_fn, params, ds)
    w = params
    for r in range(cfg.rounds):
        w, state, losses = single_step(w, state, batches, picked,
                                       jnp.int32(r))
        np.testing.assert_allclose(np.asarray(losses_f[r]),
                                   np.asarray(losses), atol=1e-6)
    _assert_trees_close(w_f, w, atol=1e-6)
    _assert_trees_close(state_f, state, atol=1e-6)


def test_pod_runs_custom_plugin():
    """ANY registered Algorithm lowers on the pod path — no engine fork."""

    def make_body(loss_fn, cfg, params):
        def round_fn(seed, w, state, batches, picked, round_idx, weights):
            def per_client(b, cid):
                from repro.core import sgd_local_update
                return sgd_local_update(loss_fn, w, b, lr=cfg.lr)

            updates, losses = jax.vmap(per_client)(batches, picked)
            wn = weights / jnp.sum(weights)
            agg = jax.tree_util.tree_map(
                lambda x: jnp.tensordot(wn, x, axes=1), updates)
            new_w = jax.tree_util.tree_map(lambda p, a: p + 0.5 * a, w, agg)
            return new_w, state, losses

        return round_fn

    from repro.core.comm import CommRecord
    from repro.fed import DenseCodec, template_of
    register_algorithm(Algorithm(
        name="toy_pod", make_round_body=make_body,
        codec=lambda cfg, p: DenseCodec(
            template_of(p), name="toy_pod",
            record=CommRecord("toy_pod", 0, 1, 1, 1))))
    try:
        loss_fn, params, ds, cfg = _setup("toy_pod", rounds=1)
        pod_step, gather, state = _pod_program(cfg, loss_fn, params, ds)
        picked = jnp.arange(cfg.clients_per_round, dtype=jnp.int32)
        batches = gather(jnp.int32(0), picked)
        w, state, losses = pod_step(params, state, batches, picked,
                                    jnp.int32(0))
        assert np.isfinite(np.asarray(losses)).all()
        changed = any(
            not np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(w)))
        assert changed
    finally:
        ALGORITHMS.pop("toy_pod", None)


# ---------------------------------------------------------------------------
# the codec wire format on the pod path (ISSUE 5 acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm, overrides", [
    ("fedmrn", {"shared_noise": True}),
    ("fedpm", {}),
])
def test_pod_int_mask_agg_matches_f32_reference(algorithm, overrides):
    """The ⌈log2(K+1)⌉-bit integer mask-count aggregate (the pod default
    for count-aggregatable MaskCodec families) reproduces the f32
    reference aggregation — same trajectories over R rounds."""
    loss_fn, params, ds, cfg = _setup(algorithm, **overrides)
    schedule = jnp.asarray(make_client_schedule(cfg), jnp.int32)
    int_step, gather, state_i = _pod_program(cfg, loss_fn, params, ds)
    f32_step, _, state_f = _pod_program(cfg, loss_fn, params, ds,
                                        int_mask_agg=False)
    w_i = w_f = params
    for r in range(cfg.rounds):
        batches = gather(jnp.int32(r), schedule[r])
        w_i, state_i, _ = int_step(w_i, state_i, batches, schedule[r],
                                   jnp.int32(r))
        w_f, state_f, _ = f32_step(w_f, state_f, batches, schedule[r],
                                   jnp.int32(r))
    _assert_trees_close(w_i, w_f, atol=1e-6)


def test_pod_mask_allreduce_lowers_to_integer_dtype():
    """Acceptance probe: with int_mask_agg (the pod default for fedmrn +
    shared noise) the cross-client collective in the compiled HLO is an
    INTEGER all-reduce, and no model-sized f32 all-reduce remains."""
    import re

    mesh = _pod_mesh()
    D = mesh.shape[client_axis_of(mesh)]
    if D == 1:
        pytest.skip("degenerate 1-device client axis emits no collective")
    loss_fn, params, ds, cfg = _setup("fedmrn", rounds=1,
                                      shared_noise=True)
    from repro.fed.codecs import min_count_dtype
    import numpy as _np
    want = _np.dtype(min_count_dtype(cfg.clients_per_round))
    hlo_dtype = {"int8": "s8", "int16": "s16", "int32": "s32"}[want.name]

    gather = jax.jit(lambda r, p: ds.gather_batches(
        r, p, steps=cfg.local_steps, batch=cfg.batch_size))
    b0 = gather(jnp.int32(0), jnp.arange(cfg.clients_per_round,
                                         dtype=jnp.int32))
    step, arg_specs, in_sh = make_pod_round(
        cfg.algorithm, mesh, PodRoundSpec(config=cfg),
        loss_fn=loss_fn, p_specs=_specs_of(params),
        batch_specs=_specs_of(b0))
    hlo = jax.jit(step, in_shardings=in_sh).lower(
        *arg_specs).compile().as_text()
    ars = re.findall(r"= (\w+)\[([0-9,]*)\][^=\n]*all-reduce", hlo)
    assert any(dt == hlo_dtype for dt, _ in ars), (
        f"no {hlo_dtype} all-reduce in HLO: {ars}")
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))

    def elems(dims):
        out = 1
        for d in dims.split(","):
            out *= int(d) if d else 1
        return out

    big_f32 = [(dt, dims) for dt, dims in ars
               if dt == "f32" and elems(dims) >= n_params]
    assert not big_f32, f"model-sized f32 all-reduce survived: {big_f32}"


def test_pod_int_mask_agg_rejects_nonuniform_weights():
    loss_fn, params, ds, cfg = _setup("fedmrn", shared_noise=True)
    cw = tuple(float(i + 1) for i in range(cfg.num_clients))
    with pytest.raises(ValueError, match="uniform"):
        _pod_program(cfg, loss_fn, params, ds, client_weights=cw,
                     int_mask_agg=True)


def test_pod_rejects_indivisible_client_axis():
    mesh = _pod_mesh()
    D = mesh.shape[client_axis_of(mesh)]
    if D == 1:
        pytest.skip("degenerate 1-device mesh divides everything")
    loss_fn, params, ds, cfg = _setup("fedmrn")
    cfg = dataclasses.replace(cfg, clients_per_round=D + 1)
    with pytest.raises(ValueError, match="divisible"):
        make_pod_round(cfg.algorithm, mesh, PodRoundSpec(config=cfg),
                       loss_fn=loss_fn, p_specs=_specs_of(params),
                       batch_specs=_specs_of(ds.gather_batches(
                           jnp.int32(0),
                           jnp.arange(D + 1, dtype=jnp.int32),
                           steps=cfg.local_steps, batch=cfg.batch_size)))


def test_pod_batch_specs_split():
    specs = pod_batch_specs(
        {"x": jax.ShapeDtypeStruct((256, 7), jnp.float32)}, 16, 2)
    assert specs["x"].shape == (16, 2, 8, 7)
    tiny = pod_batch_specs(
        {"x": jax.ShapeDtypeStruct((4,), jnp.float32)}, 16, 2)
    assert tiny["x"].shape == (16, 2, 1)      # floor clamps at 1


# ---------------------------------------------------------------------------
# sharding="devices": the seed axis over a device mesh via shard_map
# ---------------------------------------------------------------------------

def _experiment(algorithm="fedmrn", rounds=3, **cfg_kw):
    loss_fn, params, ds, cfg = _setup(algorithm, rounds, **cfg_kw)
    cfg = dataclasses.replace(cfg, clients_per_round=4)
    return Experiment(ExperimentSpec(
        loss_fn=loss_fn, params=params, data=ds, config=cfg,
        eval_apply=mlp_apply))


def test_sharded_sweep_matches_vmapped_per_seed():
    """The shard_map'd sweep is trajectory-identical to the vmapped sweep
    (and hence to S independent runs) for every seed — EF state too."""
    exp = _experiment(rounds=3, error_feedback=True)
    n_seeds = 8
    vm = exp.sweep(seeds=n_seeds)
    sh = exp.sweep(seeds=n_seeds, sharding="devices")
    assert sh.vmapped and sh.devices == sweep_device_count(n_seeds)
    if NDEV >= 8:
        assert sh.devices == 8                 # genuinely spread in CI
    for a, b in zip(vm.runs, sh.runs):
        np.testing.assert_allclose(a.acc, b.acc, atol=1e-6)
        np.testing.assert_allclose(a.local_loss, b.local_loss, atol=1e-5)
        np.testing.assert_array_equal(a.schedule, b.schedule)
    solo = exp.run(seed=sh.seeds[1])
    np.testing.assert_allclose(sh.runs[1].acc, solo.acc, atol=1e-6)


def test_sharded_sweep_chunked_and_algorithms():
    """Chunked dispatch + a second family through the same sharded path."""
    exp = _experiment("fedpm", rounds=4)
    sh = exp.sweep(seeds=4, sharding="devices", chunk=3)   # 3 + 1 trailing
    vm = exp.sweep(seeds=4, chunk=3)
    assert all(r.num_dispatches == 2 for r in sh.runs)
    for a, b in zip(vm.runs, sh.runs):
        np.testing.assert_allclose(a.acc, b.acc, atol=1e-6)
        np.testing.assert_allclose(a.local_loss, b.local_loss, atol=1e-5)


def test_sweep_device_count_picks_largest_divisor():
    assert sweep_device_count(8, max_devices=8) == 8
    assert sweep_device_count(8, max_devices=4) == 4
    assert sweep_device_count(6, max_devices=4) == 3
    assert sweep_device_count(7, max_devices=4) == 1
    assert sweep_device_count(3, max_devices=8) == 3
    with pytest.raises(ValueError, match="seed"):
        sweep_device_count(0)


def test_sharded_sweep_argument_validation():
    exp = _experiment(rounds=2)
    with pytest.raises(ValueError, match="divide"):
        exp.sweep(seeds=3, sharding="devices", devices=2)
    with pytest.raises(ValueError, match="vmapped"):
        exp.sweep(seeds=2, sharding="devices", vmapped=False)
    with pytest.raises(ValueError, match="sharding"):
        exp.sweep(seeds=2, sharding="pods")
    with pytest.raises(ValueError, match="devices"):
        exp.sweep(seeds=2, devices=2)         # devices without sharding=


def test_pod_round_refuses_pallas_backend():
    """XLA cannot partition a Mosaic kernel over the client axis, so the
    pod program lowers the jnp kernels; an explicit pallas backend is
    refused instead of failing to compile on a TPU mesh."""
    loss_fn, params, ds, cfg = _setup("fedmrn", backend="pallas")
    with pytest.raises(ValueError, match="Mosaic"):
        _pod_program(cfg, loss_fn, params, ds)
