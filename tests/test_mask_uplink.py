"""Fused mask-uplink kernel (ISSUE 6).

Asserts the fused ``mask_uplink`` pass ≡ the staged ``tree_psm-style
sample → tree_pack_stacked → tree_unpack_counts`` composition (packed
words, counts, aggregates, STE gradients) at lengths NOT divisible by
128 or 32, that ref ≡ pallas-interpret, that the fused program
materializes neither the mask tree nor an unpacked bit tensor outside
the kernel, and that fedmrn/fedpm codec trajectories are unchanged at a
fixed seed.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    # hypothesis is a pinned requirement (requirements.txt); CI sets
    # REPRO_REQUIRE_HYPOTHESIS=1 so a missing install fails instead of
    # silently skipping the property tests.
    if os.environ.get("REPRO_REQUIRE_HYPOTHESIS", "") not in ("", "0"):
        raise
    HAVE_HYPOTHESIS = False

from repro.core import mix_add, use_backend
from repro.core.masking import (tree_bernoulli_stacked, tree_mask_uplink,
                                tree_sample_mask_stacked)
from repro.core.packing import (tree_pack_stacked, tree_unpack_counts,
                                tree_unpack_counts_apply)
from repro.kernels.mask_uplink import ops as mops
from repro.kernels.psm_mask.ops import _psm_ste_core

KEY = jax.random.key(0)

# two-leaf tree with sizes divisible by neither 128 nor 32
LEAF_SHAPES = {"a": (47,), "b": (13, 7)}


def _stack_tree(key, K, scale=0.01):
    ks = jax.random.split(key, len(LEAF_SHAPES))
    return {name: scale * jax.random.normal(k, (K,) + shp)
            for k, (name, shp) in zip(ks, LEAF_SHAPES.items())}


def _template():
    return {name: jax.ShapeDtypeStruct(shp, jnp.float32)
            for name, shp in LEAF_SHAPES.items()}


def _flat(tree, K=None):
    leaves = jax.tree_util.tree_leaves(tree)
    if K is None:
        return np.concatenate([np.asarray(l).reshape(-1) for l in leaves])
    return np.concatenate(
        [np.asarray(l).reshape(K, -1) for l in leaves], axis=1)


# ---------------------------------------------------------------------------
# property: fused ≡ staged composition, ref ≡ pallas-interpret
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), K=st.integers(1, 9),
           mode=st.sampled_from(["binary", "signed"]))
    @example(seed=0, K=3, mode="signed")   # cancellation in the f32 wsum
    def test_fused_equals_staged_pipeline(seed, K, mode):
        key = jax.random.key(seed)
        ku, kn, kk = jax.random.split(key, 3)
        u = _stack_tree(ku, K)
        n = _stack_tree(kn, K)
        keys = jax.random.split(kk, K)
        w = jnp.linspace(0.5, 1.5, K)

        # the staged three-kernel pipeline on the ref backend
        masks = tree_sample_mask_stacked(u, n, keys, mode=mode)
        words_staged = tree_pack_stacked(masks, mode=mode, backend="ref")
        counts_staged = tree_unpack_counts(
            words_staged, _template(), mode=mode, dtype=jnp.int32,
            backend="ref")

        up_ref = tree_mask_uplink(u, n, keys, w, mode=mode, backend="ref")
        up_pal = tree_mask_uplink(u, n, keys, w, mode=mode,
                                  backend="pallas")

        # packed wire rows: all three bitwise equal
        np.testing.assert_array_equal(np.asarray(words_staged),
                                      np.asarray(up_ref.words))
        np.testing.assert_array_equal(np.asarray(up_ref.words),
                                      np.asarray(up_pal.words))
        # counts: exact integers on every route
        np.testing.assert_array_equal(_flat(counts_staged),
                                      np.asarray(up_ref.counts))
        np.testing.assert_array_equal(np.asarray(up_ref.counts),
                                      np.asarray(up_pal.counts))
        # Σ_k w_k n_k⊙m_k: fused vs staged masked-noise tensordot.  The
        # three routes sum K f32 terms in different orders; where the
        # terms cancel the result is near zero, so bound the difference
        # by the summation error, 2·K·eps·Σ_k |w_k n_k|, not by rtol
        hat = jax.tree_util.tree_map(
            lambda nl, ml: nl * ml.astype(nl.dtype), n, masks)
        wsum_staged = jnp.tensordot(w, jnp.asarray(_flat(hat, K)), axes=1)
        tol = (2 * K * np.finfo(np.float32).eps
               * np.abs(np.asarray(w)[:, None] * _flat(n, K)).sum(axis=0))
        for got, want in ((up_ref.wsum, wsum_staged),
                          (up_pal.wsum, up_ref.wsum)):
            err = np.abs(np.asarray(got) - np.asarray(want))
            assert np.all(err <= tol), (err - tol).max()

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), K=st.integers(1, 6))
    def test_fused_prob_mode_equals_bernoulli_draw(seed, K):
        """FedPM: the fused prob-mode draw is the per-leaf Bernoulli."""
        key = jax.random.key(seed)
        kp, kk = jax.random.split(key)
        probs = jax.tree_util.tree_map(jax.nn.sigmoid, _stack_tree(kp, K))
        keys = jax.random.split(kk, K)
        masks = tree_bernoulli_stacked(probs, keys)
        words_staged = tree_pack_stacked(masks, backend="ref")
        for backend in ("ref", "pallas"):
            up = tree_mask_uplink(probs, None, keys, jnp.ones((K,)),
                                  probs=True, wsum_values=False,
                                  backend=backend)
            np.testing.assert_array_equal(np.asarray(words_staged),
                                          np.asarray(up.words))
            np.testing.assert_array_equal(
                np.asarray(up.counts),
                _flat(masks, K).astype(np.int32).sum(axis=0))


@pytest.mark.parametrize("mode", ["binary", "signed"])
@pytest.mark.parametrize("gated", [True, False])
def test_ste_gradients_bitwise(mode, gated):
    """Fused STE ≡ the psm_mask STE rule, cotangent for cotangent."""
    K, P = 5, 333
    ku, kn, ks, kp = jax.random.split(KEY, 4)
    u = 0.01 * jax.random.normal(ku, (K, P))
    n = 0.01 * jax.random.normal(kn, (K, P))
    r_sm = jax.random.uniform(ks, (K, P))
    r_pm = jax.random.uniform(kp, (K, P)) if gated else None
    prog = 0.6 if gated else None
    cot = jnp.sin(jnp.arange(P, dtype=jnp.float32))

    def f_fused(uu):
        out = mops.mask_uplink_ste(uu, n, r_sm, r_pm, prog, mode=mode)
        return jnp.sum(out.uhat * cot)

    g_fused = jax.grad(f_fused)(u)
    if gated:
        def f_staged(uu):
            uh = _psm_ste_core(uu, n, r_sm, r_pm, jnp.float32(prog),
                               mode)
            return jnp.sum(uh * cot)
        g_staged = jax.grad(f_staged)(u)
        np.testing.assert_array_equal(np.asarray(g_fused),
                                      np.asarray(g_staged))
    else:   # progress ≡ 1: pure straight-through, ∂û/∂u = 1
        np.testing.assert_array_equal(
            np.asarray(g_fused),
            np.broadcast_to(np.asarray(cot), (K, P)))


# ---------------------------------------------------------------------------
# the acceptance criterion: the fused program materializes neither the
# mask tree nor the unpacked bit tensor outside the kernel
# ---------------------------------------------------------------------------

def _intermediate_avals(jaxpr, out):
    """All eqn-output avals, recursing into call jaxprs but NOT into the
    pallas_call kernel body (whose VMEM-staged refs are the point)."""
    for eqn in jaxpr.eqns:
        if "pallas" in eqn.primitive.name:
            for v in eqn.outvars:
                out.append(v.aval)
            continue
        for v in eqn.outvars:
            out.append(v.aval)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", None)
            if inner is not None:
                _intermediate_avals(inner, out)
    return out


def _mask_sized_bit_avals(fn, *args):
    """Avals that look like a materialized mask/bit tensor: a bool/int8
    buffer at least as large as the (K, P) mask stack."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    K, P = args[0].shape
    avals = _intermediate_avals(jaxpr.jaxpr, [])
    return [a for a in avals
            if getattr(a, "dtype", None) in (jnp.bool_, jnp.int8)
            and np.prod(a.shape) >= K * P]


def test_fused_path_materializes_no_mask_or_bit_tensor():
    K, P = 8, 4096
    ku, kn, ks = jax.random.split(KEY, 3)
    u = jax.random.normal(ku, (K, P))
    n = jax.random.normal(kn, (K, P))
    r = jax.random.uniform(ks, (K, P))
    w = jnp.ones((K,))

    def fused(u, n, r, w):
        return mops.mask_uplink_fused(u, n, r, None, None, w,
                                      use_pallas=True)

    def staged(u, n, r, w):
        m = (r < jnp.clip(u / n, 0, 1)).astype(jnp.int8)   # mask tree
        from repro.core.packing import pack_rows, unpack_rows
        words = pack_rows(m, backend="ref")
        bits = unpack_rows(words, P, backend="ref")        # 32× words
        return words, jnp.sum(bits, axis=0, dtype=jnp.int32)

    assert _mask_sized_bit_avals(fused, u, n, r, w) == []
    # positive control: the staged pipeline DOES materialize them
    assert len(_mask_sized_bit_avals(staged, u, n, r, w)) >= 2


# ---------------------------------------------------------------------------
# server side: counts + fused apply
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["binary", "signed"])
def test_counts_and_apply_parity(mode):
    K = 6
    ku, kn, kk, kw = jax.random.split(KEY, 4)
    u = _stack_tree(ku, K)
    n = _stack_tree(kn, K)
    keys = jax.random.split(kk, K)
    masks = tree_sample_mask_stacked(u, n, keys, mode=mode)
    words = tree_pack_stacked(masks, mode=mode, backend="ref")

    with use_backend("ref"):
        c_ref = tree_unpack_counts(words, _template(), mode=mode,
                                   dtype=jnp.int32)
    with use_backend("pallas"):
        c_pal = tree_unpack_counts(words, _template(), mode=mode,
                                   dtype=jnp.int32)
    np.testing.assert_array_equal(_flat(c_ref), _flat(c_pal))

    noise = {k: 0.01 * jax.random.normal(jax.random.fold_in(kn, i), s)
             for i, (k, s) in enumerate(LEAF_SHAPES.items())}
    params = {k: jax.random.normal(jax.random.fold_in(kw, i), s)
              for i, (k, s) in enumerate(LEAF_SHAPES.items())}
    scale = 0.25

    def composed(words):
        with use_backend("ref"):
            counts = tree_unpack_counts(words, _template(), mode=mode,
                                        dtype=jnp.int32)
        agg = jax.tree_util.tree_map(
            lambda nl, cl: nl * (scale * cl.astype(jnp.float32)),
            noise, counts)
        return jax.tree_util.tree_map(mix_add, params, agg)

    def fused(words, backend):
        return tree_unpack_counts_apply(words, noise, params, scale,
                                        mode=mode, backend=backend)

    want = jax.jit(composed)(words)
    for backend in ("ref", "pallas"):
        got = jax.jit(lambda w_, b=backend: fused(w_, b))(words)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-12),
            want, got)
    # ref and pallas-interpret agree bitwise under jit
    g_ref = jax.jit(lambda w_: fused(w_, "ref"))(words)
    g_pal = jax.jit(lambda w_: fused(w_, "pallas"))(words)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        g_ref, g_pal)


# ---------------------------------------------------------------------------
# codec level: uplink_stacked ≡ encode_stacked + aggregate
# ---------------------------------------------------------------------------

def _codec(mode="binary", shared=False, count_dtype=None, noise=True,
           normalize=True):
    from repro.core import NoiseConfig
    from repro.fed.codecs import MaskCodec
    return MaskCodec(
        _template(), name="t", mode=mode,
        noise=NoiseConfig(dist="uniform", alpha=1e-2) if noise else None,
        shared_noise=shared, normalize=normalize, count_dtype=count_dtype)


@pytest.mark.parametrize("mode", ["binary", "signed"])
@pytest.mark.parametrize("variant", ["per_client", "shared", "shared_int",
                                     "fedpm"])
def test_codec_uplink_stacked_matches_legacy(mode, variant):
    K = 4
    ku, kk, ks = jax.random.split(KEY, 3)
    u = _stack_tree(ku, K)
    mask_keys = jax.random.split(kk, K)
    if variant == "shared_int":
        codec = _codec(mode, shared=True, count_dtype=jnp.int8)
        weights = jnp.ones((K,))
    elif variant == "shared":
        codec = _codec(mode, shared=True)
        weights = jnp.linspace(0.5, 1.5, K)
    elif variant == "fedpm":
        if mode == "signed":
            pytest.skip("fedpm is binary-only")
        codec = _codec(noise=False, normalize=False)
        weights = jnp.ones((K,))
    else:
        codec = _codec(mode)
        weights = jnp.linspace(0.5, 1.5, K)

    probs = variant == "fedpm"
    if probs:
        scores = jax.tree_util.tree_map(jax.nn.sigmoid, u)
        seed_keys = None
    else:
        scores = u
        one = jax.random.fold_in(ks, 0)
        seed_keys = (jnp.broadcast_to(one, (K,)) if variant != "per_client"
                     else jax.random.split(ks, K))

    def run(backend):
        with use_backend(backend):
            return codec.uplink_stacked(scores, seed_keys, mask_keys,
                                        weights, probs=probs)

    msg_ref, agg_ref = jax.jit(lambda: run("ref"))()
    msg_pal, agg_pal = jax.jit(lambda: run("pallas"))()

    # legacy composition on the ref route
    legacy_msg, legacy_agg = None, None
    with use_backend("ref"):
        if probs:
            masks = tree_bernoulli_stacked(scores, mask_keys)
            legacy_msg = codec.encode_stacked({"mask": masks})
        else:
            from repro.core import gen_noise
            noise = jax.vmap(
                lambda k: gen_noise(k, codec.template, codec.noise)
            )(seed_keys)
            masks = tree_sample_mask_stacked(scores, noise, mask_keys,
                                             mode=mode)
            legacy_msg = codec.encode_stacked(
                {"mask": masks, "seed": seed_keys})
        legacy_agg = codec.aggregate(legacy_msg, weights)

    np.testing.assert_array_equal(
        np.asarray(legacy_msg.buffers["words"]),
        np.asarray(msg_ref.buffers["words"]))
    np.testing.assert_array_equal(
        np.asarray(msg_ref.buffers["words"]),
        np.asarray(msg_pal.buffers["words"]))
    for a, b, exact in ((legacy_agg, agg_ref, True),
                        (agg_ref, agg_pal, False)):
        jax.tree_util.tree_map(
            lambda x, y: (np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)) if exact else
                np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), rtol=2e-6, atol=1e-9)),
            a, b)


# ---------------------------------------------------------------------------
# end to end: fedmrn/fedpm trajectories, fused (pallas) vs staged (ref)
# ---------------------------------------------------------------------------

def _tiny_experiment(algorithm, **cfg_kw):
    from repro.data import (make_federated_dataset, make_image_task,
                            make_partition)
    from repro.fed import FLConfig, run_federated
    from repro.models.cnn import mlp_eval_program, mlp_init, mlp_loss
    task = make_image_task(0, n=320, hw=8, n_classes=4, noise=0.5)
    parts = make_partition("iid", 0, task.y, 6)
    params = mlp_init(KEY, d_in=64, d_hidden=16, n_classes=4)
    cfg = FLConfig(algorithm=algorithm, num_clients=6, clients_per_round=3,
                   rounds=3, local_steps=3, batch_size=16, lr=0.1,
                   noise_alpha=3e-2, **cfg_kw)
    ds = make_federated_dataset(task.x, task.y, parts, batch_seed=7)
    eval_prog = mlp_eval_program(jnp.asarray(task.x), jnp.asarray(task.y))
    return mlp_loss, params, ds, eval_prog, cfg


@pytest.mark.parametrize("algorithm,cfg_kw", [
    ("fedmrn", {}),
    ("fedmrns", {}),
    ("fedmrn", {"shared_noise": True, "int_mask_agg": True}),
    ("fedpm", {}),
])
def test_trajectory_fused_equals_staged(algorithm, cfg_kw):
    """Fixed-seed trajectories through MaskCodec: pallas (fused kernel)
    ≡ ref (the staged legacy composition)."""
    from repro.fed import run_federated
    loss_fn, params, ds, eval_prog, cfg = _tiny_experiment(
        algorithm, **cfg_kw)
    hist = {}
    for backend in ("ref", "pallas"):
        with use_backend(backend):
            hist[backend] = run_federated(
                loss_fn, params, ds, None, cfg, eval_program=eval_prog,
                engine="scan", chunk=3)
    np.testing.assert_allclose(hist["ref"]["acc"], hist["pallas"]["acc"],
                               atol=1e-6)
    np.testing.assert_allclose(hist["ref"]["local_loss"],
                               hist["pallas"]["local_loss"], atol=1e-5)


# ---------------------------------------------------------------------------
# compiled mode (real TPU only — auto-skipped elsewhere via the marker)
# ---------------------------------------------------------------------------

@pytest.mark.tpu
def test_compiled_kernel_matches_oracle():
    K, P = 8, 8192
    ku, kn, ks = jax.random.split(KEY, 3)
    u = 0.01 * jax.random.normal(ku, (K, P))
    n = 0.01 * jax.random.normal(kn, (K, P))
    r = jax.random.uniform(ks, (K, P))
    w = jnp.ones((K,))
    ref = mops.mask_uplink_fused(u, n, r, None, None, w, use_pallas=False)
    pal = mops.mask_uplink_fused(u, n, r, None, None, w, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(ref.words),
                                  np.asarray(pal.words))
    np.testing.assert_array_equal(np.asarray(ref.counts),
                                  np.asarray(pal.counts))
    np.testing.assert_allclose(np.asarray(ref.wsum), np.asarray(pal.wsum),
                               rtol=2e-6, atol=1e-12)
