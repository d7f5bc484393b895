"""Per-architecture smoke tests: reduced variant (≤2 layers, d≤512,
≤4 experts) runs one train step and one decode step on CPU; asserts output
shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.models.registry import build_model, input_specs, count_params

KEY = jax.random.key(0)
B, S = 2, 32


def _concrete_batch(cfg, B, S, key):
    """A small real train batch for the reduced config."""
    kt, ke = jax.random.split(key)
    tok = jax.random.randint(kt, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok}
    if cfg.arch_type == "vlm":
        P = cfg.frontend_tokens
        batch["frontend_embeds"] = 0.02 * jax.random.normal(
            ke, (B, P, cfg.d_model), cfg.dtype)
        batch["positions3"] = jnp.broadcast_to(
            jnp.arange(S + P)[None, None], (3, B, S + P))
    elif cfg.arch_type == "audio":
        batch["frontend_embeds"] = 0.02 * jax.random.normal(
            ke, (B, S, cfg.d_model), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", list_archs())
class TestArchSmoke:
    def test_train_step(self, arch):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(KEY)
        batch = _concrete_batch(cfg, B, S, KEY)

        @jax.jit
        def step(p, b):
            loss, grads = jax.value_and_grad(model.loss_fn)(p, b)
            p2 = jax.tree_util.tree_map(lambda a, g: a - 0.1 * g, p, grads)
            return loss, p2

        loss, p2 = step(params, batch)
        assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
        # one leaf actually moved
        moved = any(
            not np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(p2)))
        assert moved, f"{arch}: no parameter moved"

    def test_decode_step(self, arch):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(KEY)
        cache = model.init_cache(B, 64, jnp.float32)
        tok = jnp.zeros((B, 1), jnp.int32)

        @jax.jit
        def step(p, c, t):
            return model.decode_step(p, c, t)

        logits, cache = step(params, cache, tok)
        logits, cache = step(params, cache, tok)  # second step reuses cache
        assert logits.shape == (B, 1, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all(), f"{arch}: NaN logits"

    def test_input_specs_no_allocation(self, arch):
        cfg = get_config(arch)  # FULL config — specs only, no arrays
        for shape in INPUT_SHAPES.values():
            specs = input_specs(cfg, shape)
            for leaf in jax.tree_util.tree_leaves(specs):
                assert hasattr(leaf, "shape")

    def test_param_count_plausible(self, arch):
        cfg = get_config(arch)
        n = count_params(cfg)
        # every assigned arch is 0.3B..300B params
        assert 3e8 < n < 3e11, f"{arch}: {n/1e9:.2f}B params"


def test_optimization_barrier_grad_and_vmap():
    """grad + vmap through optimization_barrier, which the transformer
    uses to pin layer weights (identity semantics)."""
    from repro.models.transformer import optimization_barrier

    def f(x):
        return jnp.sum(optimization_barrier(x * 2.0))

    x = jnp.arange(3.0)
    assert jax.grad(f)(x) == pytest.approx([2.0, 2.0, 2.0])
    y = jax.vmap(lambda v: optimization_barrier(v) + 1.0)(x)
    assert y == pytest.approx([1.0, 2.0, 3.0])
