"""Device-resident batched evaluation — the scan engine's eval layer.

The host-loop engines call a Python ``eval_fn(params) -> float`` every
``eval_every`` rounds: a blocking device→host read per eval.  The
multi-round experiment program instead folds eval in on-device: an
``eval_program`` is a pure jax function ``params -> accuracy`` built once
over a device-resident test set, traceable inside ``lax.cond`` /
``lax.scan``.

The test set is evaluated in fixed-size minibatches via ``lax.scan`` (not
one giant batch) so eval memory is bounded by ``batch_size`` activations
regardless of test-set size.  The remainder batch is wrap-padded and the
pad positions masked out of the correct-count, so the returned accuracy
equals the full-batch mean exactly (0/1 counts are exact in f32 up to
2^24 examples).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any


def make_eval_program(
    apply_fn: Callable[[Pytree, jax.Array], jax.Array],
    x: jax.Array,
    y: jax.Array,
    *,
    batch_size: int = 256,
) -> Callable[[Pytree], jax.Array]:
    """Build ``params -> accuracy`` over a device-resident test set.

    ``apply_fn(params, x_batch) -> (B, n_classes) logits``.  The returned
    program is pure and jit/scan/cond-safe; accuracy is the exact mean of
    argmax-correctness over the ``len(y)`` true examples.  Its device
    instructions carry the ``fed.eval`` name scope.
    """
    n = int(y.shape[0])
    if n == 0:
        raise ValueError("empty test set")
    bs = min(batch_size, n)
    nb = -(-n // bs)                     # ceil
    # wrap-pad to a rectangular (nb, bs, ...) stack; valid-mask kills pads
    take = jnp.asarray(np.resize(np.arange(n), nb * bs), jnp.int32)
    xb = jnp.asarray(x)[take].reshape((nb, bs) + tuple(x.shape[1:]))
    yb = jnp.asarray(y)[take].reshape(nb, bs)
    valid = (jnp.arange(nb * bs) < n).reshape(nb, bs)

    def program(params: Pytree) -> jax.Array:
        def body(correct, inp):
            xi, yi, vi = inp
            pred = jnp.argmax(apply_fn(params, xi), axis=-1)
            hits = ((pred == yi) & vi).astype(jnp.float32)
            return correct + jnp.sum(hits), None

        with jax.named_scope("fed.eval"):
            correct, _ = jax.lax.scan(body, jnp.float32(0.0),
                                      (xb, yb, valid))
            return correct / n

    return program


def make_negloss_eval_program(
    loss_fn: Callable[[Pytree, Any], jax.Array],
    batch: Any,
) -> Callable[[Pytree], jax.Array]:
    """Build ``params -> -loss(params, batch)`` over a fixed eval batch.

    The generative-task counterpart of :func:`make_eval_program`: when
    there is no argmax accuracy to report (LM fine-tuning), the scan
    engine's eval slot takes negative loss on a held-out device-resident
    batch — pure, jit/scan/cond-safe, higher-is-better like accuracy.
    """
    batch = jax.tree_util.tree_map(jnp.asarray, batch)

    def program(params: Pytree) -> jax.Array:
        with jax.named_scope("fed.eval"):
            return -jnp.float32(loss_fn(params, batch))

    return program
