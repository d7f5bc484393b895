"""The 4-conv CNN family: data, weights, the program adapter, FLOPs, and
the plain reference.

A configuration file (``configs/cnn4-*.json``) gives the sizes; this
module turns them into

- the dataset: class prototypes plus pixel noise, made on the device in
  one jitted call and held as uint8, as image datasets are distributed;
- the initial weights, on the device in one jitted call from the seed;
- the program's loss and eval apply (``repro.models.cnn``), which take
  uint8 images and normalise them to f32;
- the forward FLOPs of one sample, counting convolutions and the dense
  head from the shapes;
- the plain reference: the same network in straightforward
  ``jax.numpy``, written from the paper's description and importing
  nothing of the program.  ``quant`` rounds every convolution and dense
  input to a lower precision (the control).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def layer_shapes(cfg):
    """``[(hw_in, c_in, c_out)]`` per conv, and the head's input width."""
    hw, c_in, out = cfg["hw"], cfg["in_channels"], []
    for c_out in cfg["channels"]:
        out.append((hw, c_in, c_out))
        hw, c_in = hw // 2, c_out
    return out, hw * hw * c_in


def forward_flops(cfg) -> int:
    """Multiply-adds x 2 of one sample's forward pass: the SAME-padded
    3x3 convolutions at full resolution and the dense head."""
    convs, feat = layer_shapes(cfg)
    flops = sum(2 * hw * hw * 9 * ci * co for hw, ci, co in convs)
    return flops + 2 * feat * cfg["n_classes"]


def num_params(cfg) -> int:
    convs, feat = layer_shapes(cfg)
    return (sum(9 * ci * co + 3 * co for _, ci, co in convs)
            + feat * cfg["n_classes"] + cfg["n_classes"])


# ---------------------------------------------------------------------------
# data and weights, on the device
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "hw", "ch", "classes", "noise",
                                   "scale"))
def _images(key, *, n, hw, ch, classes, noise, scale):
    kp, ky, kx = jax.random.split(key, 3)
    protos = jax.random.normal(kp, (classes, hw, hw, ch), jnp.float32)
    # low-pass the prototypes so convolutions have local structure
    p = jnp.pad(protos, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="edge")
    protos = sum(p[:, i:i + hw, j:j + hw, :]
                 for i in range(3) for j in range(3)) / 9.0
    y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
    x = protos[y] + noise * jax.random.normal(kx, (n, hw, hw, ch))
    x = jnp.clip(jnp.round(x * scale + 128.0), 0.0, 255.0)
    return x.astype(jnp.uint8), y


def make_data(cfg):
    """``(x_train, y_train, x_test, y_test)`` on the device."""
    n_train, n_test = cfg["n_train"], cfg["n_test"]
    x, y = _images(jax.random.key(cfg["data_seed"]), n=n_train + n_test,
                   hw=cfg["hw"], ch=cfg["in_channels"],
                   classes=cfg["n_classes"], noise=cfg["pixel_noise"],
                   scale=cfg["pixel_scale"])
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def normalize(cfg, x):
    return (x.astype(jnp.float32) - 128.0) / cfg["pixel_scale"]


def init_params(cfg, seed: int):
    """The initial weights from the seed, in one jitted call: He-scaled
    normal kernels, zero biases, unit norm scales."""
    convs, feat = layer_shapes(cfg)

    @jax.jit
    def init(key):
        ks = jax.random.split(key, len(convs) + 1)
        blocks = []
        for k, (_, ci, co) in zip(ks, convs):
            blocks.append({
                "w": jax.random.normal(k, (3, 3, ci, co)) * (9 * ci) ** -0.5,
                "b": jnp.zeros((co,)), "gamma": jnp.ones((co,)),
                "beta": jnp.zeros((co,))})
        return {"convs": blocks,
                "fc_w": jax.random.normal(ks[-1], (feat, cfg["n_classes"]))
                * feat ** -0.5,
                "fc_b": jnp.zeros((cfg["n_classes"],))}

    return init(jax.random.key(seed))


# ---------------------------------------------------------------------------
# the system under test: the program's model behind a uint8 input
# ---------------------------------------------------------------------------

def program_fns(cfg):
    """``(loss_fn, eval_apply)`` of the program's CNN on uint8 images."""
    from repro.models.cnn import cnn_apply, cnn_loss

    def loss_fn(p, batch):
        x, y = batch
        return cnn_loss(p, (normalize(cfg, x), y))

    def eval_apply(p, x):
        return cnn_apply(p, normalize(cfg, x))

    return loss_fn, eval_apply


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def fp8(x):
    """Round to float8 e4m3 and back: the control's precision."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def ref_apply(cfg, p, x_u8, quant=None):
    """Logits of the 4-conv network.  Convolutions and the dense head run
    at JAX's default precision, as the configuration states; ``quant``
    rounds their inputs first."""
    q = quant or (lambda a: a)
    x = normalize(cfg, x_u8)
    g, eps = cfg["norm_groups"], cfg["norm_eps"]
    for blk in p["convs"]:
        x = jax.lax.conv_general_dilated(
            q(x), q(blk["w"]), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + blk["b"]
        B, H, W, C = x.shape
        z = x.reshape(B, H, W, g, C // g)
        mu = z.mean(axis=(1, 2, 4), keepdims=True)
        var = z.var(axis=(1, 2, 4), keepdims=True)
        z = (z - mu) * jax.lax.rsqrt(var + eps)
        x = jax.nn.relu(z.reshape(B, H, W, C) * blk["gamma"] + blk["beta"])
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    return q(x) @ q(p["fc_w"]) + p["fc_b"]


def ref_loss(cfg, p, batch, quant=None):
    """Mean softmax cross-entropy over the batch."""
    x, y = batch
    logp = jax.nn.log_softmax(ref_apply(cfg, p, x, quant))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def ref_predictor(cfg, quant=None):
    """The reference's jitted ``(params, uint8 images) -> labels``."""
    return jax.jit(lambda p, x: jnp.argmax(ref_apply(cfg, p, x, quant), -1))


def ref_accuracy(predict, x_u8, y, p, block: int = 1000):
    """Test accuracy of ``predict``, in blocks of rows so that it fits."""
    hits = 0
    for i in range(0, x_u8.shape[0], block):
        hits += int(jnp.sum(predict(p, x_u8[i:i + block]) == y[i:i + block]))
    return hits / x_u8.shape[0]


def to_host(tree):
    return jax.tree_util.tree_map(np.asarray, tree)
