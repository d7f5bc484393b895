"""The configurations' counts from their shapes: forward FLOPs per
sample and parameters, against the numbers worked out by hand and the
program's own initialiser."""
import json

import jax
import pytest

import harness
from benchutil import BENCH

# (forward FLOPs per sample, parameters), worked out layer by layer:
# FMNIST 0.903 M + 14.45 M + 7.225 M + 2.654 M + 0.003 M
COUNTS = {"cnn4-fmnist": (25_235_968, 261_066),
          "cnn4-cifar10": (36_579_328, 266_058)}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_from_shapes(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fam = harness.family(cfg)
    flops, params = COUNTS[name]
    assert fam.forward_flops(cfg) == flops == cfg["forward_flops_per_sample"]
    assert fam.num_params(cfg) == params == cfg["num_params"]
    shapes = jax.eval_shape(lambda: fam.init_params(cfg, 0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == params


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_init_matches_the_programs_tree(name):
    """The weights the harness makes have the program's pytree layout."""
    from repro.models.cnn import cnn_init
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fam = harness.family(cfg)
    ours = jax.eval_shape(lambda: fam.init_params(cfg, 0))
    theirs = jax.eval_shape(lambda: cnn_init(
        jax.random.key(0), n_classes=cfg["n_classes"],
        channels=tuple(cfg["channels"]), in_ch=cfg["in_channels"],
        hw=cfg["hw"]))
    assert (jax.tree_util.tree_structure(ours)
            == jax.tree_util.tree_structure(theirs))
    assert ([x.shape for x in jax.tree_util.tree_leaves(ours)]
            == [x.shape for x in jax.tree_util.tree_leaves(theirs)])
