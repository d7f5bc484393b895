"""The kernels' byte counters against the arguments of the program's
own ``pallas_call`` at cell 1's and cell 2's sizes (traced, not run)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from benchutil import BENCH

CELLS = {"cell1": ("cnn4-fmnist", "fedmrn-k10"),
         "cell2": ("cnn4-cifar10", "fedmrn-shared-k50")}


def _load(cell):
    cfg_name, tr_name = CELLS[cell]
    cfg = json.loads((BENCH / "configs" / f"{cfg_name}.json").read_text())
    tr = json.loads((BENCH / "traffic" / f"{tr_name}.json").read_text())
    shapes = jax.eval_shape(lambda: harness.family(cfg).init_params(cfg, 0))
    return cfg, tr, [x.shape for x in jax.tree_util.tree_leaves(shapes)]


def _pallas_calls(jaxpr, name):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and name in str(
                eqn.params.get("name_and_src_info", eqn.params.get("name"))):
            out.append(eqn)
        for sub in _subjaxprs(eqn.params.values()):
            out.extend(_pallas_calls(sub, name))
    return out


def _subjaxprs(values):
    for v in values:
        if isinstance(v, (list, tuple)):
            yield from _subjaxprs(v)
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v


def _array_bytes(avals, floor=64):
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in avals
               if int(np.prod(a.shape)) * a.dtype.itemsize > floor)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_uplink_bytes_match_the_call(cell):
    from repro.core.masking import tree_mask_uplink
    cfg, tr, shapes = _load(cell)
    K = tr["clients_per_round"]
    u = [jax.ShapeDtypeStruct((K,) + s, jnp.float32) for s in shapes]
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), K))
    jaxpr = jax.make_jaxpr(lambda u, n, k, w: tree_mask_uplink(
        u, n, k, w, wsum_values=not tr["shared_noise"], backend="pallas"))(
        u, u, keys, jax.ShapeDtypeStruct((K,), jnp.float32))
    (eqn,) = _pallas_calls(jaxpr.jaxpr, "uplink_fused")
    ins = [v.aval for v in eqn.invars]
    outs = [v.aval for v in eqn.outvars]
    kern = harness.kernel("uplink_fused")
    rows, cols = kern.padded_shape(K, sum(int(np.prod(s)) for s in shapes))
    assert ins[0].shape == (rows, cols)
    # every array argument once, plus the weights' block re-read for
    # each further column block
    rereads = 4 * rows * (cols // kern.BLOCK_C - 1)
    want = _array_bytes(ins, floor=0) + _array_bytes(outs, floor=0) + rereads
    assert kern.call_cost(K, sum(int(np.prod(s)) for s in shapes))[1] == want


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_psm_bytes_match_the_calls(cell):
    from repro.kernels.psm_mask.ops import psm_ste
    cfg, tr, shapes = _load(cell)
    K, S = tr["clients_per_round"], tr["local_steps"]
    kern = harness.kernel("psm_fused")
    total = 0
    for s in shapes:
        x = jax.ShapeDtypeStruct((K,) + s, jnp.float32)
        keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), K))
        jaxpr = jax.make_jaxpr(jax.vmap(
            lambda u, n, k: psm_ste(u, n, k, jnp.float32(0.5))))(x, x, keys)
        (eqn,) = _pallas_calls(jaxpr.jaxpr, "psm_fused")
        got = (_array_bytes([v.aval for v in eqn.invars])
               + _array_bytes([v.aval for v in eqn.outvars]))
        assert kern.call_cost(int(np.prod(s)), K)[1] == got
        total += got
    calls, _, nbytes = kern.round_cost([int(np.prod(s)) for s in shapes],
                                       K, S)
    assert (calls, nbytes) == (S * len(shapes), S * total)
