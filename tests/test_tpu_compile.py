"""The main-path Pallas kernels compile for a TPU v5e chip.

Each test lowers and compiles for one chip of a described (not attached)
``v5e:2x2`` topology with the installed TPU compiler, so a kernel that
interpret mode accepts but Mosaic refuses (unaligned blocks, reductions
over unsigned integers, scalars outside SMEM, reshapes the layout pass
rejects, too much VMEM) fails here instead of on the chip.  Nothing runs.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and the workers
that run the suite all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import use_backend
from repro.fed import FLConfig
from repro.fed.engine import make_round_body
from repro.fed.sharded import PodRoundSpec, make_pod_round
from repro.kernels.bitpack import ops as bitpack_ops
from repro.kernels.bitpack.bitpack import pack_bits_pallas, unpack_bits_pallas
from repro.kernels.mask_uplink import ops as uplink_ops
from repro.kernels.mask_uplink.mask_uplink import (
    BLOCK_C, BLOCK_R, unpack_counts_apply_pallas, unpack_counts_pallas,
    uplink_fused)
from repro.kernels.psm_mask import ops as psm_ops
from repro.kernels.psm_mask.psm_mask import psm_fused
from repro.models.cnn import cnn_init, cnn_loss
from repro.sharding.hlo_analysis import integer_allreduces, mosaic_kernels

# the chip smoke's model: the paper's 4-conv CNN at FMNIST shape, K=10
SMOKE_P = 261_066
SMOKE_K = 10
# a larger size that still fits one 16 GB chip
LARGE_P = 2 ** 22
LARGE_K = 64
SERVER_LARGE_K = 256

f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def _assert_kernel(hlo: str, name: str):
    assert name in mosaic_kernels(hlo), (name, mosaic_kernels(hlo))


@pytest.mark.parametrize("P", [SMOKE_P, LARGE_P])
def test_psm_fused_compiles(one_chip, P):
    rows = _up(P, 128) // 128
    t = jax.ShapeDtypeStruct((rows, 128), f32, sharding=one_chip)
    prog = jax.ShapeDtypeStruct((), f32, sharding=one_chip)
    hlo = _compile(lambda u, n, a, b, p: psm_fused(u, n, a, b, p,
                                                   interpret=False),
                   t, t, t, t, prog)
    _assert_kernel(hlo, "psm_fused")


@pytest.mark.parametrize("K, P", [(SMOKE_K, SMOKE_P), (LARGE_K, LARGE_P)])
@pytest.mark.parametrize("mode", ["binary", "signed", "prob"])
@pytest.mark.parametrize("gated", [False, True])
def test_uplink_fused_compiles(one_chip, K, P, mode, gated):
    t = jax.ShapeDtypeStruct((_up(K, BLOCK_R), _up(P, BLOCK_C)), f32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((t.shape[0],), f32, sharding=one_chip)
    want_uhat = mode != "prob"
    if gated:
        prog = jax.ShapeDtypeStruct((), f32, sharding=one_chip)
        hlo = _compile(
            lambda u, n, r, rp, p, w: uplink_fused(
                u, n, r, rp, p, w, mode=mode, want_uhat=want_uhat,
                interpret=False),
            t, t, t, t, prog, w)
    else:
        hlo = _compile(
            lambda u, n, r, w: uplink_fused(
                u, n, r, None, None, w, mode=mode, want_uhat=want_uhat,
                interpret=False),
            t, t, t, w)
    _assert_kernel(hlo, "uplink_fused")


@pytest.mark.parametrize("K, P", [(SMOKE_K, SMOKE_P),
                                  (SERVER_LARGE_K, LARGE_P)])
def test_server_count_kernels_compile(one_chip, K, P):
    W = _up(_up(P, 32) // 32, 128)
    words = jax.ShapeDtypeStruct((_up(K, BLOCK_R), W), u32,
                                 sharding=one_chip)
    flat = jax.ShapeDtypeStruct((1, W * 32), f32, sharding=one_chip)
    scalars = jax.ShapeDtypeStruct((3,), f32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda x: unpack_counts_pallas(x, interpret=False), words),
        "unpack_counts")
    _assert_kernel(_compile(
        lambda x, n, b, s: unpack_counts_apply_pallas(x, n, b, s,
                                                      interpret=False),
        words, flat, flat, scalars), "unpack_counts_apply")


@pytest.mark.parametrize("K, P", [(SMOKE_K, SMOKE_P), (LARGE_K, LARGE_P)])
def test_bitpack_compiles(one_chip, K, P):
    bits = jax.ShapeDtypeStruct((K, _up(P, 32)), jnp.int8,
                                sharding=one_chip)
    words = jax.ShapeDtypeStruct((K, _up(P, 32) // 32), u32,
                                 sharding=one_chip)
    _assert_kernel(_compile(
        lambda b: pack_bits_pallas(b, interpret=False), bits), "pack_bits")
    _assert_kernel(_compile(
        lambda x: unpack_bits_pallas(x, interpret=False), words),
        "unpack_bits")


@pytest.mark.parametrize("extra, kernels", [
    ({}, {"psm_fused", "uplink_fused"}),
    ({"shared_noise": True, "int_mask_agg": True},
     {"psm_fused", "uplink_fused"}),
    ({"shared_noise": True, "int_mask_agg": True, "error_feedback": True},
     {"psm_fused", "pack_bits", "unpack_counts_apply"}),
])
def test_scan_round_body_compiles(one_chip, monkeypatch, extra, kernels):
    """The FedMRN round body of the smoke model, as the scan engine calls
    it: the kernels under vmap over clients and grad through the STE.
    The fused uplink already sums the counts, so the server update reads
    the wire words (``unpack_counts_apply``) only on the staged path that
    error feedback takes."""
    for mod in (psm_ops, uplink_ops, bitpack_ops):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    params = _smoke_params()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p_specs = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype),
                                     params)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == SMOKE_P
    cfg = FLConfig(algorithm="fedmrn", num_clients=100,
                   clients_per_round=SMOKE_K, local_steps=10,
                   batch_size=64, **extra)
    K, S, B = cfg.clients_per_round, cfg.local_steps, cfg.batch_size
    with use_backend("pallas"):
        round_fn, state = make_round_body(cnn_loss, cfg, p_specs)
        s_specs = jax.tree_util.tree_map(lambda x: spec(x.shape, x.dtype),
                                         state)
        hlo = _compile(round_fn, p_specs, s_specs,
                       (spec((K, S, B, 28, 28, 1), f32),
                        spec((K, S, B), i32)),
                       spec((K,), i32), spec((), i32), spec((K,), f32))
    assert kernels <= mosaic_kernels(hlo), mosaic_kernels(hlo)


def _smoke_params():
    return cnn_init(jax.random.key(0), n_classes=10,
                    channels=(64, 64, 128, 128), in_ch=1, hw=28)


def test_pod_round_compiles_with_int8_count_allreduce(topo):
    """The smoke CNN's pod round (fedmrn, shared noise, K=8) over the four
    chips of the described host: the mask counts cross the client axis
    as an int8 all-reduce, and no Mosaic kernel is left in the program
    (XLA cannot partition one)."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    params = jax.eval_shape(_smoke_params)
    K, S, B = 8, 10, 64
    batches = (jax.ShapeDtypeStruct((K, S, B, 28, 28, 1), f32),
               jax.ShapeDtypeStruct((K, S, B), i32))
    cfg = FLConfig(algorithm="fedmrn", num_clients=100, clients_per_round=K,
                   local_steps=S, batch_size=B, shared_noise=True)
    step, arg_specs, in_sh = make_pod_round(
        cfg.algorithm, mesh, PodRoundSpec(config=cfg), loss_fn=cnn_loss,
        p_specs=params, batch_specs=batches)
    specs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        arg_specs, in_sh,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    hlo = jax.jit(step).lower(*specs).compile().as_text()
    groups = [g for _, g in integer_allreduces(hlo, "s8")]
    assert 4 in groups, integer_allreduces(hlo, "s8")
    assert not mosaic_kernels(hlo)
