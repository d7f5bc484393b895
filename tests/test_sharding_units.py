"""Unit tests: HLO analyzer, sharding rules, roofline math (no big mesh)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config, list_archs, shape_applicable
from repro.sharding import hlo_analysis
from repro.sharding.roofline import active_params, model_flops

HLO_SAMPLE = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%c0, %x)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  %ag = f32[8,16]{1,0} all-gather(%x), replica_groups=[4,2]<=[8], dimensions={1}
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


class TestHLOAnalysis:
    def test_while_trip_multiplied(self):
        cost = hlo_analysis.analyze(HLO_SAMPLE)
        # dot: 2*8*8*8 = 1024 flops × 5 trips
        assert cost.flops == 1024 * 5

    def test_collective_ring_bytes(self):
        cost = hlo_analysis.analyze(HLO_SAMPLE)
        # all-gather out 8*16*4 bytes × (g-1)/g with g=2
        assert cost.by_collective["all-gather"] == pytest.approx(
            8 * 16 * 4 * 0.5)

    def test_shape_bytes(self):
        assert hlo_analysis.shape_bytes("f32[2,3]{1,0}") == 24
        assert hlo_analysis.shape_bytes("(s32[], bf16[4,4]{1,0})") == 4 + 32
        assert hlo_analysis.shape_bytes("pred[7]") == 7

    def test_known_trip_count_attr(self):
        txt = HLO_SAMPLE.replace(
            "body=%body",
            'body=%body, backend_config={"known_trip_count":{"n":"7"}}')
        cost = hlo_analysis.analyze(txt)
        assert cost.flops == 1024 * 7


class TestRooflineMath:
    def test_active_params_moe(self):
        cfg = get_config("qwen3-moe-235b-a22b")
        from repro.models.registry import count_params
        total = count_params(cfg)
        act = active_params(cfg, total)
        assert act < total * 0.15          # a22b of 235b ≈ 9%
        assert act > total * 0.05

    def test_model_flops_kinds(self):
        cfg = get_config("llama3.2-1b")
        from repro.models.registry import count_params
        total = count_params(cfg)
        tr = model_flops(cfg, INPUT_SHAPES["train_4k"], total)
        pf = model_flops(cfg, INPUT_SHAPES["prefill_32k"], total)
        dc = model_flops(cfg, INPUT_SHAPES["decode_32k"], total)
        assert tr == pytest.approx(6 * total * 256 * 4096)
        assert pf == pytest.approx(2 * total * 32 * 32768)
        assert dc == pytest.approx(2 * total * 128)

    def test_skip_matrix(self):
        """Exactly the 3 sub-quadratic archs run long_500k."""
        runs = [a for a in list_archs()
                if shape_applicable(get_config(a),
                                    INPUT_SHAPES["long_500k"])[0]]
        assert sorted(runs) == ["h2o-danube-1.8b", "rwkv6-3b", "zamba2-1.2b"]


class TestShardingRules:
    def test_param_specs_divisible(self):
        """Every sharded dim divides the mesh axis for every arch."""
        from repro.models.registry import param_specs
        from repro.sharding.rules import param_shardings
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        for arch in list_archs():
            cfg = get_config(arch)
            specs = param_specs(cfg)
            shard = param_shardings(specs, mesh, num_layers=cfg.num_layers,
                                    encoder_layers=cfg.encoder_layers,
                                    zero=True)
            # NamedSharding construction already validates mesh axes; check
            # leaf count parity
            assert len(jax.tree_util.tree_leaves(shard)) == len(
                jax.tree_util.tree_leaves(specs))


def test_meshes_mark_every_axis_auto():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    assert make_host_mesh().axis_types == (AxisType.Auto,) * 2
