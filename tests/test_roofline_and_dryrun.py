"""Roofline machinery: the while-body-once cost_analysis calibration, the
loop-aware collective parser, the analytic cost model, and a real (small
mesh) lower+compile of train/serve steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES_BY_NAME, ShapeConfig
from repro.configs.registry import get_config
from repro.launch import costmodel as cm
from repro.launch import roofline as rf
from repro.launch.mesh import make_test_mesh
from repro.sharding.rules import ShardingCtx, get_profile


class TestCostAnalysisCalibration:
    def test_xla_counts_while_bodies_once(self):
        """The measured fact that justifies the analytic model: scan trip
        count does not change cost_analysis flops."""

        def make(n):
            def f(w, x):
                def body(c, _):
                    return jnp.tanh(c @ w), None

                c, _ = jax.lax.scan(body, x, None, length=n)
                return c.sum()

            return f

        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        flops = []
        for n in (2, 8):
            ca = jax.jit(make(n)).lower(w, x).compile().cost_analysis()
            ca = ca[0] if isinstance(ca, list) else ca
            flops.append(ca["flops"])
        assert flops[0] == flops[1]  # the undercount this framework corrects


SAMPLE_HLO = """\
HloModule test

%inner_body (arg: (s32[], f32[128,128])) -> (s32[], f32[128,128]) {
  %ar = f32[128,128]{1,0} all-reduce(%x), channel_id=1, replica_groups=[16,16]<=[256], to_apply=%add
  ROOT %t = (s32[], f32[128,128]) tuple(%i, %ar)
}

%inner_cond (arg: (s32[], f32[128,128])) -> pred[] {
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%outer_body (arg: (s32[], f32[128,128])) -> (s32[], f32[128,128]) {
  %w = (s32[], f32[128,128]) while(%arg), condition=%inner_cond, body=%inner_body
  ROOT %t2 = (s32[], f32[128,128]) tuple(%j, %gte)
}

%outer_cond (arg: (s32[], f32[128,128])) -> pred[] {
  %c2 = s32[] constant(3)
  ROOT %lt2 = pred[] compare(%j, %c2), direction=LT
}

ENTRY %main (p: f32[128,128]) -> f32[128,128] {
  %ag = f32[256,128]{1,0} all-gather(%p), channel_id=2, replica_groups=[128,2]<=[256], dimensions={0}
  %w0 = (s32[], f32[128,128]) while(%init), condition=%outer_cond, body=%outer_body
  ROOT %r = f32[128,128] get-tuple-element(%w0), index=1
}
"""


class TestCollectiveParser:
    def test_nested_loop_multipliers(self):
        comps, entry = rf._split_computations(SAMPLE_HLO)
        assert entry == "main"
        mult = rf._comp_multipliers(comps, entry)
        assert mult["outer_body"] == 3.0
        assert mult["inner_body"] == 15.0  # 3 * 5

    def test_byte_accounting(self):
        stats = rf.parse_collectives(SAMPLE_HLO, 256)
        # all-gather at entry: result 256*128*4 bytes * (2-1)/2, once
        ag = 256 * 128 * 4 * (1 / 2)
        # all-reduce inside nested loops: result 128*128*4, group 16,
        # 2*(n-1)/n ring factor, 15 executions
        ar = 2 * 128 * 128 * 4 * (15 / 16) * 15
        assert stats.op_bytes["all-gather"] == pytest.approx(ag)
        assert stats.op_bytes["all-reduce"] == pytest.approx(ar)
        assert stats.unattributed_comps == 0

    def test_group_size_forms(self):
        assert rf._group_size("replica_groups=[16,16]<=[16,16]T(1,0)", 256) == 16
        assert rf._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}", 256) == 4
        assert rf._group_size("replica_groups={}", 256) == 256


class TestAnalyticCostModel:
    def test_train_flops_close_to_6nd(self):
        """For a dense arch, cell_flops should be ~ (4/3)*6*N*D with full
        remat (8*N*D) within attention/unembed slack."""
        cfg = get_config("qwen3-8b")
        shape = SHAPES_BY_NAME["train_4k"]
        from repro.launch.dryrun import active_param_count

        n = active_param_count(cfg)
        got = cm.cell_flops(cfg, shape)
        lower = 0.8 * 8 * n * shape.tokens  # remat factor 4 => 8ND
        upper = 2.0 * 8 * n * shape.tokens
        assert lower < got < upper, (got, 8 * n * shape.tokens)

    def test_decode_dominated_by_cache_bytes(self):
        cfg = get_config("qwen2.5-14b")
        shape = SHAPES_BY_NAME["decode_32k"]
        b = cm.cell_bytes_per_device(cfg, shape, 256)
        state = cm._decode_state_bytes(cfg, shape) / 256
        assert state * 2 < b < state * 2 + 4e9  # cache read+write dominates

    def test_moe_flops_scale_with_topk_not_experts(self):
        cfg = get_config("deepseek-v2-236b")
        shape = SHAPES_BY_NAME["train_4k"]
        fl = cm.cell_flops(cfg, shape)
        from dataclasses import replace

        cfg_bigger_pool = replace(cfg, moe=replace(cfg.moe, n_experts=320))
        fl2 = cm.cell_flops(cfg_bigger_pool, shape)
        assert abs(fl2 - fl) / fl < 0.02  # router-only delta

    def test_all_cells_have_positive_terms(self):
        from repro.configs.base import ALL_SHAPES, shape_applicable
        from repro.configs.registry import list_archs

        for arch in list_archs():
            cfg = get_config(arch)
            for shape in ALL_SHAPES:
                ok, _ = shape_applicable(cfg, shape)
                if not ok:
                    continue
                c = cm.analytic_cost(cfg, shape, 256)
                assert c.flops_per_device > 0, (arch, shape.name)
                assert c.bytes_per_device > 0, (arch, shape.name)


class TestSmallMeshLowering:
    """The dry-run machinery on a 1x1 mesh with reduced configs: proves the
    train/serve jits lower+compile with shardings end-to-end in-tests."""

    @pytest.mark.parametrize("arch", ["llama3.2-3b", "deepseek-v2-236b", "recurrentgemma-2b"])
    def test_train_step_lowers(self, arch):
        from repro.train.step import make_train_setup, make_train_step

        cfg = get_config(arch).reduced()
        shape = ShapeConfig("t", "train", seq_len=32, global_batch=2)
        mesh = make_test_mesh(1, 1)
        sctx = ShardingCtx(mesh=mesh, profile=get_profile("dp_tp"))
        with mesh:
            setup = make_train_setup(cfg, shape, sctx)
            fn = make_train_step(setup)
            compiled = (
                jax.jit(fn, donate_argnums=(0,))
                .lower(setup.abstract_state(), setup.abstract_batch())
                .compile()
            )
        assert compiled.memory_analysis().temp_size_in_bytes >= 0

    @pytest.mark.parametrize("arch", ["qwen3-8b", "xlstm-1.3b"])
    def test_decode_step_lowers(self, arch):
        from repro.serve.step import (
            decode_state_specs,
            make_decode_step,
            serve_param_specs,
            token_specs,
        )

        cfg = get_config(arch).reduced()
        shape = ShapeConfig("d", "decode", seq_len=64, global_batch=2)
        mesh = make_test_mesh(1, 1)
        sctx = ShardingCtx(mesh=mesh, profile=get_profile("decode_default"))
        with mesh:
            fn = make_decode_step(cfg, sctx)
            compiled = (
                jax.jit(fn, donate_argnums=(1,))
                .lower(
                    serve_param_specs(cfg, sctx),
                    decode_state_specs(cfg, shape, sctx),
                    token_specs(shape, sctx),
                )
                .compile()
            )
        assert compiled is not None


class TestCompileCache:
    def test_fixed_checkout_path_unless_env_set(self, tmp_path, monkeypatch):
        from repro.launch.compile_cache import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
            assert enable_compile_cache(tmp_path) == "/elsewhere"
            assert jax.config.jax_compilation_cache_dir == before
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            want = str(tmp_path.resolve() / ".jax_cache")
            assert enable_compile_cache(tmp_path) == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
