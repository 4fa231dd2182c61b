"""Optimizer math, checkpoint store, data pipeline, training loop
(incl. kill -> resume), sharding rule resolution."""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.ckpt.store import CheckpointStore
from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.data.pipeline import DataConfig, SyntheticLM, make_batch_fn
from repro.sharding.rules import ShardingCtx, get_profile, pspec_for
from repro.train.optimizer import (
    AdamW,
    AdamWConfig,
    Schedule,
    clip_by_global_norm,
    q8_dequantize,
    q8_quantize,
)


class TestOptimizer:
    def test_adamw_matches_closed_form_step(self):
        cfg = AdamWConfig(
            schedule=Schedule(base_lr=0.1, warmup_steps=0, kind="const"),
            b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0, clip_norm=1e9,
        )
        opt = AdamW(cfg)
        p = {"w": jnp.array([1.0, -2.0])}
        g = {"w": jnp.array([0.5, 0.5])}
        st_ = opt.init(p)
        new_p, st2, _ = opt.update(g, st_, p)
        # closed form for step 1: m_hat = g, v_hat = g^2 -> update = g/(|g|+eps)
        expect = p["w"] - 0.1 * (g["w"] / (jnp.abs(g["w"]) + 1e-8))
        np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(expect), rtol=1e-5)
        assert int(st2["step"]) == 1

    def test_weight_decay_direction(self):
        cfg = AdamWConfig(
            schedule=Schedule(base_lr=0.1, warmup_steps=0, kind="const"),
            weight_decay=0.5, clip_norm=1e9,
        )
        opt = AdamW(cfg)
        p = {"w": jnp.array([10.0])}
        g = {"w": jnp.array([0.0])}
        new_p, _, _ = opt.update(g, opt.init(p), p)
        assert float(new_p["w"][0]) < 10.0  # decays toward zero

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        total = math.sqrt(sum(float(jnp.sum(x * x)) for x in jax.tree.leaves(clipped)))
        assert abs(total - 1.0) < 1e-5
        assert abs(float(norm) - math.sqrt(90 + 160)) < 1e-3

    def test_schedule_warmup_and_decay(self):
        s = Schedule(base_lr=1.0, warmup_steps=10, total_steps=100, min_ratio=0.1)
        assert float(s(jnp.asarray(0))) == 0.0
        assert abs(float(s(jnp.asarray(10))) - 1.0) < 1e-6
        assert float(s(jnp.asarray(100))) == pytest.approx(0.1, rel=1e-3)

    def test_int8_state_memory_and_training(self):
        cfg = AdamWConfig(
            schedule=Schedule(base_lr=0.05, warmup_steps=0, kind="const"),
            int8_moments=True, clip_norm=1e9, weight_decay=0.0,
        )
        opt = AdamW(cfg)
        p = {"w": jnp.array(np.random.RandomState(0).randn(256).astype(np.float32))}
        state = opt.init(p)
        assert state["m"]["w"]["codes"].dtype == jnp.int8
        # a few steps on a quadratic: loss must fall
        target = jnp.zeros(256)
        for _ in range(20):
            g = {"w": 2 * (p["w"] - target)}
            p, state, _ = opt.update(g, state, p)
        assert float(jnp.mean(p["w"] ** 2)) < 0.5

    @given(st.integers(0, 1000), st.floats(0.01, 100.0))
    @settings(max_examples=20, deadline=None)
    def test_q8_roundtrip_error_bound(self, seed, scale):
        x = jnp.asarray(np.random.RandomState(seed).randn(300) * scale, jnp.float32)
        err = jnp.abs(q8_dequantize(q8_quantize(x)) - x)
        # per-block bound: absmax/127 per element
        blocks = jnp.pad(x, (0, (-x.shape[0]) % 128)).reshape(-1, 128)
        bound = jnp.repeat(jnp.max(jnp.abs(blocks), axis=1) / 127.0, 128)[: x.shape[0]]
        assert bool(jnp.all(err <= bound * 1.01 + 1e-9))


class TestShardingRules:
    def test_divisibility_fallback(self):
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(1, 1)
        prof = get_profile("dp_tp")
        # size-1 mesh axes are never emitted into specs
        spec = pspec_for((24, 128), ("heads", "head_dim"), prof, mesh)
        assert spec == jax.sharding.PartitionSpec()

    def test_no_axis_reuse_within_tensor(self):
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(1, 1)
        prof = get_profile("fsdp_tp")
        spec = pspec_for((64, 64), ("embed", "embed"), prof, mesh)
        flat = [a for e in spec for a in ((e,) if isinstance(e, str) else (e or ()))]
        assert len(flat) == len(set(flat))

    def test_profiles_exist(self):
        for name in ("dp_tp", "dp_wide", "fsdp_tp", "fsdp_wide", "decode_default", "decode_big", "decode_long"):
            assert get_profile(name).rules


class TestCheckpointStore:
    def _state(self, x=0.0):
        return {
            "params": {"w": jnp.full((4, 4), 1.0 + x), "b": jnp.zeros(3)},
            "step": jnp.asarray(int(x), jnp.int32),
        }

    def test_roundtrip(self, tmp_path):
        store = CheckpointStore(tmp_path)
        store.save(5, self._state(5.0))
        step, restored = store.restore(self._state())
        assert step == 5
        assert float(restored["params"]["w"][0, 0]) == 6.0

    def test_keep_last_k(self, tmp_path):
        store = CheckpointStore(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            store.save(s, self._state(float(s)))
        assert store.all_steps() == [3, 4]
        assert store.latest_step() == 4

    def test_async_write(self, tmp_path):
        store = CheckpointStore(tmp_path)
        store.save(1, self._state(1.0), blocking=False)
        store.wait()
        assert store.latest_step() == 1

    def test_restore_with_target_sharding(self, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(1, 1)
        store = CheckpointStore(tmp_path)
        store.save(1, self._state(2.0))
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), self._state())
        _, restored = store.restore(self._state(), shardings=sh)
        assert restored["params"]["w"].sharding == NamedSharding(mesh, P())

    def test_tree_mismatch_rejected(self, tmp_path):
        store = CheckpointStore(tmp_path)
        store.save(1, self._state())
        from repro.core.exceptions import CheckpointError

        with pytest.raises(CheckpointError):
            store.restore({"params": {"other": jnp.zeros(3)}})


class TestDataPipeline:
    def test_determinism(self):
        src = SyntheticLM(DataConfig(seed=1, vocab_size=100))
        b1 = src.batch(3, 8, 16)
        b2 = src.batch(3, 8, 16)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_steps_differ(self):
        src = SyntheticLM(DataConfig(seed=1, vocab_size=100))
        assert not np.array_equal(src.batch(0, 8, 16)["tokens"], src.batch(1, 8, 16)["tokens"])

    def test_host_shards_disjoint_and_cover(self):
        src = SyntheticLM(DataConfig(seed=1, vocab_size=100))
        full = src.batch(0, 8, 16)
        h0 = src.batch(0, 8, 16, host_index=0, host_count=2)
        h1 = src.batch(0, 8, 16, host_index=1, host_count=2)
        np.testing.assert_array_equal(np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"])

    def test_labels_are_shifted_tokens(self):
        src = SyntheticLM(DataConfig(seed=0, vocab_size=50))
        b = src.batch(0, 2, 16)
        # labels[t] is the next token after tokens[t] by construction
        assert b["tokens"].shape == b["labels"].shape == (2, 16)


class TinyShape(ShapeConfig):
    pass


@pytest.fixture(scope="module")
def tiny_train():
    cfg = get_config("llama3.2-3b").reduced()
    shape = ShapeConfig("tiny", "train", seq_len=32, global_batch=4)
    return cfg, shape


class TestTrainLoop:
    def test_loss_decreases_and_resume_matches(self, tiny_train, tmp_path):
        from repro.train.loop import TrainRunConfig, train_run
        from repro.train.optimizer import AdamWConfig, Schedule

        cfg, shape = tiny_train
        sctx = ShardingCtx.null()
        opt = AdamWConfig(schedule=Schedule(base_lr=3e-3, warmup_steps=5, kind="const"))
        run = TrainRunConfig(
            steps=16, ckpt_every=8, log_every=4, opt=opt,
            ckpt_dir=str(tmp_path / "a"),
            data=DataConfig(seed=0, vocab_size=cfg.vocab_size, noise=0.02),
        )
        res = train_run(cfg, shape, sctx, run)
        assert res["loss_last"] < res["loss_first"], res

        # interrupted run: first 8 steps land a checkpoint ...
        run_b1 = TrainRunConfig(
            steps=8, ckpt_every=8, log_every=4, opt=opt, ckpt_dir=str(tmp_path / "b"),
            data=run.data,
        )
        train_run(cfg, shape, sctx, run_b1)
        # ... then a fresh loop resumes at 8 and finishes at 16
        run_b2 = TrainRunConfig(
            steps=16, ckpt_every=8, log_every=4, opt=opt, ckpt_dir=str(tmp_path / "b"),
            data=run.data,
        )
        res_b = train_run(cfg, shape, sctx, run_b2)
        # deterministic data + deterministic init => identical final loss
        assert res_b["loss_last"] == pytest.approx(res["loss_last"], rel=1e-3)
