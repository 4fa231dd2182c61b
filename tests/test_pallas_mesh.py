"""Paged Pallas kernels under a multi-device mesh (shard_map).

GSPMD cannot partition a pallas_call, so the serving step wraps the paged
decode/chunk kernels in shard_map with per-shard page-id localization
(see kernels/ops.py + models/attention.py ``_paged_kernel_specs``). These
tests pin:

  * op-level identity: the shard_map'd kernel reproduces the
    single-device kernel bit-for-bit under model- and data-sharded
    meshes, including global->local page-id translation against a truly
    partitioned pool;
  * end-to-end identity: serving with ``attn_backend="pallas"`` under a
    mesh emits exactly the tokens of the XLA gather path, and the
    sharded kernel wrapper is actually on the traced path (not silently
    falling back);
  * clean fallbacks: layouts that can't partition (indivisible heads,
    single-slot chunks under a data axis) return None from the spec
    resolver and take the XLA path.

Multi-device cases skip on 1-device CI; the sharded-smoke lane forces 8
host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import ops as kops
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.models.attention import _paged_kernel_specs
from repro.models.schema import init_params
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx, get_profile

needs_2dev = pytest.mark.skipif(
    not mesh_mod.devices_required(2),
    reason="needs >=2 XLA devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

from jax.sharding import PartitionSpec as P  # noqa: E402


# ==========================================================================
# Op level: shard_map'd kernel vs the single-device kernel
# ==========================================================================
def _pool_problem(rng, *, n_slots, per_shard, shards, page, KV, D, H):
    """A paged-decode problem over a pool laid out in per-shard blocks
    (each block's last row is its trash page), page tables shard-local."""
    stride = per_shard + 1
    total = shards * stride
    k_pool = jnp.asarray(rng.normal(size=(total, page, KV, D)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(total, page, KV, D)), jnp.float32)
    max_pages = per_shard // (n_slots // shards)
    pt = np.zeros((n_slots, max_pages), np.int32)
    pos = np.zeros((n_slots,), np.int32)
    slots_per_shard = n_slots // shards
    for s in range(n_slots):
        sh = s * shards // n_slots
        base = sh * stride + (s % slots_per_shard) * max_pages
        held = 1 + (s % max_pages)
        row = [base + j for j in range(held)]
        row += [sh * stride + per_shard] * (max_pages - held)  # trash fill
        pt[s] = row
        pos[s] = held * page - 1 - (s % page)
    q = jnp.asarray(rng.normal(size=(n_slots, 1, H, D)), jnp.float32)
    return q, k_pool, v_pool, jnp.asarray(pt), jnp.asarray(pos), max_pages


class TestOpIdentity:
    @needs_2dev
    def test_model_sharded_kernel_matches_single_device(self):
        """(1, 2) mesh: heads split over model, pool replicated — the
        shard_map'd kernel equals the direct call."""
        rng = np.random.default_rng(0)
        q, k, v, pt, pos, n_lp = _pool_problem(
            rng, n_slots=4, per_shard=8, shards=1, page=4, KV=2, D=8, H=4
        )
        ref = kops.paged_decode_attention_op(q, k, v, pt, pos, n_lp=n_lp)
        mesh = mesh_mod.make_test_mesh(data=1, model=2)
        out = kops.paged_decode_attention_sharded(
            q, k, v, pt, pos, n_lp=n_lp, mesh=mesh,
            q_spec=P(None, None, "model", None),
            pool_spec=P(None, None, "model", None),
            table_spec=P(None, None), vec_spec=P(None),
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

    @needs_2dev
    @pytest.mark.parametrize("retired", [False, True])
    def test_data_sharded_kernel_localizes_page_ids(self, retired):
        """(2, 1) mesh over a truly partitioned pool: each shard sees its
        sub-pool with local ids; output equals the global single-device
        kernel fed the global table. With ``retired``, the first slot of
        each shard holds a row of its shard's trash page and a large frozen
        position: localized, that row starts at the local trash page, so
        the walk reads nothing for it (a zero row), while the global kernel
        would read shard 0's trash page as data; the live rows still match."""
        rng = np.random.default_rng(1)
        q, k, v, pt, pos, n_lp = _pool_problem(
            rng, n_slots=4, per_shard=8, shards=2, page=4, KV=2, D=8, H=4
        )
        live = np.ones(4, bool)
        if retired:
            pt, pos = np.array(pt), np.array(pos)
            for s, trash in ((0, 8), (2, 17)):  # shard sh's trash: 9 * sh + 8
                pt[s], pos[s], live[s] = trash, 10**6, False
            pt, pos = jnp.asarray(pt), jnp.asarray(pos)
        ref = kops.paged_decode_attention_op(q, k, v, pt, pos, n_lp=n_lp)
        mesh = mesh_mod.make_test_mesh(data=2, model=1)
        out = kops.paged_decode_attention_sharded(
            q, k, v, pt, pos, n_lp=n_lp, mesh=mesh,
            q_spec=P("data", None, None, None),
            pool_spec=P("data", None, None, None),
            table_spec=P("data", None), vec_spec=P("data"),
            localize_pages=True,
        )
        out, ref = np.asarray(out), np.asarray(ref)
        np.testing.assert_allclose(out[live], ref[live], rtol=1e-6)
        assert not out[~live].any()

    @needs_2dev
    def test_chunk_kernel_model_sharded(self):
        """Chunked-prefill kernel under (1, 2): head-split shard_map equals
        the direct call (single-slot chunk, pool replicated)."""
        rng = np.random.default_rng(2)
        page, KV, D, H, C = 4, 2, 8, 4, 8
        total, n_lp = 7, 4
        k = jnp.asarray(rng.normal(size=(total, page, KV, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(total, page, KV, D)), jnp.float32)
        pt = jnp.asarray([[0, 1, 2, 6]], jnp.int32)  # 6 == trash
        start = jnp.asarray([5], jnp.int32)
        q = jnp.asarray(rng.normal(size=(1, C, H, D)), jnp.float32)
        ref = kops.paged_chunk_attention_op(q, k, v, pt, start, n_lp=n_lp)
        mesh = mesh_mod.make_test_mesh(data=1, model=2)
        out = kops.paged_chunk_attention_sharded(
            q, k, v, pt, start, n_lp=n_lp, mesh=mesh,
            q_spec=P(None, None, "model", None),
            pool_spec=P(None, None, "model", None),
            table_spec=P(None, None), vec_spec=P(None),
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# ==========================================================================
# Spec resolution: when shard_map applies vs XLA fallback
# ==========================================================================
class TestSpecResolution:
    def test_single_device_returns_none(self):
        assert _paged_kernel_specs(
            ShardingCtx.null(), B=4, H=4, KV=2, total_pages=10,
            batch_sharded=True,
        ) is None

    @needs_2dev
    def test_indivisible_heads_fall_back(self):
        sctx = ShardingCtx(
            mesh_mod.make_test_mesh(data=1, model=2),
            get_profile("decode_default"),
        )
        assert _paged_kernel_specs(
            sctx, B=4, H=3, KV=1, total_pages=10, batch_sharded=True
        ) is None

    @needs_2dev
    def test_chunk_under_data_axis_falls_back(self):
        sctx = ShardingCtx(
            mesh_mod.make_test_mesh(data=2, model=1),
            get_profile("decode_default"),
            pool_data_shards=2,
        )
        assert _paged_kernel_specs(
            sctx, B=1, H=4, KV=2, total_pages=10, batch_sharded=False
        ) is None

    @needs_2dev
    def test_replicated_pool_under_data_axis_does_not_localize(self):
        """data > 1 with a single-shard pool (pool_data_shards == 1): the
        batch still splits but page ids stay global."""
        sctx = ShardingCtx(
            mesh_mod.make_test_mesh(data=2, model=1),
            get_profile("decode_default"),
        )
        specs = _paged_kernel_specs(
            sctx, B=4, H=4, KV=2, total_pages=16, batch_sharded=True
        )
        assert specs is not None
        assert specs["localize_pages"] is False
        assert specs["pool_spec"] == P(None, None, None, None, None)  # stacked pools

    @needs_2dev
    def test_partitioned_pool_localizes(self):
        sctx = ShardingCtx(
            mesh_mod.make_test_mesh(data=2, model=1),
            get_profile("decode_default"),
            pool_data_shards=2,
        )
        specs = _paged_kernel_specs(
            sctx, B=4, H=4, KV=2, total_pages=18, batch_sharded=True
        )
        assert specs is not None
        assert specs["localize_pages"] is True
        assert specs["pool_spec"] == P(None, "data", None, None, None)
        assert specs["table_spec"] == P("data", None)


# ==========================================================================
# End to end: serving with the Pallas backend under a mesh
# ==========================================================================
def _serve(cfg, params, prompts, **kw):
    sched = Scheduler(cfg, params, ShardingCtx.null(), SchedulerConfig(**kw))
    for p in prompts:
        sched.submit(Request(prompt=p, max_new_tokens=6))
    return [rs.tokens for rs in sched.run()], sched


class TestEndToEndIdentity:
    @needs_2dev
    @pytest.mark.parametrize("mesh_shape", [(1, 2), (2, 1)])
    def test_pallas_under_mesh_matches_xla_gather(self, mesh_shape, monkeypatch):
        """Serving with the Pallas backend under a mesh is token-identical
        to the XLA gather path, and the shard_map'd decode kernel really
        is on the traced path."""
        base = get_config("llama3.2-3b").reduced()
        params = init_params(lm.model_schema(base), jax.random.PRNGKey(0))
        rng = np.random.default_rng(5)
        prompts = [
            rng.integers(0, base.vocab_size, size=t).astype(np.int32)
            for t in (8, 21, 13, 9)
        ]
        kw = dict(n_slots=4, cache_len=64, chunk_budget=16, page_size=8)

        cfg_x = replace(base, attn_backend="xla")
        ref, _ = _serve(cfg_x, params, prompts, mesh_shape=mesh_shape, **kw)

        hits = {"decode": 0}
        orig = kops.paged_decode_attention_sharded

        def spy(*a, **k):
            hits["decode"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(kops, "paged_decode_attention_sharded", spy)
        cfg_p = replace(base, attn_backend="pallas")
        out, sched = _serve(cfg_p, params, prompts, mesh_shape=mesh_shape, **kw)
        assert out == ref
        assert hits["decode"] > 0, "sharded kernel never traced; fallback?"
        if mesh_shape == (2, 1):
            assert sched.mem.data_shards == 2
            assert sched.sctx.pool_data_shards == 2

    @needs_2dev
    def test_pallas_under_mesh_matches_single_device_pallas(self):
        """Same backend, with and without the mesh: the shard_map path
        changes layout, never tokens."""
        base = get_config("llama3.2-3b").reduced()
        cfg = replace(base, attn_backend="pallas")
        params = init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        prompts = [
            rng.integers(0, cfg.vocab_size, size=t).astype(np.int32)
            for t in (8, 17)
        ]
        kw = dict(n_slots=2, cache_len=64, chunk_budget=16, page_size=8)
        ref, _ = _serve(cfg, params, prompts, **kw)
        out, _ = _serve(cfg, params, prompts, mesh_shape=(1, 2), **kw)
        assert out == ref
