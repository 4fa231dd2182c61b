"""Sharded multi-device serving: tensor-parallel stepping over a
("data", "model") mesh must be a pure layout change.

Greedy token identity sharded vs unsharded across the model zoo's state
families, layout-aware hot-path features (CoW forks, preemption swap and
recompute round-trips, speculative rollback) with pool conservation under
a 2-device mesh, bounded compile counts independent of mesh size, and the
analytic decode roofline predictor.

Multi-device cases gate on ``mesh.devices_required(2)`` and *skip* on
1-device CI; the sharded-smoke CI lane forces 8 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) so they run for
real there. Everything mesh-independent (predictor math, mesh error
messages) runs everywhere.
"""

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.launch import mesh as mesh_mod
from repro.models import lm
from repro.models.schema import count_params, init_params
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx

needs_2dev = pytest.mark.skipif(
    not mesh_mod.devices_required(2),
    reason="needs >=2 XLA devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

ARCHS = [
    "llama3.2-3b",  # dense GQA, paged
    "recurrentgemma-2b",  # windowed ring KV + RG-LRU hybrid
    "deepseek-v2-236b",  # MLA compressed cache (per-slot path)
    "xlstm-1.3b",  # pure recurrent (mLSTM + sLSTM), zero pages
    "llama4-scout-17b-a16e",  # MoE, scan-stacked groups
]


def _params_for(name):
    cfg = get_config(name).reduced()
    return cfg, init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32) for p in lengths]


def _run(cfg, params, prompts, max_new=6, **sched_kw):
    sched = Scheduler(
        cfg, params, ShardingCtx.null(), SchedulerConfig(**sched_kw)
    )
    for p in prompts:
        sched.submit(Request(prompt=p, max_new_tokens=max_new))
    outs = [rs.tokens for rs in sched.run()]
    return outs, sched


# ==========================================================================
# Token identity: sharded vs single-device, across state families
# ==========================================================================
class TestShardedTokenIdentity:
    @needs_2dev
    @pytest.mark.parametrize("arch", ARCHS)
    def test_sharded_greedy_matches_unsharded(self, arch):
        """The same workload on mesh (1, 2) must emit the same greedy
        tokens as the 1-device step, with identical trace counts: sharding
        changes array layouts, never the math or the compile cadence."""
        cfg, params = _params_for(arch)
        prompts = _prompts(cfg, (8, 21, 13))
        base, s0 = _run(
            cfg, params, prompts, cache_len=64, chunk_budget=16, page_size=8
        )
        shd, s1 = _run(
            cfg, params, prompts,
            cache_len=64, chunk_budget=16, page_size=8, mesh_shape=(1, 2),
        )
        assert base == shd
        assert s1.stats()["mesh"] == {"data": 1, "model": 2}
        assert s1.stats()["mesh_devices"] == 2
        assert (s0.decode_traces, s0.chunk_traces, s0.admit_traces) == (
            s1.decode_traces, s1.chunk_traces, s1.admit_traces,
        )

    @needs_2dev
    def test_sharded_state_actually_sharded(self):
        """The resolved layer shardings place at least one leaf over the
        model axis — the mesh isn't silently all-replicated."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, mesh_shape=(1, 2)),
        )
        assert sched._layer_shardings is not None
        specs = jax.tree.leaves(
            jax.tree.map(lambda s: str(s.spec), sched._layer_shardings)
        )
        assert any("model" in s for s in specs), specs
        b = sched.paged_cache_bytes()
        assert 0 < b["bytes_per_page_per_device"] < b["bytes_per_page"]


# ==========================================================================
# Layout-aware hot-path features under a 2-device mesh
# ==========================================================================
class TestShardedHotPaths:
    @needs_2dev
    @pytest.mark.parametrize("policy", ["swap", "recompute"])
    def test_preemption_roundtrip_identity_and_conservation(self, policy):
        """A pool sized to force preemption: preempted-then-resumed requests
        stay token-identical to the uncontended single-device run, and every
        page returns to the pool on drain."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, (16, 18, 17, 20))
        base, _ = _run(
            cfg, params, prompts, max_new=10,
            n_slots=3, cache_len=64, chunk_budget=16, page_size=4,
        )
        shd, sched = _run(
            cfg, params, prompts, max_new=10,
            n_slots=3, cache_len=64, chunk_budget=16, page_size=4,
            n_pages=14, preemption=policy, mesh_shape=(1, 2),
        )
        assert base == shd
        assert sched.preemptions_total > 0, "pool never ran dry; tighten it"
        assert sched.pool.in_use == 0
        assert sched.pool.available() == sched.pages.n_pages

    @needs_2dev
    def test_cow_fork_shard_map_under_mesh(self):
        """Prefix sharing + a second writer: the shard_map CoW program forks
        shared pages device-locally and the fork is observable (cow_traces)
        without breaking greedy identity."""
        cfg, params = _params_for("llama3.2-3b")
        rng = np.random.default_rng(3)
        shared = rng.integers(0, cfg.vocab_size, size=17).astype(np.int32)
        prompts = [
            np.concatenate([shared, rng.integers(0, cfg.vocab_size, size=t).astype(np.int32)])
            for t in (5, 9)
        ]
        kw = dict(
            n_slots=2, cache_len=64, chunk_budget=16, page_size=8,
            prefix_sharing=True,
        )
        base, s0 = _run(cfg, params, prompts, **kw)
        shd, s1 = _run(cfg, params, prompts, mesh_shape=(1, 2), **kw)
        assert base == shd
        assert s1.prefix_hits == s0.prefix_hits
        # Force a fork through the CoW program directly so the shard_map
        # copy itself is exercised even when the scheduler's write pattern
        # keeps steady-state CoW a no-op: real KV data lands in page 0
        # during the run, then page 0 is forked into page 1.
        from repro.models import blocks

        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(mesh_shape=(1, 2), **kw),
        )
        sched.submit(Request(prompt=prompts[0], max_new_tokens=2))
        sched.run()
        layers = sched._states["layers"]
        src = jax.numpy.asarray([0], jax.numpy.int32)
        dst = jax.numpy.asarray([1], jax.numpy.int32)
        forked = sched._cow_jit(layers, src, dst)
        assert sched.cow_traces >= 1
        caps = blocks.stack_paged_caps(cfg, kw["cache_len"])
        for cap, old, new in zip(
            jax.tree.leaves(caps),
            jax.tree.leaves(layers),
            jax.tree.leaves(forked),
        ):
            if not cap:
                continue
            old_np, new_np = np.asarray(old), np.asarray(new)
            if old_np.ndim == 5:
                np.testing.assert_array_equal(new_np[:, 1], old_np[:, 0])
            else:
                np.testing.assert_array_equal(new_np[1], old_np[0])

    @needs_2dev
    def test_speculative_rollback_identity_under_mesh(self):
        """Oracle-quality and garbage drafts: verify, partial-accept
        rollback (pos fixup for dense) and page truncation all run sharded
        and stay token-identical."""
        cfg, params = _params_for("llama3.2-3b")
        p = np.array([5, 6, 7, 8, 5, 6, 7, 8, 5, 6], np.int32)
        kw = dict(
            n_slots=2, cache_len=64, chunk_budget=16, page_size=8,
            speculative=True, draft_k=4,
        )
        base, s0 = _run(cfg, params, [p, p[1:]], max_new=8, **kw)
        shd, s1 = _run(cfg, params, [p, p[1:]], max_new=8, mesh_shape=(1, 2), **kw)
        assert base == shd
        assert s1.total_spec_steps == s0.total_spec_steps
        assert s1.verify_traces == s0.verify_traces
        assert s1.accepted_tokens_total == s0.accepted_tokens_total

    @needs_2dev
    def test_recurrent_replay_rollback_under_mesh(self):
        """Archs whose state advances through rejected tokens roll back by
        snapshot replay — sharded, that replay must also stay identical."""
        cfg, params = _params_for("recurrentgemma-2b")
        p = np.array([3, 9, 4, 3, 9, 4, 3, 9], np.int32)
        kw = dict(
            n_slots=2, cache_len=64, chunk_budget=16, page_size=8,
            speculative=True, draft_k=3,
        )
        base, s0 = _run(cfg, params, [p], max_new=7, **kw)
        shd, s1 = _run(cfg, params, [p], max_new=7, mesh_shape=(1, 2), **kw)
        assert base == shd
        assert s1.total_spec_replays == s0.total_spec_replays


# ==========================================================================
# Data-axis partitioning: slots and pool slices split across `data`
# ==========================================================================
class TestDataAxisPartitioning:
    @needs_2dev
    @pytest.mark.parametrize("arch", ARCHS)
    def test_data_axis_greedy_identity(self, arch):
        """mesh (2, 1): the slot batch and (for paged archs) the page pool
        partition across the data axis; greedy tokens and compile cadence
        stay identical to the single-device run."""
        cfg, params = _params_for(arch)
        prompts = _prompts(cfg, (8, 21, 13, 9))
        kw = dict(n_slots=4, cache_len=64, chunk_budget=16, page_size=8)
        base, s0 = _run(cfg, params, prompts, **kw)
        shd, s1 = _run(cfg, params, prompts, mesh_shape=(2, 1), **kw)
        assert base == shd
        assert s1.stats()["mesh"] == {"data": 2, "model": 1}
        assert (s0.decode_traces, s0.chunk_traces, s0.admit_traces) == (
            s1.decode_traces, s1.chunk_traces, s1.admit_traces,
        )

    @needs_2dev
    def test_data_axis_partitions_pool_and_slots(self):
        """With data=2 dividing n_slots and n_pages, the MemoryManager runs
        two per-shard sub-pools (each with its own trash row) and the live
        pool leaves are page-axis sharded over data — not replicated."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(
                n_slots=4, cache_len=64, chunk_budget=16, page_size=8,
                mesh_shape=(2, 1),
            ),
        )
        mem = sched.mem
        assert mem.data_shards == 2
        assert len(mem.pools) == 2
        assert all(p.layout.n_pages == mem.n_pages // 2 for p in mem.pools)
        # Slot -> shard follows the contiguous batch blocks; each shard's
        # trash row is the last row of its GSPMD block.
        assert [mem.shard_of(s) for s in range(4)] == [0, 0, 1, 1]
        per, stride = mem.n_pages // 2, mem.n_pages // 2 + 1
        assert mem.trash_of(0) == per
        assert mem.trash_of(3) == stride + per
        with pytest.raises(AttributeError):
            mem.pool  # single-pool view is unavailable when partitioned
        # The live device pool leaves carry a data-sharded page axis.
        total = sched.pages.total_pages
        page_leaves = [
            (arr.ndim, arr.sharding.spec)
            for arr in jax.tree.leaves(sched._states["layers"])
            if arr.ndim >= 4 and arr.shape[arr.ndim - 4] == total
        ]
        assert page_leaves, "no pool-shaped leaves found"
        for ndim, spec in page_leaves:
            # The page axis (4th from the end) carries the data axis.
            padded = tuple(spec) + (None,) * ndim
            assert padded[ndim - 4] in ("data", ("data",)), spec
        # Accounting reflects the partition.
        st = sched.stats()["pages"]
        assert st["data_shards"] == 2

    @needs_2dev
    def test_data_axis_falls_back_when_indivisible(self):
        """n_pages not divisible by data: the pool stays single-shard
        (replicated leaves, the pre-partitioning layout) and serving still
        produces identical tokens."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, (8, 13))
        kw = dict(
            n_slots=2, cache_len=64, chunk_budget=16, page_size=8, n_pages=15,
        )
        base, _ = _run(cfg, params, prompts, **kw)
        shd, s1 = _run(cfg, params, prompts, mesh_shape=(2, 1), **kw)
        assert base == shd
        assert s1.mem.data_shards == 1
        assert s1.pool is not None  # single-pool view still available

    @needs_2dev
    @pytest.mark.parametrize("policy", ["swap", "recompute"])
    def test_data_axis_preemption_is_shard_local(self, policy):
        """Preemption under a partitioned pool picks victims within the
        requester's shard; round-trips stay token-identical and every page
        returns to its shard's sub-pool on drain."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, (16, 18, 17, 20, 15, 19))
        kw = dict(
            max_new=10, n_slots=4, cache_len=64, chunk_budget=16,
            page_size=4, n_pages=20, preemption=policy,
        )
        base, _ = _run(cfg, params, prompts, **kw)
        shd, sched = _run(cfg, params, prompts, mesh_shape=(2, 1), **kw)
        assert base == shd
        assert sched.preemptions_total > 0, "pool never ran dry; tighten it"
        assert sched.mem.in_use == 0
        assert sched.mem.available_total() == sched.pages.n_pages

    @needs_2dev
    def test_data_axis_prefix_sharing_is_shard_local(self):
        """Prefix adoption under a partitioned pool: the index lives per
        sub-pool, so sharing works within a shard and never aliases pages
        across shards; greedy identity holds throughout."""
        cfg, params = _params_for("llama3.2-3b")
        rng = np.random.default_rng(7)
        shared = rng.integers(0, cfg.vocab_size, size=17).astype(np.int32)
        prompts = [
            np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, size=t).astype(np.int32)]
            )
            for t in (5, 9, 6, 11)
        ]
        kw = dict(
            n_slots=4, cache_len=64, chunk_budget=16, page_size=8,
            prefix_sharing=True,
        )
        base, _ = _run(cfg, params, prompts, **kw)
        shd, s1 = _run(cfg, params, prompts, mesh_shape=(2, 1), **kw)
        assert base == shd
        assert s1.mem.in_use == 0


# ==========================================================================
# Mesh plumbing and failure modes (run everywhere)
# ==========================================================================
class TestMeshPlumbing:
    def test_make_test_mesh_fails_loudly_naming_the_flag(self):
        n = len(jax.devices()) + 1
        with pytest.raises(RuntimeError) as e:
            mesh_mod.make_test_mesh(data=1, model=n)
        msg = str(e.value)
        assert "--xla_force_host_platform_device_count" in msg
        assert "devices_required" in msg

    def test_devices_required(self):
        assert mesh_mod.devices_required(1)
        assert not mesh_mod.devices_required(len(jax.devices()) + 1)

    def test_scheduler_mesh_shape_1x1_is_noop(self):
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, mesh_shape=(1, 1)),
        )
        assert sched.sctx.mesh is None
        assert sched._layer_shardings is None
        assert sched.stats()["mesh"] is None
        assert sched.stats()["mesh_devices"] == 1

    def test_paged_state_is_made_at_its_shardings(self, monkeypatch):
        """The page pool is made in place at its sharding: a pool of every
        KV head never lands whole on one device first (at full width it
        does not fit one chip beside that chip's share of the weights)."""
        from repro.serve.pages import PageLayout, model_page_span
        from repro.serve.step import decode_state_shardings, init_paged_decode_state
        from repro.sharding.rules import get_profile

        cfg = get_config("llama3.2-3b").reduced()
        n = 2 if mesh_mod.devices_required(2) else 1
        sctx = ShardingCtx(mesh_mod.make_test_mesh(1, n), get_profile("decode_default"))
        layout = PageLayout(page_size=16, n_pages=8, span=model_page_span(cfg, 64))

        def no_put(*a, **kw):
            raise AssertionError("a whole state leaf was put on a device")

        monkeypatch.setattr(jax, "device_put", no_put)
        state = init_paged_decode_state(cfg, 2, 64, layout, sctx=sctx)
        monkeypatch.undo()
        want = decode_state_shardings(cfg, 2, 64, sctx, pages=layout)
        for leaf, sh in zip(jax.tree.leaves(state["layers"]), jax.tree.leaves(want)):
            assert leaf.sharding == sh
            assert not np.asarray(leaf).any()

    def test_serve_sweep_mesh_shape_knob_normalizes(self):
        from repro.experiments.serve import _mesh_shape_opt

        assert _mesh_shape_opt(None) is None
        assert _mesh_shape_opt("1x2") == (1, 2)
        assert _mesh_shape_opt("2X4") == (2, 4)
        assert _mesh_shape_opt((1, 2)) == (1, 2)
        assert _mesh_shape_opt([2, 2]) == (2, 2)


# ==========================================================================
# Analytic decode roofline predictor
# ==========================================================================
class TestDecodeRoofline:
    def test_predictor_terms(self):
        from repro.launch.roofline import V5E, device_peaks, predict_decode_step

        peaks = device_peaks(V5E)

        cfg = get_config("llama3.2-3b").reduced()
        n = count_params(lm.model_schema(cfg))
        one = predict_decode_step(cfg, n, batch=4, mesh_shape=(1, 1))
        tp = predict_decode_step(cfg, n, batch=4, mesh_shape=(1, 2))
        # Single device: no collective term, memory = full weights.
        assert one.t_collective == 0.0
        assert one.hlo_bytes_per_device == n * 2
        assert one.step_time_lower_bound > 0
        # TP=2 halves per-device weight traffic and adds an all-reduce term.
        assert tp.hlo_bytes_per_device == n  # n * 2 bytes / 2 devices
        assert tp.t_collective > 0
        exp_coll = 2 * cfg.n_layers * (2 * 4 * cfg.d_model * 2 * 0.5)
        assert tp.collective_bytes_per_device == pytest.approx(exp_coll)
        assert tp.t_memory == pytest.approx((n / peaks.hbm_bw))
        assert tp.t_collective == pytest.approx(exp_coll / peaks.ici_link_bw)
        assert peaks.ici_link_bw == pytest.approx(50e9)
        with pytest.raises(KeyError, match="no published peaks"):
            device_peaks("cpu")
        assert tp.chips == 2

    def test_serve_sweep_emits_prediction(self, tmp_path):
        import repro.core as memento
        from repro.experiments import serve_matrix, serve_sweep
        from repro.launch.roofline import PEAKS

        matrix = serve_matrix(
            ["llama3.2-3b"], backends=["xla"], scheduler={"n_slots": [2]},
            cache_len=64, n_requests=2, prompt_lens=(4, 6),
            max_new_tokens=3, warmup=False,
        )
        eng = memento.Memento(
            serve_sweep, memento.RecordingProvider(), workdir=tmp_path,
            namespace="sharded-pred",
            runner_config=memento.RunnerConfig(
                max_workers=1, retries=0, enable_speculation=False
            ),
        )
        (r,) = eng.run(matrix)
        assert r.status == "ok"
        v = r.value
        assert v["mesh"] == "1x1"
        assert v["mesh_devices"] == 1
        dev = jax.devices()[0]
        assert v["device"] == {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
        }
        # A prediction only for a device kind with published peaks.
        if dev.device_kind in PEAKS:
            assert v["predicted_step_ms"] > 0
            assert v["predicted_bottleneck"] in ("compute", "memory", "collective")
        else:
            assert v["predicted_step_ms"] is None
            assert v["predicted_bottleneck"] is None

    def test_roofline_ratio_metric(self):
        from repro.analysis.metrics import MetricSpec

        from repro.experiments.serve import SERVE_METRIC_SPECS

        spec = {s.name: s for s in SERVE_METRIC_SPECS}["roofline_ratio"]
        assert spec.from_row(
            {"itl_p50_s": 0.002, "predicted_step_ms": 1.0}
        ) == pytest.approx(2.0)
        assert spec.from_row({"itl_p50_s": 0.002, "predicted_step_ms": 0}) is None
        assert spec.from_row({"predicted_step_ms": 1.0}) is None
