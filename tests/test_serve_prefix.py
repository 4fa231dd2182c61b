"""Prefix sharing, multi-streamer scheduling, and multi-tenant admission:
the single-streamer gate is gone (concurrent streamers must coexist and the
old two-streamer deadlock must not come back), adopted prefixes stay
greedy-token-identical to the static engine, warm re-submits skip prompt
compute, tenant quotas defer without starving other tenants, weighted-fair
admission follows stride order, and the prefill bucket ladder stays bounded
past the dense cap."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import lm
from repro.models.schema import LeafLayout, init_params
from repro.serve.cache import _graft_leaf
from repro.serve.engine import Engine, ServeConfig
from repro.serve.request import Request, RequestStatus
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx


def _params_for(name):
    cfg = get_config(name).reduced()
    return cfg, init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32) for p in lengths]


def _solo(cfg, params, prompt, max_new):
    eng = Engine(
        cfg, params, ShardingCtx.null(), ServeConfig(max_new_tokens=max_new, cache_len=64)
    )
    return eng.generate_static({"tokens": np.asarray(prompt)[None, :]}).tokens[0].tolist()


# ==========================================================================
# The deadlock gate is gone: concurrent streamers prefill and complete
# ==========================================================================
class TestConcurrentStreamers:
    @pytest.mark.parametrize("policy", ["swap", "recompute"])
    def test_two_streamers_prefill_concurrently_and_finish(self, policy):
        """Regression for the single-streamer gate. Two reservation-free
        streamers over a pool too small for both worst cases used to
        deadlock (each waiting for the other's unreserved pages); the gate
        serialized them instead. Now both slots must be PREFILLING at once
        at some step, the drain must terminate in bounded steps, and both
        outputs must match solo runs."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [28, 30], seed=11)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=6,
                            chunk_budget=16, preemption=policy),
        )
        rids = [sched.submit(Request(p, max_new_tokens=8)) for p in prompts]
        # Count the streamers as each step plans its chunk: between step()
        # calls the host's view already runs one step ahead.
        both_streaming = 0
        chunk_step = sched._prefill_chunk_step

        def counting_chunk_step():
            nonlocal both_streaming
            n_prefilling = sum(
                rs.status is RequestStatus.PREFILLING
                for rs in sched._active.values()
            )
            both_streaming = max(both_streaming, n_prefilling)
            return chunk_step()

        sched._prefill_chunk_step = counting_chunk_step
        for _ in range(500):
            if not (sched.pending or sched.num_active):
                break
            sched.step()
        else:
            pytest.fail("two-streamer drain did not terminate in 500 steps")
        assert both_streaming >= 2, (
            "concurrent streamers never coexisted; the single-streamer "
            "gate is effectively back"
        )
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 8)

    def test_chunk_growth_restarts_younger_streamer_only(self):
        """The victim rule that keeps reservation-free multi-streaming
        deadlock-free: among streamers, growth may only restart *younger*
        ones (higher rid) — the oldest streamer always makes progress.
        The restarted streamer replays from chunk zero and still finishes
        token-identically."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [40, 40], seed=4)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16, preemption="recompute",
                            prefix_sharing=False),
        )
        r0 = sched.submit(Request(prompts[0], max_new_tokens=6))
        r1 = sched.submit(Request(prompts[1], max_new_tokens=6))
        # From cold one step() dispatches two steps, each a chunk of r0's
        # 40 tokens (16 at a time): both prompts are still streaming.
        sched.step()
        slots = {rs.rid: slot for slot, rs in sched._active.items()}
        assert all(
            rs.status is RequestStatus.PREFILLING
            for rs in sched._active.values()
        ), "setup: both requests should still be streaming their prompts"
        # the younger streamer may not restart the older one...
        assert not sched._preempt_lru(slots[r1], requester_rid=r1)
        # ...but the older one restarts the youngest above its rid
        assert sched._preempt_lru(slots[r0], requester_rid=r0)
        assert sched.preemptions_total == 1
        assert any(rs.rid == r1 for rs in sched._preempted)
        sched.run()
        for rid, p in zip((r0, r1), prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 6)


# ==========================================================================
# Prefix sharing: token identity, warm adoption, preempt + resume
# ==========================================================================
class TestPrefixSharingIdentity:
    @pytest.mark.parametrize(
        "arch",
        [
            "llama3.2-3b",  # dense GQA, paged: shares
            "recurrentgemma-2b",  # windowed ring pages: sharing no-op
            "deepseek-v2-236b",  # MLA per-slot cache: sharing no-op
            "xlstm-1.3b",  # pure recurrent: sharing no-op
            "llama4-scout-17b-a16e",  # MoE, paged: shares
        ],
    )
    def test_duplicate_prompts_greedy_match_static(self, arch):
        """Duplicate prompts force page adoption (where eligible) and must
        stay token-identical to the lockstep static engine."""
        cfg, params = _params_for(arch)
        eng = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=5, cache_len=64, page_size=8,
                        chunk_budget=16, prefix_sharing=True),
        )
        row = np.asarray(
            jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, cfg.vocab_size)
        )
        batch = {"tokens": np.concatenate([row, row, row])}
        np.testing.assert_array_equal(
            eng.generate(batch).tokens, eng.generate_static(batch).tokens
        )

    def test_warm_resubmit_adopts_and_skips_chunks(self):
        """A re-submitted prompt adopts its registered pages: fewer prompt
        tokens stream, TTFT work shrinks, tokens stay identical."""
        cfg, params = _params_for("llama3.2-3b")
        (prompt,) = _prompts(cfg, [33], seed=5)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16),
        )
        cold = sched.submit(Request(prompt, max_new_tokens=6))
        sched.run()
        warm = sched.submit(Request(prompt, max_new_tokens=6))
        sched.run()
        rs_cold, rs_warm = sched.result(cold), sched.result(warm)
        assert rs_cold.adopted_tokens == 0
        # 33 tokens @ 8/page: 4 full prompt pages adopted, the 33rd token
        # still streams so the final chunk's logits seed sampling
        assert rs_warm.adopted_tokens == 32
        assert sched.prefix_hits == 1 and sched.prefix_hit_tokens == 32
        assert rs_warm.tokens == rs_cold.tokens == _solo(cfg, params, prompt, 6)

    def test_shared_pages_survive_writer_divergence(self):
        """Two live requests with a common prefix: when the later one
        decodes into its copy, copy-on-write isolates the earlier one;
        both match solo references."""
        cfg, params = _params_for("llama3.2-3b")
        (common,) = _prompts(cfg, [24], seed=8)
        tails = _prompts(cfg, [7, 13], seed=9)
        prompts = [np.concatenate([common, t]).astype(np.int32) for t in tails]
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16),
        )
        r0 = sched.submit(Request(prompts[0], max_new_tokens=8))
        for _ in range(3):  # stream prompt 0 in; its pages get registered
            sched.step()
        r1 = sched.submit(Request(prompts[1], max_new_tokens=8))
        sched.run()
        assert sched.prefix_hits >= 1  # r1 adopted the common prefix pages
        for rid, p in zip((r0, r1), prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 8)

    def test_preempted_then_resumed_with_sharing(self):
        """Sharing on + preemption churn: a preempted-then-resumed request
        (restart re-adopts its own registered pages) stays identical."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [26, 26], seed=13)
        for policy in ("swap", "recompute"):
            sched = Scheduler(
                cfg, params, ShardingCtx.null(),
                SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=5,
                                chunk_budget=16, preemption=policy,
                                prefix_sharing=True),
            )
            rids = [sched.submit(Request(p, max_new_tokens=10)) for p in prompts]
            sched.run()
            assert sched.preemptions_total >= 1, policy
            for rid, p in zip(rids, prompts):
                assert sched.result(rid).tokens == _solo(cfg, params, p, 10), (
                    f"divergence under {policy} with sharing on"
                )


# ==========================================================================
# Multi-tenant admission: quotas and weighted-fair ordering
# ==========================================================================
class TestMultiTenant:
    def test_quota_blocked_tenant_does_not_starve_others(self):
        """Tenant A's second request would exceed A's page quota; it defers
        while tenant B admits and finishes. Everything drains, outputs
        stay solo-identical, and the deferral is counted."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [9, 9, 9], seed=7)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16, tenant_quota=3),
        )
        reqs = [
            Request(prompts[0], max_new_tokens=8, tenant="A"),
            Request(prompts[1], max_new_tokens=8, tenant="A"),
            Request(prompts[2], max_new_tokens=8, tenant="B"),
        ]
        rids = [sched.submit(r) for r in reqs]
        sched.run()
        assert sched.quota_deferrals > 0
        # B was admitted while A's second request sat quota-blocked
        assert sched.result(rids[2]).t_admit < sched.result(rids[1]).t_admit
        for rid, r in zip(rids, reqs):
            assert sched.result(rid).tokens == _solo(
                cfg, params, r.prompt, 8
            )

    def test_single_request_over_quota_fails_fast(self):
        cfg, params = _params_for("llama3.2-3b")
        (prompt,) = _prompts(cfg, [9], seed=1)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16, tenant_quota=1),
        )
        sched.submit(Request(prompt, max_new_tokens=30, tenant="A"))
        with pytest.raises(RuntimeError, match="whole quota"):
            sched.run()

    def test_weighted_fair_stride_order(self):
        """Weights {A: 3, B: 1} with one slot and equal-size requests admit
        in stride order A1, B1, A2, A3, A4, B2 — the 3x weight lets A's
        third and fourth requests overtake B's second."""
        cfg, params = _params_for("llama3.2-3b")
        (prompt,) = _prompts(cfg, [8], seed=2)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16,
                            tenant_weights={"A": 3.0, "B": 1.0}),
        )
        order = ["A", "B", "A", "A", "A", "B"]  # submission order
        rids = [
            sched.submit(Request(prompt, max_new_tokens=4, tenant=t))
            for t in order
        ]
        sched.run()
        admitted = sorted(rids, key=lambda r: sched.result(r).t_admit)
        labels = [f"{order[rids.index(r)]}{rids.index(r)}" for r in admitted]
        assert labels == ["A0", "B1", "A2", "A3", "A4", "B5"]


# ==========================================================================
# Prefill bucket ladder stays bounded past the dense cap
# ==========================================================================
class TestBucketCapBoundary:
    def test_past_cap_prompts_use_bounded_pow2_ladder(self):
        """Windowed models legitimately stream prompts past cache_len; the
        bucket for such a length must be a power of two (bounded distinct
        trace count), never the raw length."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, chunk_budget=16),
        )
        sched.cfg = dataclasses.replace(cfg, window_size=32)
        lengths = list(range(65, 700, 3))
        buckets = {sched._bucket_len(n) for n in lengths}
        assert all(b & (b - 1) == 0 for b in buckets), "non-pow2 bucket"
        assert all(sched._bucket_len(n) >= n for n in lengths)
        # log2 ladder: a handful of shapes for hundreds of lengths
        assert len(buckets) <= 4

    def test_past_cap_on_dense_model_fails_loudly(self):
        """A dense model can never legitimately see a past-cap prompt at
        prefill (admission validates); the old code silently returned the
        unbucketed raw length — one fresh compile per prompt."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, chunk_budget=16),
        )
        assert sched._bucket_len(64) == 64
        with pytest.raises(RuntimeError, match="exceeds the dense prefill cap"):
            sched._bucket_len(65)


# ==========================================================================
# Cache graft layout metadata: collisions raise instead of mis-grafting
# ==========================================================================
class TestGraftLayouts:
    def test_dense_graft_longer_source_raises(self):
        """With explicit layout metadata, a dense source longer than the
        target raises instead of being silently ring-folded (the old
        shape-guessing treated any shorter target as a ring)."""
        dst = np.zeros((4, 8, 2), np.float32)
        src = np.ones((4, 12, 2), np.float32)
        lay = LeafLayout("dense", seq_axis=1)
        with pytest.raises(ValueError, match="exceeds target"):
            _graft_leaf(dst, src, prompt_len=12, layout=lay)

    def test_ring_layout_folds_long_source(self):
        """The same shapes graft fine when the layout says ring: the last
        window of the source lands rotated at prompt_len % window."""
        window = 8
        dst = np.zeros((4, window, 2), np.float32)
        src = np.arange(4 * 12 * 2, dtype=np.float32).reshape(4, 12, 2)
        lay = LeafLayout("ring", seq_axis=1, cap=window)
        out = np.asarray(_graft_leaf(dst, src, prompt_len=12, layout=lay))
        # position p lands at ring slot p % window
        for p in range(12 - window, 12):
            np.testing.assert_array_equal(out[:, p % window], src[:, p])

    def test_copy_layout_requires_exact_shape(self):
        dst = np.zeros((4, 8), np.float32)
        src = np.ones((4, 9), np.float32)
        lay = LeafLayout("copy")
        with pytest.raises(ValueError):
            _graft_leaf(dst, src, prompt_len=9, layout=lay)
