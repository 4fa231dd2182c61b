"""Paged KV serving: PagePool alloc/free invariants (property tests),
paged-vs-static greedy token identity across the model zoo's state
families, the cache layout each registered arch gets by default,
pool-capacity admission backpressure, shape-stable decode under
page growth, bucketed-prefill compile counts, and the paged decode kernel
vs its XLA gather reference."""
import json
import os
import re
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_config, list_archs
from repro.kernels import decode_attention as dec
from repro.kernels import ops as kops
from repro.models import lm
from repro.models.schema import init_params
from repro.serve.cache import graft_pages_leaf
from repro.serve.engine import Engine, ServeConfig
from repro.serve.pages import PageLayout, PagePool, model_page_span
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx


def _params_for(name):
    cfg = get_config(name).reduced()
    return cfg, init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32) for p in lengths]


# ==========================================================================
# PagePool invariants
# ==========================================================================
class TestPagePoolProperties:
    @settings(max_examples=30)
    @given(
        n_pages=st.integers(min_value=1, max_value=40),
        page_size=st.integers(min_value=1, max_value=32),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    def test_random_admit_retire_no_alias_no_leak(self, n_pages, page_size, seed):
        """Under random reserve/grow/release traffic: a page is never held
        by two slots, reservations are never overcommitted, and releasing
        everything returns the pool to fully free."""
        layout = PageLayout(page_size=page_size, n_pages=n_pages, span=n_pages * page_size)
        pool = PagePool(layout)
        rng = np.random.default_rng(seed)
        live: dict[int, int] = {}  # slot -> reserved count
        next_slot = 0
        for _ in range(200):
            op = rng.integers(0, 3)
            if op == 0:  # admit
                want = int(rng.integers(1, max(n_pages // 2, 2)))
                if pool.can_reserve(want):
                    pool.reserve(next_slot, want)
                    pool.grow_to(next_slot, int(rng.integers(0, want + 1)))
                    live[next_slot] = want
                    next_slot += 1
            elif op == 1 and live:  # grow an existing slot within reservation
                slot = int(rng.choice(list(live)))
                pool.grow_to(slot, int(rng.integers(0, live[slot] + 1)))
            elif op == 2 and live:  # retire
                slot = int(rng.choice(list(live)))
                pool.release(slot)
                del live[slot]
            # no-alias: every allocated page id is unique across slots
            held = [p for s in live for p in pool.allocated(s)]
            assert len(held) == len(set(held)), "page aliased across slots"
            # no-leak: free + allocated partitions the pool exactly
            assert pool.n_free + len(held) == n_pages
            # reservations stay backed: growth can never fail
            assert pool.available() >= 0
        for slot in list(live):
            pool.release(slot)
        assert pool.n_free == n_pages and pool.in_use == 0

    def test_overcommit_and_overgrow_rejected(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=4, span=16))
        pool.reserve(0, 3)
        assert not pool.can_reserve(2)  # only 1 page unbacked
        with pytest.raises(RuntimeError):
            pool.reserve(1, 2)
        pool.reserve(1, 1)
        with pytest.raises(RuntimeError):
            pool.grow_to(1, 2)  # beyond its reservation
        pool.release(0)
        assert pool.can_reserve(3)

    def test_pages_for_len_ring_folds(self):
        layout = PageLayout(page_size=8, n_pages=16, span=32)  # e.g. window 32
        assert layout.pages_for_len(0) == 0
        assert layout.pages_for_len(1) == 1
        assert layout.pages_for_len(8) == 1
        assert layout.pages_for_len(9) == 2
        assert layout.pages_for_len(32) == 4
        assert layout.pages_for_len(500) == 4  # ring reuse, bounded set
        assert layout.max_pages == 4 and layout.trash == 16


# ==========================================================================
# Refcounted sharing + copy-on-write invariants
# ==========================================================================
def _chain(prompt_id: int, n: int) -> list[bytes]:
    """A deterministic prefix-key chain standing in for prefix_page_keys."""
    return [f"p{prompt_id}-{j}".encode() for j in range(n)]


class TestRefcountCoWProperties:
    def _check(self, pool, live):
        """The module-docstring invariants, recomputed from scratch."""
        n_pages = pool.layout.n_pages
        # conservation: free + cached + distinct-in-use partitions the pool
        assert pool.n_free + pool.n_cached + pool.in_use == n_pages
        # refcount == number of slot table entries mapping the page, and a
        # page reaches the free/cached sets only at refcount zero
        counts: dict[int, int] = {}
        for s in live:
            for pid in pool.allocated(s):
                counts[pid] = counts.get(pid, 0) + 1
        assert counts == {pid: pool.refcount(pid) for pid in counts}
        assert all(r > 0 for r in counts.values())
        # the incremental owed-backing counter equals the recomputed sum
        assert pool._owed == pool.owed_recomputed()
        assert pool.available() >= 0

    @settings(max_examples=30)
    @given(
        n_pages=st.integers(min_value=2, max_value=32),
        page_size=st.integers(min_value=1, max_value=8),
        seed=st.integers(min_value=0, max_value=10_000),
    )
    def test_random_share_cow_traffic(self, n_pages, page_size, seed):
        """Random adopt/grow/register/prepare_write/release traffic keeps
        refcounts exact, conserves pages, keeps the incremental owed
        counter equal to its recomputation, and leaves written ranges
        exclusively owned."""
        layout = PageLayout(
            page_size=page_size, n_pages=n_pages, span=n_pages * page_size
        )
        pool = PagePool(layout)
        rng = np.random.default_rng(seed)
        live: dict[int, list[bytes]] = {}  # slot -> its prompt key chain
        next_slot = 0
        for _ in range(150):
            op = rng.integers(0, 4)
            if op == 0:  # admit, scheduler-style: pre-check worst case,
                # reserve nothing, adopt the indexed prefix run, extend
                keys = _chain(int(rng.integers(0, 3)), int(rng.integers(1, 5)))
                if pool.can_reserve(len(keys)):
                    pool.reserve(next_slot, 0)
                    adopted = pool.adopt_prefix(next_slot, keys)
                    target = int(rng.integers(adopted, len(keys) + 1))
                    assert pool.extend_to(next_slot, target)
                    live[next_slot] = keys
                    next_slot += 1
            elif op == 1 and live:  # grow + register full pages
                slot = int(rng.choice(list(live)))
                held = len(pool.allocated(slot))
                target = int(rng.integers(held, pool._reserved[slot] + 1))
                pool.grow_to(slot, target)
                for j in range(len(pool.allocated(slot))):
                    if j < len(live[slot]) and rng.integers(0, 2):
                        pool.register_page(slot, j, live[slot][j])
            elif op == 2 and live:  # write a random token range: CoW
                slot = int(rng.choice(list(live)))
                held = pool.allocated(slot)
                if held:
                    start = int(rng.integers(0, len(held) * page_size))
                    stop = int(rng.integers(start + 1, len(held) * page_size + 1))
                    need = {j for j in range(start // page_size,
                                             (stop - 1) // page_size + 1)}
                    shared = sum(
                        1 for j in need if pool.refcount(held[j]) > 1
                    )
                    # Forks are unreserved allocations: only fork within the
                    # unreserved headroom, as scheduler traffic does (writes
                    # land past the adopted span, so shared forks are rare).
                    if shared <= pool.available():
                        pool.prepare_write(slot, start, stop)
                        held = pool.allocated(slot)
                        for j in need:
                            # written pages are exclusively owned + unindexed
                            assert pool.refcount(held[j]) == 1
                            assert held[j] not in pool._key_of
            elif op == 3 and live:  # retire
                slot = int(rng.choice(list(live)))
                pool.release(slot)
                del live[slot]
            self._check(pool, live)
        for slot in list(live):
            pool.release(slot)
        self._check(pool, {})
        # cached pages are recyclable: taking everything drains the pool
        assert pool.n_free + pool.n_cached == n_pages

    def test_adopt_longest_indexed_run_and_revival(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=8, span=32))
        keys = _chain(0, 4)
        pool.reserve(0, 0)
        assert pool.extend_to(0, 3) and pool.grow_to(0, 3)
        for j in range(3):
            pool.register_page(0, j, keys[j])
        pool.release(0)  # refcount zero -> indexed pages park in cached LRU
        assert pool.n_cached == 3 and pool.in_use == 0
        pool.reserve(1, 0)
        assert pool.adopt_prefix(1, keys) == 3  # keys[3] unindexed: run stops
        assert pool.n_cached == 0 and pool.in_use == 3
        assert [pool.refcount(p) for p in pool.allocated(1)] == [1, 1, 1]
        # adoption raised reservation with allocation: owed unchanged
        assert pool._owed == 0 == pool.owed_recomputed()

    def test_register_first_wins(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=8, span=32))
        key = _chain(7, 1)[0]
        for slot in (0, 1):
            pool.reserve(slot, 1)
            pool.grow_to(slot, 1)
        assert pool.register_page(0, 0, key)
        assert not pool.register_page(1, 0, key)  # concurrent same prompt
        assert not pool.register_page(0, 0, key)  # idempotent
        pool.reserve(2, 0)
        assert pool.adopt_prefix(2, [key]) == 1
        assert pool.allocated(2) == pool.allocated(0) != pool.allocated(1)

    def test_shared_write_always_forks(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=8, span=32))
        keys = _chain(0, 2)
        pool.reserve(0, 2)
        pool.grow_to(0, 2)
        for j in range(2):
            pool.register_page(0, j, keys[j])
        pool.reserve(1, 0)
        assert pool.adopt_prefix(1, keys) == 2
        shared = list(pool.allocated(1))
        assert shared == pool.allocated(0)
        forks = pool.prepare_write(1, 0, 5)  # touches pages 0 and 1
        assert [(j, old) for j, old, _ in forks] == [(0, shared[0]), (1, shared[1])]
        assert pool.allocated(1) != pool.allocated(0)
        assert all(pool.refcount(p) == 1 for p in pool.allocated(0))
        assert all(pool.refcount(p) == 1 for p in pool.allocated(1))
        assert pool.cow_forks == 2
        # owner's copies stay indexed; a third adopter still hits them
        pool.reserve(2, 0)
        assert pool.adopt_prefix(2, keys) == 2
        assert pool.allocated(2) == pool.allocated(0)

    def test_release_frees_only_at_refcount_zero(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=4, span=16))
        key = _chain(0, 1)[0]
        pool.reserve(0, 1)
        pool.grow_to(0, 1)
        pool.register_page(0, 0, key)
        pool.reserve(1, 0)
        assert pool.adopt_prefix(1, [key]) == 1
        pid = pool.allocated(0)[0]
        pool.release(0)
        assert pool.refcount(pid) == 1 and pool.n_cached == 0  # still held by 1
        pool.release(1)
        assert pool.refcount(pid) == 0 and pool.n_cached == 1  # parked, indexed

    def test_cached_lru_eviction_unindexes(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=2, span=8))
        keys = _chain(0, 2)
        pool.reserve(0, 2)
        pool.grow_to(0, 2)
        for j in range(2):
            pool.register_page(0, j, keys[j])
        pool.release(0)
        assert pool.n_cached == 2 and pool.n_free == 0
        # a fresh private allocation must evict the LRU cached page
        pool.reserve(1, 1)
        pool.grow_to(1, 1)
        assert pool.cache_evictions == 1
        pool.reserve(2, 0)
        # the evicted (oldest) page left the index; the newer one survives
        assert pool.adopt_prefix(2, keys) == 0  # chain broken at keys[0]
        assert keys[0] not in pool._index and keys[1] in pool._index


# ==========================================================================
# Token identity: paged scheduler vs static engine, across state families
# ==========================================================================
class TestPagedTokenIdentity:
    @pytest.mark.parametrize(
        "arch",
        [
            "llama3.2-3b",  # dense GQA
            "qwen3-8b",  # dense GQA, per-head q/k RMSNorm
            "qwen2.5-14b",  # dense GQA, q/k/v bias
            "recurrentgemma-2b",  # windowed ring KV + recurrent hybrid
            "deepseek-v2-236b",  # MLA (per-slot path behind same interface)
            "xlstm-1.3b",  # pure recurrent: zero pages
            "llama4-scout-17b-a16e",  # MoE, scan-stacked groups
        ],
    )
    def test_greedy_paged_matches_static(self, arch):
        cfg, params = _params_for(arch)
        eng = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=5, cache_len=64, page_size=8),
        )
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(7), (3, 9), 0, cfg.vocab_size)
        }
        np.testing.assert_array_equal(
            eng.generate(batch).tokens, eng.generate_static(batch).tokens
        )

    def test_ring_window_prompt_longer_than_window_paged(self):
        """Windowed arch, prompt > window: ring-folded pages match static."""
        cfg, params = _params_for("recurrentgemma-2b")
        assert cfg.window_size and cfg.window_size < 40
        eng = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=4, cache_len=64, page_size=8),
        )
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, cfg.vocab_size)
        }
        np.testing.assert_array_equal(
            eng.generate(batch).tokens, eng.generate_static(batch).tokens
        )


# ==========================================================================
# The cache layout follows the model
# ==========================================================================
@pytest.mark.parametrize("arch", list_archs())
def test_layout_follows_the_model(arch):
    """No option picks the cache layout: a Scheduler built with defaults
    holds a page table and page-pool leaves exactly when the model's
    layers page, and reports page bytes exactly then."""
    cfg, params = _params_for(arch)
    n_slots, cache_len, page = 2, 64, 8
    span = model_page_span(cfg, cache_len)
    sched = Scheduler(
        cfg, params, ShardingCtx.null(),
        SchedulerConfig(n_slots=n_slots, cache_len=cache_len, page_size=page),
    )
    state = sched._states
    n_pages = n_slots * -(-span // page)  # capacity parity
    pools = [
        x for x in jax.tree.leaves(state["layers"])
        if x.ndim >= 4 and x.shape[-4:-2] == (n_pages + 1, page)
    ]
    assert ("page_table" in state) == (span > 0)
    assert bool(pools) == (span > 0)
    assert sched.mem.paged == (span > 0)
    assert (set(sched.paged_cache_bytes().values()) == {0}) == (span == 0)


# ==========================================================================
# Admission against pool capacity (OOM backpressure)
# ==========================================================================
class TestPoolBackpressure:
    def test_small_pool_defers_admission_and_stays_correct(self):
        """A pool too small for two worst-case requests serializes them:
        free slots alone don't admit, results still match solo runs."""
        cfg, params = _params_for("llama3.2-3b")
        page = 8
        # Each request worst-cases at ceil((9 + 8)/8) = 3 pages; pool of 4
        # pages fits one at a time even though 2 slots are free.
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=page, n_pages=4),
        )
        prompts = _prompts(cfg, [9, 9], seed=3)
        r0 = sched.submit(Request(prompts[0], max_new_tokens=8))
        r1 = sched.submit(Request(prompts[1], max_new_tokens=8))
        sched.step()
        assert sched.num_active == 1 and sched.pending == 1, (
            "second request must defer on pool capacity, not slot count"
        )
        assert sched.stats()["deferred_admissions"] > 0
        sched.run()
        solo = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=8, cache_len=64, page_size=page),
        )
        for rid, p in ((r0, prompts[0]), (r1, prompts[1])):
            expect = solo.generate_static({"tokens": p[None, :]}).tokens[0].tolist()
            assert sched.result(rid).tokens == expect

    def test_never_admissible_request_fails_fast(self):
        """A request whose worst case exceeds the whole pool must raise a
        clear error instead of deferring forever (run() would spin)."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8, n_pages=2),
        )
        sched.submit(Request(_prompts(cfg, [20])[0], max_new_tokens=8))
        with pytest.raises(RuntimeError, match="pool has only 2"):
            sched.run()

    def test_zero_page_models_skip_pool(self):
        """Pure-recurrent models need no pages; the scheduler takes the
        per-slot path with no pool at all."""
        cfg, params = _params_for("xlstm-1.3b")
        assert model_page_span(cfg, 64) == 0
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64),
        )
        assert sched.pool is None
        sched.submit(Request(_prompts(cfg, [6])[0], max_new_tokens=3))
        [rs] = sched.run()
        assert len(rs.tokens) == 3


# ==========================================================================
# Shape stability + compile counts
# ==========================================================================
class TestPagedNoRecompile:
    def test_single_decode_trace_across_churn_and_page_growth(self):
        """Joins, retirements, and page-table growth (decode crossing page
        boundaries) must never retrace the decode step: the page table is a
        fixed-shape int32 array whose values change."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            # page_size 4: every request crosses several page boundaries
            SchedulerConfig(n_slots=2, cache_len=64, page_size=4),
        )
        prompts = _prompts(cfg, [4, 11, 7, 5], seed=4)
        sched.submit(Request(prompts[0], max_new_tokens=6))
        sched.submit(Request(prompts[1], max_new_tokens=9))
        for _ in range(3):
            sched.step()
        sched.submit(Request(prompts[2], max_new_tokens=7))
        sched.submit(Request(prompts[3], max_new_tokens=3))
        sched.run()
        assert sched.stats()["finished"] == 4
        assert sched.decode_traces == 1, (
            f"decode step retraced {sched.decode_traces}x; joins/retires/"
            "page-growth must only change array values"
        )
        # Growth actually happened: some slot ended holding > 1 page worth.
        assert sched.pool.peak_in_use >= 3

    def test_prefill_buckets_bound_compiles(self):
        """Many distinct prompt lengths inside one power-of-two bucket must
        share a single prefill and a single admit compilation."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, min_bucket=16),
        )
        assert sched._bucketed
        for p in _prompts(cfg, [9, 10, 11, 12, 13, 16], seed=5):  # all -> bucket 16
            sched.submit(Request(p, max_new_tokens=2))
        sched.run()
        assert sched.prefill_traces == 1, sched.prefill_traces
        assert sched.admit_traces == 1, sched.admit_traces
        sched.submit(Request(_prompts(cfg, [17], seed=6)[0], max_new_tokens=2))
        sched.run()  # next bucket: exactly one more of each
        assert sched.prefill_traces == 2 and sched.admit_traces == 2

    def test_buckets_disabled_for_recurrent_models(self):
        """Recurrent states would absorb pad tokens; bucketing auto-disables
        and prefill compiles per exact length (correctness over compiles)."""
        cfg, params = _params_for("recurrentgemma-2b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(), SchedulerConfig(n_slots=1, cache_len=64)
        )
        assert not sched._bucketed


# ==========================================================================
# Scheduler stats & result retention (satellite)
# ==========================================================================
class TestStatsAndEviction:
    def test_cumulative_stats_survive_eviction(self):
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, keep_finished=2),
        )
        rids = [
            sched.submit(Request(p, max_new_tokens=3))
            for p in _prompts(cfg, [4, 5, 6, 7, 8], seed=7)
        ]
        results = sched.run()
        st_ = sched.stats()
        assert st_["finished"] == 5, "cumulative count must survive eviction"
        assert st_["generated_tokens"] == sum(len(r.tokens) for r in results) == 15
        assert st_["retained"] == 2
        # Oldest results were evicted: clear error, not a bare KeyError.
        with pytest.raises(KeyError, match="evicted \\(keep_finished=2\\)"):
            sched.result(rids[0])
        sched.result(rids[-1])  # newest still retained
        with pytest.raises(KeyError, match="unknown request id"):
            sched.result(99)

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_decode_page_counters_match_hand_count(self, backend):
        """Under the Pallas backend each decode step adds to
        ``decode_pages_walked`` the pages the paged decode kernel reads (a
        live slot: up to the page holding its position; a free or retired
        slot: none) and to ``decode_pages_table`` the table's pages (3 slots
        x 4). The XLA gather reads the whole table, so it reports None."""
        cfg, params = _params_for("llama3.2-3b")
        cfg = replace(cfg, attn_backend=backend)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=3, cache_len=64, page_size=16),
        )
        a, b, c = _prompts(cfg, [5, 20, 40], seed=3)
        sched.submit(Request(a, max_new_tokens=6))  # decodes at 5..9
        sched.submit(Request(b, max_new_tokens=3))  # decodes at 20, 21
        walked, table = [], []
        for step in range(6):
            if step == 2:  # admitted into b's retired slot; decodes at 40..42
                sched.submit(Request(c, max_new_tokens=4))
            sched.step()
            walked.append(sched.stats()["decode_pages_walked"])
            table.append(sched.stats()["decode_pages_table"])
        if backend == "xla":
            assert walked == table == [None] * 6
            return
        # a: 1 page a step; b: 2; c: 3; the sixth step decodes nothing.
        assert np.diff([0] + walked).tolist() == [1 + 2, 1 + 2, 1 + 3, 1 + 3, 1 + 3, 0]
        assert np.diff([0] + table).tolist() == [12, 12, 12, 12, 12, 0]

    def test_result_of_inflight_request(self):
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(), SchedulerConfig(n_slots=1, cache_len=64)
        )
        rid = sched.submit(Request(_prompts(cfg, [4])[0], max_new_tokens=8))
        sched.step()
        with pytest.raises(KeyError, match="not finished"):
            sched.result(rid)
        sched.run()
        assert sched.result(rid).done


# ==========================================================================
# Paged graft + paged decode kernel vs reference
# ==========================================================================
# The paged decode kernel's cases: page 8 and a 72-page (576-token) table, so
# dense slots walk 32-page (256-token) blocks. Each case lists its slots' live
# lengths; None is a retired slot, its table row all trash and its frozen
# position far past the table. Every case has 5 slots and a 96-page pool, so
# one compile per window serves them all.
_BLOCK = 256
_KERNEL_CASES = {
    "len-1": [1, 100, None, None, None],
    "len-page-1": [7, 100, None, None, None],
    "len-page": [8, 100, None, None, None],
    "len-page+1": [9, 100, None, None, None],
    "len-block": [_BLOCK, 100, None, None, None],
    "len-block+1": [_BLOCK + 1, 100, None, None, None],
    "len-table": [576, 100, None, None, None],
    "retired": [None, 37, None, 300, None],
    "table-wider-than-live": [20, 100, 65, 3, 40],
}
_paged_decode_op = jax.jit(
    kops.paged_decode_attention_op, static_argnames=("n_lp", "window")
)


class TestPagedGraftAndKernel:
    def test_graft_pages_dense_left_align(self):
        P1, page, S = 5, 4, 6
        pool = jnp.zeros((P1, page, 2, 3), jnp.bfloat16)
        src = jnp.arange(S * 2 * 3, dtype=jnp.float32).reshape(1, S, 2, 3) + 1.0
        ids = jnp.asarray([2, 0, 4, 4], jnp.int32)  # 2 real pages, trash-padded
        out = graft_pages_leaf(pool, src, ids, S, cap=16, page_size=page)
        got = np.concatenate([np.asarray(out[2], np.float32), np.asarray(out[0], np.float32)])
        np.testing.assert_array_equal(got[:S], np.asarray(src[0].astype(jnp.bfloat16), np.float32))
        np.testing.assert_array_equal(got[S:], 0.0)

    def test_graft_pages_ring_fold_with_traced_len(self):
        """Windowed leaf, prompt > window: last W positions land at p % W,
        and a traced prompt_len produces the same pages as a static one."""
        W, page, S = 8, 4, 13
        pool = jnp.zeros((4, page, 2, 1), jnp.float32)
        src = jnp.arange(S * 2, dtype=jnp.float32).reshape(1, S, 2, 1)
        ids = jnp.asarray([1, 2, 3, 3], jnp.int32)
        static = graft_pages_leaf(pool, src, ids, S, cap=W, page_size=page)
        traced = jax.jit(
            lambda pl_, s_, n: graft_pages_leaf(pl_, s_, ids, n, cap=W, page_size=page)
        )(pool, src, jnp.asarray(S, jnp.int32))
        np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))
        ring = np.concatenate([np.asarray(static[1]), np.asarray(static[2])])
        for p in range(S - W, S):
            np.testing.assert_array_equal(ring[p % W], np.asarray(src[0, p]))

    @pytest.mark.parametrize("window", [0, 13, 16])
    @pytest.mark.parametrize("case", list(_KERNEL_CASES))
    def test_paged_kernel_matches_gather_reference(self, window, case):
        """The kernel walks only each slot's live pages, in blocks of
        pages: every live row equals the XLA gather over the whole table,
        and a retired row (trash-only table, frozen position far past the
        table) reads nothing: finite zeros."""
        lengths = _KERNEL_CASES[case]
        rng = np.random.default_rng(0)
        B, KV, G, D, page, MP = len(lengths), 2, 4, 16, 8, 72
        assert dec.pages_per_block(page, KV, D, 4, MP) * page == _BLOCK
        cap = window or MP * page
        held = [0 if n is None else -(-min(n, cap) // page) for n in lengths]
        P = 96  # allocatable pages; row P is the trash page
        ids = rng.permutation(P)
        pt = np.full((B, MP), P, np.int32)
        for b, h in enumerate(held):
            pt[b, :h], ids = ids[:h], ids[h:]
        cur = jnp.asarray([10**6 if n is None else n - 1 for n in lengths], jnp.int32)
        kp = jnp.asarray(rng.normal(size=(P + 1, page, KV, D)).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(P + 1, page, KV, D)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(B, 1, KV * G, D)).astype(np.float32))
        n_lp = MP if not window else -(-window // page)

        o = _paged_decode_op(q, kp, vp, jnp.asarray(pt), cur, n_lp=n_lp, window=window)

        # XLA reference: materialise the gather, mask by analytic positions.
        T = MP * page
        kg = kp[jnp.asarray(pt)].reshape(B, T, KV, D)
        vg = vp[jnp.asarray(pt)].reshape(B, T, KV, D)
        kb = jnp.broadcast_to(kg[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, KV * G, D)
        vb = jnp.broadcast_to(vg[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, KV * G, D)
        idx = jnp.arange(T)
        if window:
            k_pos = cur[:, None] - ((cur[:, None] - idx[None, :]) % window)
            k_pos = jnp.where(idx[None, :] < window, k_pos, -1)
        else:
            k_pos = jnp.broadcast_to(idx[None, :], (B, T))
        s = jnp.einsum("bhd,bthd->bht", q.reshape(B, KV * G, D), kb) * (D ** -0.5)
        valid = (k_pos <= cur[:, None]) & (k_pos >= 0)
        if window:
            valid = valid & (k_pos > cur[:, None] - window)
        s = jnp.where(valid[:, None, :], s, -1e30)
        ref = jnp.einsum("bht,bthe->bhe", jax.nn.softmax(s, -1), vb).reshape(B, 1, KV * G, D)
        live = np.asarray([n is not None for n in lengths])
        err = float(jnp.max(jnp.abs(o[live] - ref[live])))
        assert err < 2e-5, err
        assert np.isfinite(np.asarray(o)).all()
        assert not np.asarray(o)[~live].any()  # a retired row reads nothing

    def test_pallas_backend_end_to_end_paged_decode(self):
        """attn_backend=pallas routes paged decode through the kernel; greedy
        tokens must match the XLA gather path."""
        cfg, params = _params_for("llama3.2-3b")
        toks = []
        for backend in ("xla", "pallas"):
            c = replace(cfg, attn_backend=backend)
            sched = Scheduler(
                c, params, ShardingCtx.null(),
                SchedulerConfig(n_slots=2, cache_len=64, page_size=16),
            )
            for p in _prompts(cfg, [7, 12], seed=8):
                sched.submit(Request(p, max_new_tokens=4))
            toks.append([rs.tokens for rs in sched.run()])
        assert toks[0] == toks[1]


# ==========================================================================
# The step programs update the stacked pool in place
# ==========================================================================
def _pool_hlo(backend: str, mesh_shape) -> dict:
    """Compile the registry's decode and chunk programs on the CPU for a
    small paged config, the caller having turned state donation on, and
    read each program's HLO: its pool parameters, how many of them alias
    an output, and its dynamic-slice / dynamic-update-slice ops shaped as a
    whole layer of the pool (a layer's slice out of the stack, or the stack
    it is written back into). Four KV heads, so a 1x4 mesh splits them."""
    base = get_config("llama3.2-3b").reduced()
    cfg = replace(base, attn_backend=backend, n_kv_heads=base.n_heads)
    params = init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))
    sched = Scheduler(
        cfg, params, ShardingCtx.null(),
        SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=16,
                        mesh_shape=mesh_shape),
    )
    st, pr = sched._states, sched.programs
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    texts = {
        "decode": pr.decode.lower(
            sched.params, st, jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), bool)
        ),
        "chunk": pr.chunk.lower(
            sched.params, st["layers"], st["pos"], jnp.zeros((1, 16), jnp.int32),
            i32(0), i32(0), i32(16), i32(sched.mem.pt[0, :2]),
        ),
    }
    pool = jax.tree.leaves(st["layers"])[0]
    L, rows, page, kv, hd = pool.shape
    kv_local = kv // (mesh_shape[1] if mesh_shape else 1)
    layer_dims = f"{rows},{page},{kv_local},{hd}"

    def is_layer(dims: str) -> bool:
        return dims in (layer_dims, "1," + layer_dims)

    out = {}
    for name, lowered in texts.items():
        txt = lowered.compile().as_text()
        head = txt.splitlines()[0]
        params_part = head.split("entry_computation_layout={(", 1)[1].split(")->", 1)[0]
        shapes = re.findall(r"(\w+)\[([\d,]*)\]", params_part)
        pool_params = [
            i for i, (dt, dims) in enumerate(shapes)
            if dt == "bf16" and dims == f"{L},{rows},{page},{kv_local},{hd}"
        ]
        aliased = [i for i in pool_params if re.search(rf"\({i}, \{{\}}, may-alias\)", head)]
        # A slice's own shape; an update's, that of the value it writes.
        dims_of = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", txt))
        sliced = [
            l.strip()[:120] for l in txt.splitlines()
            if (m := re.search(r"= \w+\[([\d,]*)\]\S* dynamic-slice\(", l)) and is_layer(m[1])
            or (m := re.search(r"dynamic-update-slice\(%[\w.-]+, %([\w.-]+)", l))
            and is_layer(dims_of.get(m[1], ""))
        ]
        out[name] = {
            "pool_params": len(pool_params), "aliased": len(aliased),
            "whole_layer_slices": sliced,
        }
    return out


_MESH_HLO = """
import json, sys
sys.path.insert(0, {tests!r})
from repro.serve import programs
programs.donates_state = lambda: True
from test_serve_pages import _pool_hlo
print(json.dumps({{b: _pool_hlo(b, (1, 4)) for b in ("xla", "pallas")}}))
"""


@pytest.fixture(scope="module")
def mesh_pool_hlo():
    """Both backends' findings on a forced four-device CPU mesh, from one
    child process (the device count is fixed when JAX starts)."""
    root = Path(__file__).resolve().parents[1]
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(root / "src"), os.environ.get("PYTHONPATH", "")]),
    )
    code = _MESH_HLO.format(tests=str(root / "tests"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=600, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("devices", ["one-device", "mesh-1x4"])
def test_step_programs_update_the_pool_in_place(backend, devices, monkeypatch, request):
    """The decode and chunk programs carry the stacked pool through the
    layer scan: no layer's pool is sliced out and written back, and the
    donated pool parameters alias the pool outputs."""
    if devices == "one-device":
        from repro.serve import programs

        monkeypatch.setattr(programs, "donates_state", lambda: True)
        found = _pool_hlo(backend, None)
    else:
        found = request.getfixturevalue("mesh_pool_hlo")[backend]
    for name, f in found.items():
        assert f["pool_params"] == 2, (name, f)  # K and V of the one group
        assert f["aliased"] == f["pool_params"], (name, f)
        assert not f["whole_layer_slices"], (name, f)
