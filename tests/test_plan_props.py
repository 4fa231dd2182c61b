"""Property tests for the plan layer (serve/plan.py) against a real
MemoryManager (serve/memory.py).

Neither module imports JAX, so these properties run without compiling a
single program — the point of the layered split. Three families:

  * sizing: buckets are powers of two from fixed sets, pads never lose
    tokens, the planner's page demands never exceed what the
    MemoryManager's capacity queries said was available (no
    over-commit);
  * safety: decode plans never include a frozen slot, victim picks
    respect protection / shard locality / the younger-streamer rule;
  * determinism: plan -> execute -> plan over a fixed arrival trace is
    a pure function of the trace — two independent replays produce the
    same decision sequence and the same page-table state.
"""
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serve import plan as planlib
from repro.serve.memory import MemoryManager
from repro.serve.pages import PageLayout


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def _mem(page_size=8, n_pages=16, span=128, data_shards=1, n_slots=4):
    return MemoryManager(
        PageLayout(
            page_size=page_size, n_pages=n_pages, span=span,
            data_shards=data_shards,
        ),
        n_slots,
    )


# ==========================================================================
# Sizing
# ==========================================================================
class TestBucketLen:
    @given(
        token_len=st.integers(min_value=1, max_value=512),
        min_bucket=st.sampled_from([1, 4, 8, 16]),
        cache_len=st.sampled_from([64, 128, 256, 1024]),
    )
    @settings(max_examples=80)
    def test_bucketed_pad_never_loses_tokens(self, token_len, min_bucket, cache_len):
        if token_len > cache_len:
            return  # separate property below
        b = planlib.bucket_len(
            token_len, bucketed=True, min_bucket=min_bucket,
            cache_len=cache_len, prefix_len=0, long_ok=False,
        )
        assert b >= token_len
        assert b <= cache_len
        # Power of two unless clamped to the cache cap.
        assert _is_pow2(b) or b == cache_len

    @given(token_len=st.integers(min_value=1, max_value=512))
    @settings(max_examples=30)
    def test_unbucketed_is_identity(self, token_len):
        assert planlib.bucket_len(
            token_len, bucketed=False, min_bucket=8,
            cache_len=64, prefix_len=0, long_ok=False,
        ) == token_len

    @given(over=st.integers(min_value=1, max_value=100))
    @settings(max_examples=20)
    def test_past_cap_requires_long_ok(self, over):
        cache_len = 64
        try:
            planlib.bucket_len(
                cache_len + over, bucketed=True, min_bucket=8,
                cache_len=cache_len, prefix_len=0, long_ok=False,
            )
            raise AssertionError("expected RuntimeError past the cap")
        except RuntimeError:
            pass
        b = planlib.bucket_len(
            cache_len + over, bucketed=True, min_bucket=8,
            cache_len=cache_len, prefix_len=0, long_ok=True,
        )
        assert _is_pow2(b) and b >= cache_len + over


class TestChunkAndVerifySizing:
    @given(
        remaining=st.integers(min_value=1, max_value=400),
        chunk_budget=st.sampled_from([16, 32, 48, 100]),
        min_chunk=st.sampled_from([4, 8, 16]),
        start=st.integers(min_value=0, max_value=64),
    )
    @settings(max_examples=100)
    def test_chunk_plan_shapes_and_pages(self, remaining, chunk_budget, min_chunk, start):
        if min_chunk > chunk_budget:
            return
        mem = _mem(page_size=4, n_pages=64, n_slots=4)
        cp = planlib.plan_chunk(
            0, 0, start, remaining,
            chunk_budget=chunk_budget, min_chunk=min_chunk, mem=mem,
        )
        # Shapes come from the fixed pow2 set [min_chunk, pow2_floor(budget)].
        assert _is_pow2(cp.bucket)
        assert min_chunk <= cp.bucket <= planlib.pow2_floor(chunk_budget)
        assert 1 <= cp.n_real <= min(cp.bucket, remaining)
        # Page demand covers exactly the post-chunk prefix, no more.
        assert cp.need_pages == mem.pages_for_len(start + cp.n_real)
        assert _is_pow2(cp.n_lp) or cp.n_lp == mem.max_pages
        assert cp.n_lp >= max(cp.need_pages, 1)

    @given(
        k=st.integers(min_value=1, max_value=8),
        draft_k=st.integers(min_value=1, max_value=8),
        start=st.integers(min_value=0, max_value=100),
    )
    @settings(max_examples=60)
    def test_verify_plan_bucket_is_bounded(self, k, draft_k, start):
        if k > draft_k:
            return  # scheduler never drafts past draft_k
        mem = _mem(page_size=4, n_pages=64, n_slots=4)
        vp = planlib.plan_verify(0, 0, start, k, draft_k=draft_k, mem=mem)
        assert vp.n_real == k + 1
        assert _is_pow2(vp.bucket)
        assert vp.n_real <= vp.bucket <= planlib.pow2_ceil(draft_k + 1)
        assert vp.need_pages == mem.pages_for_len(start + k + 1)


# ==========================================================================
# No over-commit: plans vs MemoryManager capacity
# ==========================================================================
class TestNoOvercommit:
    @given(
        seed=st.integers(min_value=0, max_value=10_000),
        data_shards=st.sampled_from([1, 2]),
    )
    @settings(max_examples=40)
    def test_admission_plans_never_exceed_capacity(self, seed, data_shards):
        """Drive random reserve/grow/release traffic; whenever the planner
        says an admission fits, actually reserving and extending to the
        worst case must succeed — the capacity query is never optimistic."""
        import random

        rng = random.Random(seed)
        n_slots, n_pages = 4, 16
        mem = _mem(page_size=4, n_pages=n_pages, data_shards=data_shards,
                   n_slots=n_slots)
        live: set[int] = set()
        for _ in range(30):
            op = rng.random()
            free = [s for s in range(n_slots) if s not in live]
            if op < 0.5 and free:
                slot = rng.choice(free)
                n_worst = rng.randint(1, n_pages // data_shards)
                if planlib.can_admit_prefill(mem, slot, n_worst):
                    mem.reserve(slot, n_worst)
                    assert mem.extend_to(slot, n_worst), (
                        "planner said fit; pool disagreed"
                    )
                    mem.grow(slot, rng.randint(1, n_worst))
                    live.add(slot)
            elif op < 0.75 and live:
                slot = rng.choice(sorted(live))
                held = mem.held(slot)
                want = rng.randint(held, n_pages // data_shards)
                if planlib.can_resume_swap(mem, slot, want - held):
                    # available_for promised headroom: growth must land.
                    if mem.extend_to(slot, want):
                        mem.grow(slot, want)
            elif live:
                slot = rng.choice(sorted(live))
                mem.release(slot)
                live.discard(slot)
        # Conservation at drain.
        for slot in sorted(live):
            mem.release(slot)
        assert mem.in_use == 0
        assert mem.available_total() == n_pages

    @given(seed=st.integers(min_value=0, max_value=10_000))
    @settings(max_examples=40)
    def test_streaming_chunks_never_overcommit(self, seed):
        """Stream a random prompt chunk-by-chunk: every plan's page demand
        is backable exactly when extend_to says so; the page table mirror
        only ever maps pages the pool granted."""
        import random

        rng = random.Random(seed)
        mem = _mem(page_size=4, n_pages=8, n_slots=2)
        prompt_len = rng.randint(1, 40)
        mem.reserve(0, 0)
        start = 0
        while start < prompt_len:
            cp = planlib.plan_chunk(
                0, 0, start, prompt_len - start,
                chunk_budget=16, min_chunk=4, mem=mem,
            )
            if not mem.extend_to(0, cp.need_pages):
                break  # executor would defer/preempt here
            mem.grow(0, cp.need_pages)
            assert mem.held(0) == cp.need_pages
            mapped = [p for p in mem.pt[0] if p != mem.trash_of(0)]
            assert len(mapped) == cp.need_pages
            assert len(set(mapped)) == cp.need_pages  # no aliasing
            start += cp.n_real
        mem.release(0)
        assert mem.in_use == 0


# ==========================================================================
# Frozen slots and victim picks
# ==========================================================================
class TestDecodeRowsAndVictims:
    @given(
        mask=st.lists(st.booleans(), min_size=1, max_size=12),
        handled=st.lists(st.integers(min_value=0, max_value=11), max_size=6),
    )
    @settings(max_examples=60)
    def test_decode_rows_exclude_frozen_and_handled(self, mask, handled):
        rows = planlib.decode_rows(mask, handled)
        assert rows == tuple(sorted(rows))
        for r in rows:
            assert mask[r] and r not in set(handled)
        for i, a in enumerate(mask):
            if a and i not in set(handled):
                assert i in rows

    @given(
        seed=st.integers(min_value=0, max_value=10_000),
        n=st.integers(min_value=1, max_value=8),
        shard=st.sampled_from([None, 0, 1]),
    )
    @settings(max_examples=60)
    def test_pick_victim_safety(self, seed, n, shard):
        import random

        rng = random.Random(seed)
        views = [
            planlib.SlotView(
                slot=i, rid=rng.randint(0, 20),
                status=rng.choice(["active", "prefilling"]),
                t_admit=rng.random(), preemptable=rng.random() < 0.7,
                shard=rng.randint(0, 1),
            )
            for i in range(n)
        ]
        protect = rng.randrange(n)
        requester = rng.randint(0, 20)
        v = planlib.pick_victim(
            views, protect=protect, requester_rid=requester, shard=shard,
        )
        if v is None:
            return
        assert v != protect
        view = next(x for x in views if x.slot == v)
        if shard is not None:
            assert view.shard == shard
        eligible = [
            x for x in views
            if x.slot != protect and (shard is None or x.shard == shard)
        ]
        actives = [x for x in eligible if x.status == "active" and x.preemptable]
        if actives:
            # LRU among preemptable actives.
            assert view.status == "active" and view.preemptable
            assert view.t_admit == min(x.t_admit for x in actives)
        else:
            # Younger-streamer rule: only a streamer younger than the
            # requester, and the youngest of them.
            assert view.status == "prefilling"
            assert view.rid > requester
            assert view.rid == max(
                x.rid for x in eligible
                if x.status == "prefilling" and x.rid > requester
            )


# ==========================================================================
# Determinism: plan -> execute -> plan over a fixed arrival trace
# ==========================================================================
class TestDeterminism:
    @given(
        seed=st.integers(min_value=0, max_value=10_000),
        data_shards=st.sampled_from([1, 2]),
    )
    @settings(max_examples=25)
    def test_trace_replay_is_bit_identical(self, seed, data_shards):
        """Replay the same arrival trace through two independent
        plan+memory stacks: every decision and the final page-table state
        must match exactly (the plan layer has no hidden state)."""
        import random

        def run_trace():
            rng = random.Random(seed)
            mem = _mem(page_size=4, n_pages=16, data_shards=data_shards,
                       n_slots=4)
            decisions = []
            streams: dict[int, tuple[int, int]] = {}  # slot -> (start, len)
            rid = 0
            for _ in range(40):
                op = rng.random()
                free = [s for s in range(4) if s not in streams]
                if op < 0.4 and free:
                    slot = free[0]
                    plen = rng.randint(1, 30)
                    n_worst = mem.pages_for_len(plen + 8)
                    ok = planlib.can_admit_streaming(
                        mem, slot, n_worst, reservation_free=True
                    )
                    decisions.append(("admit", slot, n_worst, ok))
                    if ok:
                        mem.reserve(slot, 0)
                        streams[slot] = (0, plen)
                        rid += 1
                elif streams:
                    slot = sorted(streams)[0]
                    start, plen = streams[slot]
                    cp = planlib.plan_chunk(
                        slot, rid, start, plen - start,
                        chunk_budget=16, min_chunk=4, mem=mem,
                    )
                    decisions.append(("chunk", cp))
                    if mem.extend_to(slot, cp.need_pages):
                        mem.grow(slot, cp.need_pages)
                        start += cp.n_real
                        if start >= plen:
                            mem.release(slot)
                            del streams[slot]
                        else:
                            streams[slot] = (start, plen)
                    else:
                        mem.release(slot)
                        del streams[slot]
            return decisions, mem.pt.tolist(), mem.in_use

        a, b = run_trace(), run_trace()
        assert a == b
