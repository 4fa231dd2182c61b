"""Speculative decoding on the chunk-step substrate: drafter units, greedy
token identity across the model zoo's state families, accept/rollback
invariants (exact state restoration on rejection, accepted <= drafted, page
pool conservation), composition with preemption and prefix sharing, bounded
verify compiles, and stop tokens landing mid-draft."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import get_config
from repro.models import lm
from repro.models.schema import init_params
from repro.serve.draft import (
    Drafter,
    NgramDrafter,
    ReplayDrafter,
    ScriptDrafter,
)
from repro.serve.engine import Engine, ServeConfig
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx


def _params_for(name):
    cfg = get_config(name).reduced()
    return cfg, init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))


def _patterned(cfg, length, period=7, seed=0):
    """A prompt with short-range repetition so the n-gram drafter fires."""
    rng = np.random.default_rng(seed)
    pat = rng.integers(0, cfg.vocab_size, size=period).astype(np.int32)
    return np.tile(pat, length // period + 1)[:length]


def _solo(cfg, params, prompt, max_new, stop_token=-1):
    sched = Scheduler(
        cfg, params, ShardingCtx.null(),
        SchedulerConfig(n_slots=1, cache_len=64, page_size=8, chunk_budget=16),
    )
    rid = sched.submit(Request(prompt, max_new_tokens=max_new, stop_token=stop_token))
    sched.run()
    return sched.result(rid).tokens


def _rs(sched, rid):
    """The live RequestState for ``rid``, finished or in flight."""
    import itertools

    for rs in itertools.chain(
        sched._active.values(), sched._queue, sched._preempted
    ):
        if rs.rid == rid:
            return rs
    return sched.result(rid)


def _pool_conserved(sched):
    pool = sched.pool
    n = pool.layout.n_pages if hasattr(pool, "layout") else None
    if n is None:
        n = len(pool._free) + len(pool._cached) + len(pool._ref)
    assert len(pool._free) + len(pool._cached) + len(pool._ref) == n
    assert pool.owed_recomputed() == pool._owed
    return True


# ==========================================================================
# Drafter units
# ==========================================================================
class TestDrafters:
    def test_ngram_proposes_periodic_continuation(self):
        ctx = np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int32)
        d = NgramDrafter()
        assert d.propose(ctx, 3).tolist() == [3, 1, 2]

    def test_ngram_no_match_is_empty(self):
        d = NgramDrafter()
        assert d.propose(np.arange(10, dtype=np.int32), 4).size == 0

    def test_ngram_short_context(self):
        d = NgramDrafter()
        assert d.propose(np.array([5], np.int32), 4).size == 0

    def test_replay_prefix_match_and_miss(self):
        d = ReplayDrafter([np.array([5, 6, 7, 8, 9], np.int32)])
        assert d.propose(np.array([5, 6, 7], np.int32), 2).tolist() == [8, 9]
        assert d.propose(np.array([5, 6, 7], np.int32), 9).tolist() == [8, 9]
        assert d.propose(np.array([4, 6, 7], np.int32), 2).size == 0

    def test_script_pops_in_order_then_empty(self):
        d = ScriptDrafter([np.array([1, 2], np.int32), np.array([3], np.int32)])
        ctx = np.zeros(4, np.int32)
        assert d.propose(ctx, 4).tolist() == [1, 2]
        assert d.propose(ctx, 4).tolist() == [3]
        assert d.propose(ctx, 4).size == 0
        assert d.calls == 3


class _MultiOracleNoise(Drafter):
    """Proposes the true continuation of the matching sequence with the tail
    corrupted after ``n_correct`` tokens (popped per call, 0 when exhausted)
    — drives the verify pass to exactly chosen accept lengths."""

    def __init__(self, seqs, n_correct):
        self.seqs = [np.asarray(s, np.int64) for s in seqs]
        self.n_correct = list(n_correct)

    def propose(self, context, k):
        ctx = np.asarray(context, np.int64)
        L = len(ctx)
        for full in self.seqs:
            if L < len(full) and np.array_equal(full[:L], ctx):
                cont = full[L : L + k].astype(np.int32).copy()
                nc = self.n_correct.pop(0) if self.n_correct else 0
                # out-of-vocab sentinel: never equals a greedy token, forces
                # rejection at exactly position nc
                cont[min(nc, len(cont)):] = -2
                return cont
        return np.zeros((0,), np.int32)


# ==========================================================================
# Token identity: speculative decode vs static reference, across families
# ==========================================================================
class TestSpecTokenIdentity:
    @pytest.mark.parametrize(
        "arch",
        [
            "llama3.2-3b",  # dense GQA, paged
            "recurrentgemma-2b",  # windowed ring KV + RG-LRU (replay rollback)
            "deepseek-v2-236b",  # MLA compressed cache (per-slot path)
            "xlstm-1.3b",  # pure recurrent, zero pages (replay rollback)
            "llama4-scout-17b-a16e",  # MoE, scan-stacked groups
        ],
    )
    def test_spec_greedy_matches_static(self, arch):
        """Whatever the drafter proposes, greedy acceptance emits exactly the
        sequential-decode tokens — asserted against the static engine on
        every cache family. The drafter is an oracle with a corrupted tail
        (accept lengths cycling 0..3), so full accepts, partial accepts, and
        full rejections — including the recurrent/windowed replay rollback —
        all fire on every arch."""
        cfg, params = _params_for(arch)
        batch = {"tokens": np.stack([
            _patterned(cfg, 33, period=5, seed=1),
            _patterned(cfg, 33, period=3, seed=2),
        ])}
        ref = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=6, cache_len=64, page_size=8,
                        chunk_budget=16),
        ).generate_static(batch)
        seqs = [
            np.concatenate([batch["tokens"][i], ref.tokens[i]]) for i in range(2)
        ]
        eng = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=6, cache_len=64, page_size=8,
                        chunk_budget=16, speculative=True, draft_k=4,
                        drafter=_MultiOracleNoise(seqs, [0, 1, 2, 3] * 8)),
        )
        np.testing.assert_array_equal(eng.generate(batch).tokens, ref.tokens)
        sched = eng._schedulers[2]
        assert sched.drafted_tokens_total > 0, "speculation never fired"
        assert sched.accepted_tokens_total <= sched.drafted_tokens_total


# ==========================================================================
# Accept/rollback invariants
# ==========================================================================
class TestAcceptRollback:
    @pytest.fixture(scope="class")
    def setup(self):
        cfg, params = _params_for("llama3.2-3b")
        prompt = _patterned(cfg, 12, seed=4)
        solo = _solo(cfg, params, prompt, 12)
        return cfg, params, prompt, solo

    def test_full_rejection_restores_state_exactly(self, setup):
        """A fully rejected draft must leave the scheduler in the identical
        host-visible state as a plain decode step: same tokens, same cached
        position, same page table and refcounts."""
        cfg, params, prompt, _ = setup

        def build():
            s = Scheduler(
                cfg, params, ShardingCtx.null(),
                SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                                chunk_budget=16, speculative=True, draft_k=4),
            )
            rid = s.submit(Request(prompt, max_new_tokens=8))
            s.step()  # prefill + first token
            return s, rid

        # The plain side speculates too, with a drafter that never proposes:
        # its step is a plain decode, read before the next step is planned,
        # like the verify step it is compared with. Without speculation the
        # next step would already be dispatched, its positions and pages
        # one step ahead.
        spec, rid_s = build()
        plain, rid_p = build()
        spec.set_drafter(ScriptDrafter([np.full(4, -2, np.int32)]))
        plain.set_drafter(ScriptDrafter([]))
        spec.step()
        plain.step()
        assert spec.total_spec_steps == 1
        assert spec.accepted_tokens_total == 0
        rs, rp = _rs(spec, rid_s), _rs(plain, rid_p)
        assert rs.tokens == rp.tokens, "rejected step emitted wrong tokens"
        assert spec._pos_host.tolist() == plain._pos_host.tolist()
        assert dict(spec.pool._ref) == dict(plain.pool._ref)
        assert spec.pool._allocated == plain.pool._allocated
        np.testing.assert_array_equal(spec._pt, plain._pt)
        assert _pool_conserved(spec)
        # and the run still finishes token-identically
        spec.run(), plain.run()
        assert spec.result(rid_s).tokens == plain.result(rid_p).tokens

    @settings(max_examples=8, deadline=None)
    @given(st.lists(st.integers(min_value=0, max_value=4), min_size=1, max_size=12))
    def test_partial_accepts_preserve_identity_and_pool(self, setup, n_correct):
        """For every per-step accept length the drafter can force, the run
        stays token-identical to the reference and the page pool conserves
        pages at every step boundary."""
        cfg, params, prompt, solo = setup
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True, draft_k=4),
        )
        sched.set_drafter(
            _MultiOracleNoise([np.concatenate([prompt, solo])], n_correct)
        )
        rid = sched.submit(Request(prompt, max_new_tokens=12))
        while not _rs(sched, rid).done:
            sched.step()
            assert _pool_conserved(sched)
            rs = _rs(sched, rid)
            if rs.tokens:
                assert rs.tokens == solo[: len(rs.tokens)]
        assert sched.result(rid).tokens == solo
        assert sched.accepted_tokens_total <= sched.drafted_tokens_total
        assert len(sched.pool._ref) == 0, "finished run must free all pages"

    def test_budget_clamps_drafts_near_max_new(self, setup):
        """Near max_new_tokens the draft window shrinks so a spec step can
        never overshoot the token budget."""
        cfg, params, prompt, solo = setup
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True, draft_k=8),
        )
        sched.set_drafter(ReplayDrafter([np.concatenate([prompt, solo])]))
        rid = sched.submit(Request(prompt, max_new_tokens=5))
        sched.run()
        assert sched.result(rid).tokens == solo[:5]

    def test_sampling_requests_never_speculate(self, setup):
        cfg, params, prompt, _ = setup
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True),
        )
        rid = sched.submit(Request(prompt, max_new_tokens=6, temperature=0.8))
        sched.run()
        assert sched.total_spec_steps == 0
        assert len(sched.result(rid).tokens) == 6


# ==========================================================================
# Composition: preemption, prefix sharing, bounded compiles, stop tokens
# ==========================================================================
class TestSpecComposition:
    @pytest.mark.parametrize("policy", ["swap", "recompute"])
    def test_preempt_resume_mid_speculation(self, policy):
        """A pool too small for both live footprints preempts mid-decode
        while speculation is active; victims resume token-identical."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = [_patterned(cfg, 24, seed=3), _patterned(cfg, 30, seed=5)]
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=8,
                            chunk_budget=16, preemption=policy,
                            speculative=True, draft_k=4),
        )
        rids = [sched.submit(Request(p, max_new_tokens=12)) for p in prompts]
        sched.run()
        assert sched.preemptions_total > 0, "workload must actually preempt"
        assert sched.drafted_tokens_total > 0, "speculation never fired"
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 12), (
                f"request {rid} diverged under {policy} preemption"
            )

    def test_spec_with_prefix_sharing(self):
        """Adopted prefix pages are CoW-protected from verify writes: two
        requests sharing a prompt prefix both match the reference with
        speculation on."""
        cfg, params = _params_for("llama3.2-3b")
        shared = _patterned(cfg, 16, seed=8)
        prompts = [
            np.concatenate([shared, _patterned(cfg, 8, seed=s)]) for s in (11, 12)
        ]
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16, prefix_sharing=True,
                            speculative=True, draft_k=4),
        )
        # primer registers the shared prefix pages
        primer = sched.submit(Request(shared, max_new_tokens=1))
        sched.run()
        assert sched.result(primer).done
        # oracle drafts force accepted multi-token verify writes right next
        # to (and CoW-guarded away from) the adopted prefix pages
        sched.set_drafter(
            ReplayDrafter(
                [np.concatenate([p, _solo(cfg, params, p, 8)]) for p in prompts]
            )
        )
        rids = [sched.submit(Request(p, max_new_tokens=8)) for p in prompts]
        sched.run()
        assert sched.prefix_hits > 0, "workload must adopt shared pages"
        assert sched.drafted_tokens_total > 0
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 8)

    def test_verify_traces_bounded(self):
        """One verify compile per (k-bucket, page-bucket): many requests with
        wildly varying draft lengths stay within the pow2 ladder."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True, draft_k=4),
        )
        for s in range(6):
            sched.submit(Request(_patterned(cfg, 9 + 5 * s, seed=s),
                                 max_new_tokens=10))
        sched.run()
        assert sched.drafted_tokens_total > 0
        # k+1 in [2..5] -> buckets {2, 4, 8}; pages bucket to <= 2 values
        assert sched.verify_traces <= 6, (
            f"verify compiled {sched.verify_traces} traces — unbounded"
        )

    def test_stop_token_mid_draft(self):
        """When the stop token lands inside an accepted run, emission must
        halt at it exactly — trailing accepted tokens are discarded."""
        cfg, params = _params_for("llama3.2-3b")
        prompt = _patterned(cfg, 12, seed=4)
        ref = _solo(cfg, params, prompt, 10)
        stop = ref[4]
        ref_stop = _solo(cfg, params, prompt, 10, stop_token=stop)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True, draft_k=6),
        )
        sched.set_drafter(ReplayDrafter([np.concatenate([prompt, ref])]))
        rid = sched.submit(Request(prompt, max_new_tokens=10, stop_token=stop))
        sched.run()
        assert sched.result(rid).tokens == ref_stop
        assert sched.result(rid).finish_reason == "stop"
        assert sched.accepted_tokens_total > 0, "oracle draft must accept"

    def test_stats_surface_spec_counters(self):
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=1, cache_len=64, page_size=8,
                            chunk_budget=16, speculative=True, draft_k=4),
        )
        rid = sched.submit(Request(_patterned(cfg, 15, seed=2), max_new_tokens=8))
        sched.run()
        st_ = sched.stats()
        for key in ("spec_steps", "spec_replays", "spec_fallbacks",
                    "drafted_tokens", "accepted_tokens", "verify_traces"):
            assert key in st_
        assert st_["drafted_tokens"] >= st_["accepted_tokens"]
        assert sched.result(rid).drafted >= sched.result(rid).accepted


# ==========================================================================
# Donated state: no caller reads a program's input state after the call
# ==========================================================================
class TestDonatedState:
    @pytest.mark.parametrize(
        "arch, spec",
        [
            ("llama3.2-3b", True),  # verify (undonated) and position fixups
            ("llama3.2-3b", False),  # no verify: every pool call in place
            ("recurrentgemma-2b", True),  # replay from the pre-verify state
        ],
        ids=["dense-spec", "dense-plain", "windowed-spec"],
    )
    def test_churn_stays_identical_with_donated_state(self, arch, spec, monkeypatch):
        """With the state donated to every program that updates it (verify
        aside), a run that forks a shared page, swaps a request out and in,
        and verifies drafts (replaying rejected ones where the state
        advanced) emits the static engine's tokens; the donated inputs are
        gone after each call, so a caller that read one would fail. Verify
        calls are the only pool calls that copy."""
        from repro.serve import programs

        monkeypatch.setattr(programs, "donates_state", lambda: True)
        cfg, params = _params_for(arch)
        shared = _patterned(cfg, 16, period=5, seed=8)
        plen = 26 if arch == "llama3.2-3b" else 33
        prompts = [
            np.concatenate([shared, _patterned(cfg, plen - 16, period=3, seed=s)])
            for s in (11, 12)
        ]
        batch = {"tokens": np.stack(prompts)}
        ref = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=10, cache_len=64, page_size=8, chunk_budget=16),
        ).generate_static(batch).tokens
        seqs = [np.concatenate([prompts[i], ref[i]]) for i in range(2)]
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=7,
                            chunk_budget=16, preemption="swap", speculative=spec,
                            draft_k=4,
                            drafter=_MultiOracleNoise(seqs, [0, 1, 2, 3] * 8)),
        )
        r0 = sched.submit(Request(prompts[0], max_new_tokens=10))
        for _ in range(3):  # stream prompt 0 in; its full pages get indexed
            sched.step()
        r1 = sched.submit(Request(prompts[1], max_new_tokens=10))
        before = jax.tree.leaves(sched._states["layers"])
        sched.step()
        assert all(x.is_deleted() for x in before), "state was not donated"
        if arch == "llama3.2-3b":
            # Fork the first page r1 adopted: the copy-on-write program runs
            # on the donated pool mid-run.
            slot = next(s for s, rs in sched._active.items() if rs.rid == r1)
            forks = sched.mem.prepare_write(slot, 0, 1)
            assert forks, "r1 must hold a shared page"
            sched._apply_cow(forks)
            assert sched.cow_traces == 1
        sched.run()
        assert [sched.result(r).tokens for r in (r0, r1)] == [list(t) for t in ref]
        st = sched.stats()
        assert st["preemptions"] > 0 and st["swap_traces"] > 0
        assert (st["spec_steps"] > 0) == spec
        if arch == "recurrentgemma-2b":
            assert st["spec_replays"] > 0
        assert st["pool_copy_calls"] == st["spec_steps"]
        in_place = st["decode_steps"] + st["chunk_steps"] + st["spec_replays"]
        assert st["pool_inplace_calls"] > in_place
