"""Unified token-budget serving step: chunked-prefill token identity with
the static engine across the model zoo's state families, decode-not-stalled
scheduling behavior, bounded chunk-bucket compiles, page-aware preemption
(swap and recompute) with greedy identity for preempted-then-resumed
requests, and reservation-free pool accounting."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import lm
from repro.models.schema import init_params
from repro.serve.engine import Engine, ServeConfig
from repro.serve.pages import PageLayout, PagePool
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
# Token identity: chunked streaming vs static engine, across state families
# ==========================================================================
class TestChunkedTokenIdentity:
    @pytest.mark.parametrize(
        "arch",
        [
            "llama3.2-3b",  # dense GQA, paged
            "qwen3-8b",  # dense GQA, per-head q/k RMSNorm
            "qwen2.5-14b",  # dense GQA, q/k/v bias
            "recurrentgemma-2b",  # windowed ring KV + RG-LRU hybrid
            "deepseek-v2-236b",  # MLA compressed cache (per-slot path)
            "xlstm-1.3b",  # pure recurrent (mLSTM + sLSTM), zero pages
            "llama4-scout-17b-a16e",  # MoE, scan-stacked groups
        ],
    )
    def test_chunked_greedy_matches_static(self, arch):
        """Prompts longer than the chunk budget (and, for the hybrid, than
        the attention window) stream in over several steps and must stay
        token-identical to the lockstep reference."""
        cfg, params = _params_for(arch)
        eng = Engine(
            cfg, params, ShardingCtx.null(),
            ServeConfig(max_new_tokens=5, cache_len=64, page_size=8, chunk_budget=16),
        )
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(7), (2, 40), 0, cfg.vocab_size)
        }
        np.testing.assert_array_equal(
            eng.generate(batch).tokens, eng.generate_static(batch).tokens
        )

    def test_chunked_matches_unchunked_scheduler(self):
        """The unified step is a scheduling change only: same requests,
        chunked and whole-prompt schedulers, identical greedy tokens."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [5, 23, 40, 11], seed=2)
        outs = []
        for budget in (None, 16):
            sched = Scheduler(
                cfg, params, ShardingCtx.null(),
                SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=budget),
            )
            for p in prompts:
                sched.submit(Request(p, max_new_tokens=6))
            outs.append([rs.tokens for rs in sched.run()])
        assert outs[0] == outs[1]


# ==========================================================================
# Scheduling behavior: decode rides while long prompts stream in
# ==========================================================================
class TestUnifiedStep:
    def test_decode_not_stalled_by_long_prefill(self):
        """A long prompt admitted mid-flight streams in chunk by chunk while
        the in-flight request keeps emitting one token per step."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=16),
        )
        short, long_ = _prompts(cfg, [4, 48], seed=3)
        r_short = sched.submit(Request(short, max_new_tokens=10))
        sched.step()  # 4-token prompt fits one chunk: joins decode at once
        rs_short = next(rs for rs in sched._active.values() if rs.rid == r_short)
        assert rs_short.status is RequestStatus.ACTIVE
        n0 = len(rs_short.tokens)
        r_long = sched.submit(Request(long_, max_new_tokens=4))
        # Three steps stream the 48-token prompt (3 chunks of 16); the short
        # request must collect one token per step throughout.
        for _ in range(3):
            sched.step()
        rs_long = next(rs for rs in sched._active.values() if rs.rid == r_long)
        assert len(rs_short.tokens) == n0 + 3, (
            "in-flight decode stalled behind a streaming prefill"
        )
        assert rs_long.chunk_pos == 48, "long prompt should be fully streamed"
        sched.run()

    def test_prefilling_state_survives_decode_churn(self):
        """A PREFILLING slot's half-streamed state must not be perturbed by
        other slots' decode steps (recurrences would absorb the masked
        slot's garbage token) — asserted end-to-end via token identity on
        the recurrent hybrid."""
        cfg, params = _params_for("recurrentgemma-2b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=16),
        )
        prompts = _prompts(cfg, [6, 40], seed=4)
        rids = [sched.submit(Request(p, max_new_tokens=6)) for p in prompts]
        sched.run()
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 6)


# ==========================================================================
# Compile counts
# ==========================================================================
class TestChunkCompileCounts:
    def test_bounded_traces_per_chunk_and_page_bucket(self):
        """Chunk shapes are (token bucket, page bucket) pairs — both
        power-of-two — so streaming prompts of many lengths compiles a
        bounded set of chunk programs, the decode step exactly once, and a
        repeat of the same workload compiles nothing new."""
        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=128, page_size=8,
                            chunk_budget=32, min_chunk=8),
        )
        # Token buckets {8, 16, 32} x page buckets {1, 2, 4, 8}: at most 12
        # shapes, far fewer than the 6 distinct lengths x cursor positions.
        lengths = [40, 19, 55, 9, 33, 24]
        for p in _prompts(cfg, lengths, seed=5):
            sched.submit(Request(p, max_new_tokens=3))
        sched.run()
        assert sched.stats()["finished"] == 6
        assert sched.decode_traces == 1, sched.decode_traces
        assert sched.chunk_traces <= 12, (
            f"chunk program traced {sched.chunk_traces}x for <= 12 buckets"
        )
        assert sched.prefill_traces == 0, "chunked requests must not run prefill"
        # Steady state: the same length mix re-traces nothing.
        before = sched.chunk_traces
        for p in _prompts(cfg, lengths, seed=6):
            sched.submit(Request(p, max_new_tokens=3))
        sched.run()
        assert sched.chunk_traces == before, "steady-state workload retraced"
        assert sched.decode_traces == 1

    def test_weights_are_program_arguments(self):
        """Step programs take the weights as an argument: a jitted closure
        over them would embed them in the program as constants (gigabytes
        at full width)."""
        import jax.numpy as jnp

        cfg, params = _params_for("llama3.2-3b")
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, chunk_budget=16),
        )
        st = sched._states
        i32 = lambda v: jnp.asarray(v, jnp.int32)
        span = (
            st["layers"], st["pos"], jnp.zeros((1, 16), jnp.int32),
            i32(0), i32(0), i32(16), i32(sched.mem.pt[0, :1]),
        )
        traced = {
            "decode": sched.programs.decode.trace(
                params, st, jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), bool)
            ),
            "chunk": sched.programs.chunk.trace(params, *span),
            "verify": sched.programs.verify.trace(params, *span),
        }
        weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        for name, t in traced.items():
            const_bytes = sum(getattr(c, "nbytes", 0) for c in t.jaxpr.consts)
            assert const_bytes < weight_bytes // 100, (name, const_bytes)

    def test_chunk_budget_validation(self):
        cfg, params = _params_for("llama3.2-3b")
        with pytest.raises(ValueError, match="chunk_budget"):
            Scheduler(
                cfg, params, ShardingCtx.null(),
                SchedulerConfig(chunk_budget=8, min_chunk=16),
            )
        with pytest.raises(ValueError, match="preemption"):
            Scheduler(
                cfg, params, ShardingCtx.null(), SchedulerConfig(preemption="swap")
            )


# ==========================================================================
# Page-aware preemption
# ==========================================================================
class TestPreemption:
    @pytest.mark.parametrize("policy", ["swap", "recompute"])
    def test_preempted_requests_resume_token_identical(self, policy):
        """A pool too small for two requests' live footprints forces
        preemption mid-decode; the victim resumes and its final tokens are
        exactly its solo run's."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [24, 30], seed=3)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=8,
                            chunk_budget=16, preemption=policy),
        )
        rids = [sched.submit(Request(p, max_new_tokens=12)) for p in prompts]
        sched.run()
        assert sched.preemptions_total > 0, "workload must actually preempt"
        assert sched.decode_traces == 1
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 12), (
                f"request {rid} diverged after {policy} preemption"
            )

    def test_swap_snapshot_roundtrips_recurrent_state(self):
        """Swap preemption on the windowed+recurrent hybrid: the snapshot
        carries ring pages AND per-slot recurrence states verbatim."""
        cfg, params = _params_for("recurrentgemma-2b")
        prompts = _prompts(cfg, [20, 26], seed=6)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=5,
                            chunk_budget=16, preemption="swap"),
        )
        rids = [sched.submit(Request(p, max_new_tokens=10)) for p in prompts]
        sched.run()
        assert sched.preemptions_total > 0
        for rid, p in zip(rids, prompts):
            assert sched.result(rid).tokens == _solo(cfg, params, p, 10)

    def test_decoder_self_preempts_when_streamer_pins_pool(self):
        """Decode-side growth never victimizes a streamer (only a chunk
        request may restart a *younger* streamer); when a streamer has
        pinned the pool and a decoder crosses a page boundary, the decoder
        parks *itself* (instead of crashing) and resumes token-identically."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [6, 24], seed=9)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=4,
                            chunk_budget=16, preemption="swap"),
        )
        r0 = sched.submit(Request(prompts[0], max_new_tokens=12))
        sched.step()  # r0 streams its 1-chunk prompt and starts decoding
        r1 = sched.submit(Request(prompts[1], max_new_tokens=4))
        sched.run()
        assert sched.preemptions_total >= 1
        for rid, p, max_new in ((r0, prompts[0], 12), (r1, prompts[1], 4)):
            assert sched.result(rid).tokens == _solo(cfg, params, p, max_new)

    def test_reservation_free_admission_overcommits_pool(self):
        """With preemption on, admission no longer reserves the worst case:
        two requests whose combined worst case exceeds the pool are both
        admitted (the off policy would defer the second)."""
        cfg, params = _params_for("llama3.2-3b")
        prompts = _prompts(cfg, [9, 9], seed=3)
        sched = Scheduler(
            cfg, params, ShardingCtx.null(),
            SchedulerConfig(n_slots=2, cache_len=64, page_size=8, n_pages=4,
                            chunk_budget=16, preemption="recompute"),
        )
        for p in prompts:
            sched.submit(Request(p, max_new_tokens=8))
        for _ in range(3):
            sched.step()
        assert sched.num_active == 2, (
            "reservation-free admission must not defer on worst-case capacity"
        )
        sched.run()
        assert sched.finished_total == 2


# ==========================================================================
# Paged chunked-prefill kernel vs XLA gather reference
# ==========================================================================
class TestPagedChunkKernel:
    def test_kernel_matches_gather_reference(self):
        import jax.numpy as jnp

        from repro.kernels import ops

        rng = np.random.default_rng(0)
        B, KV, G, D, page, P, MP, C = 2, 2, 3, 16, 8, 9, 4, 8
        kp = jnp.asarray(rng.normal(size=(P + 1, page, KV, D)).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(P + 1, page, KV, D)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(B, C, KV * G, D)).astype(np.float32))
        pt = np.full((B, MP), P, np.int32)
        pt[0, :3] = [0, 1, 2]
        pt[1, :4] = [3, 4, 5, 6]
        start = jnp.asarray([10, 17], jnp.int32)  # chunks mid-prompt

        o = ops.paged_chunk_attention_op(q, kp, vp, jnp.asarray(pt), start, n_lp=MP)

        T = MP * page
        kg = kp[jnp.asarray(pt)].reshape(B, T, KV, D)
        vg = vp[jnp.asarray(pt)].reshape(B, T, KV, D)
        kb = jnp.broadcast_to(kg[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, KV * G, D)
        vb = jnp.broadcast_to(vg[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, KV * G, D)
        s = jnp.einsum("bchd,bthd->bhct", q, kb) * (D ** -0.5)
        k_pos = jnp.arange(T)[None, None, :]
        q_pos = (start[:, None] + jnp.arange(C)[None, :])[:, :, None]
        valid = k_pos <= q_pos  # (B, C, T)
        s = jnp.where(valid[:, None], s, -1e30)
        ref = jnp.einsum("bhct,bthe->bche", jax.nn.softmax(s, -1), vb)
        err = float(jnp.max(jnp.abs(o - ref)))
        assert err < 2e-5, err

    def test_pallas_backend_end_to_end_chunked(self):
        """attn_backend=pallas routes dense chunked prefill through the
        paged chunk kernel; greedy tokens must match the XLA gather path."""
        from dataclasses import replace

        cfg, params = _params_for("llama3.2-3b")
        toks = []
        for backend in ("xla", "pallas"):
            c = replace(cfg, attn_backend=backend)
            sched = Scheduler(
                c, params, ShardingCtx.null(),
                SchedulerConfig(n_slots=2, cache_len=64, page_size=16, chunk_budget=16),
            )
            for p in _prompts(cfg, [40, 12], seed=8):
                sched.submit(Request(p, max_new_tokens=4))
            toks.append([rs.tokens for rs in sched.run()])
        assert toks[0] == toks[1]


# ==========================================================================
# Pool accounting: incremental reservations
# ==========================================================================
class TestExtendTo:
    def test_extend_to_accounting(self):
        pool = PagePool(PageLayout(page_size=4, n_pages=6, span=24))
        pool.reserve(0, 0)
        assert pool.extend_to(0, 4)
        pool.grow_to(0, 4)
        pool.reserve(1, 0)
        assert pool.extend_to(1, 2)
        assert not pool.extend_to(1, 3), "only 2 pages left to back"
        assert pool.extend_to(1, 2) and pool.extend_to(1, 1), "shrink is a no-op"
        pool.release(0)
        assert pool.extend_to(1, 6)
        with pytest.raises(ValueError):
            pool.extend_to(3, 1)  # never reserved

    def test_extend_never_aliases(self):
        layout = PageLayout(page_size=2, n_pages=10, span=20)
        pool = PagePool(layout)
        held = {}
        for slot in range(3):
            pool.reserve(slot, 0)
            assert pool.extend_to(slot, 3)
            held[slot] = pool.grow_to(slot, 3)
        flat = [p for ids in held.values() for p in ids]
        assert len(flat) == len(set(flat)) == 9
        assert pool.available() == 1
