"""One step queued on the device ahead of the host: a step() dispatches the
next step before it reads the tokens of the one before, and the sampled
tokens reach the next decode without leaving the device. Greedy serving
stays token-identical to the lockstep reference on every path (chunked
streaming, prefix sharing with a copy-on-write fork, speculation, swap and
recompute preemption, stop tokens), the decode program compiles once, and
``last_plan`` still describes the step whose tokens the call emitted."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import lm
from repro.models.schema import init_params
from repro.serve.engine import Engine, ServeConfig
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx


def _params_for(name):
    cfg = get_config(name).reduced()
    return cfg, init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense_model():
    return _params_for("llama3.2-3b")


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p).astype(np.int32) for p in lengths]


def _solo(cfg, params, prompt, max_new):
    eng = Engine(
        cfg, params, ShardingCtx.null(), ServeConfig(max_new_tokens=max_new, cache_len=64)
    )
    return eng.generate_static({"tokens": np.asarray(prompt)[None, :]}).tokens[0].tolist()


def _chunked(cfg, params, **kw):
    kw = {"n_slots": 2, "cache_len": 64, "page_size": 8, "chunk_budget": 16, **kw}
    return Scheduler(cfg, params, ShardingCtx.null(), SchedulerConfig(**kw))


@pytest.mark.parametrize("arch", ["qwen3-8b", "qwen2.5-14b"])
def test_chunked_greedy_matches_static_with_overlap(arch):
    """The two chip configurations' families, streamed in chunks with one
    step queued ahead, match the static engine token for token."""
    cfg, params = _params_for(arch)
    eng = Engine(
        cfg, params, ShardingCtx.null(),
        ServeConfig(max_new_tokens=6, cache_len=64, page_size=8, chunk_budget=16),
    )
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 21), 0, cfg.vocab_size)}
    got = eng.generate(batch)
    np.testing.assert_array_equal(got.tokens, eng.generate_static(batch).tokens)
    st = eng._schedulers[2].stats()
    assert st["overlapped_steps"] > 0
    assert st["drained_steps"] == 0
    assert st["decode_traces"] == 1


def test_prefix_sharing_and_forced_fork_with_overlap(dense_model):
    """A request that adopts another's prompt pages, one of them then forked
    by copy-on-write while a step is in flight, stays token-identical."""
    cfg, params = dense_model
    shared = _prompts(cfg, [24], seed=21)[0]
    tails = _prompts(cfg, [6, 9], seed=22)
    prompts = [np.concatenate([shared, t]) for t in tails]
    sched = _chunked(cfg, params)
    r0 = sched.submit(Request(prompts[0], max_new_tokens=8))
    for _ in range(3):  # stream prompt 0 in; its full pages get indexed
        sched.step()
    r1 = sched.submit(Request(prompts[1], max_new_tokens=8))
    sched.step()
    assert sched._flights, "a step should be in flight"
    slot = next(s for s, rs in sched._active.items() if rs.rid == r1)
    forks = sched.mem.prepare_write(slot, 0, 1)
    assert forks, "r1 must hold a shared page"
    sched._apply_cow(forks)
    sched.run()
    assert sched.prefix_hits >= 1
    for rid, p in zip((r0, r1), prompts):
        assert sched.result(rid).tokens == _solo(cfg, params, p, 8)
    assert sched.stats()["overlapped_steps"] > 0


def test_speculation_reads_first_and_matches(dense_model):
    """A verify drafts from the host's tokens: every step that verifies was
    planned with nothing in flight, and the run stays token-identical."""
    cfg, params = dense_model
    pat = _prompts(cfg, [7], seed=4)[0]
    prompt = np.tile(pat, 3)[:18]
    sched = _chunked(cfg, params, speculative=True, draft_k=4)
    rid = sched.submit(Request(prompt, max_new_tokens=12))
    while sched.pending or sched.num_active:
        before = sched.overlapped_steps
        sched.step()
        if sched.last_plan.verifies:
            assert sched.overlapped_steps == before
    assert sched.total_spec_steps > 0
    assert sched.result(rid).tokens == _solo(cfg, params, prompt, 12)


@pytest.mark.parametrize("policy", ["swap", "recompute"])
def test_preemption_drains_and_matches(dense_model, policy):
    """A pool too small for both requests: growth completes the step in
    flight before it picks a victim (whose tokens swap or recompute
    replays), and both requests stay token-identical."""
    cfg, params = dense_model
    prompts = _prompts(cfg, [14, 12], seed=9)
    sched = _chunked(cfg, params, n_pages=5, preemption=policy)
    rids = [sched.submit(Request(p, max_new_tokens=12)) for p in prompts]
    sched.run()
    st = sched.stats()
    assert st["preemptions"] > 0
    assert st["drained_steps"] > 0 and st["overlapped_steps"] > 0
    for rid, p in zip(rids, prompts):
        assert sched.result(rid).tokens == _solo(cfg, params, p, 12)


def test_decode_compiles_once_across_churn_with_overlap(dense_model):
    """Requests joining and leaving while a step is in flight change only
    array values: the token vector the sample program writes keeps the
    layout the decode program was traced with."""
    cfg, params = dense_model
    sched = _chunked(cfg, params, n_slots=3)
    prompts = _prompts(cfg, [5, 19, 11, 7, 26], seed=6)
    for p, n in zip(prompts[:2], (3, 9)):
        sched.submit(Request(p, max_new_tokens=n))
    for i in range(12):
        if i in (2, 5, 7):
            sched.submit(Request(prompts[2 + (i > 2) + (i > 5)], max_new_tokens=4))
        sched.step()
    sched.run()
    assert sched.stats()["finished"] == 5
    assert sched.decode_traces == 1
    assert sched.overlapped_steps > 0


def test_last_plan_rows_match_the_tokens_emitted(dense_model):
    """What the chip harness reads after each step(): every row of
    ``last_plan.decode_rows`` gained its decode token in that call, and its
    live context is ``prompt_len + len(tokens) - 1``; a request outside the
    rows gained at most its first token."""
    cfg, params = dense_model
    sched = _chunked(cfg, params, n_slots=3)
    prompts = _prompts(cfg, [5, 30, 17, 9], seed=8)
    states = []
    for p, n in zip(prompts, (6, 5, 7, 4)):
        sched.submit(Request(p, max_new_tokens=n))
        states.append(sched._queue[-1])
    decoded = {rs.rid: 0 for rs in states}
    while sched.pending or sched.num_active:
        before = {rs.rid: len(rs.tokens) for rs in states}
        sched.step()
        lp = sched.last_plan
        rows = {
            rs.slot: rs for rs in states
            if rs.slot in lp.decode_rows and len(rs.tokens) > before[rs.rid]
        }
        assert set(rows) == set(lp.decode_rows)
        for rs in states:
            gained = len(rs.tokens) - before[rs.rid]
            first = before[rs.rid] == 0 and gained > 0
            in_rows = rows.get(rs.slot) is rs
            assert gained == int(first) + int(in_rows), rs.rid
            decoded[rs.rid] += int(in_rows)
            if in_rows:
                # the decode attended the prompt and every token before its own
                ctx = rs.prompt_len + len(rs.tokens) - 1
                assert ctx == len(rs.request.prompt) + decoded[rs.rid]
    for rs, p in zip(states, prompts):
        assert rs.tokens == _solo(cfg, params, p, rs.request.max_new_tokens)


def test_stop_on_a_token_still_on_the_device(dense_model):
    """A request stops on a token that was on the device when the next step
    was dispatched: that step's row of it is dropped, and the slot's pages
    pass to a waiting request that stays token-identical to its solo run.
    ``last_decode_logits`` stays those of the last step that emitted."""
    cfg, params = dense_model
    p_stop, p_next = _prompts(cfg, [10, 13], seed=12)
    solo = _solo(cfg, params, p_stop, 8)
    stop = solo[3]
    assert stop not in solo[:3]
    sched = _chunked(cfg, params, n_slots=1)
    r_stop = sched.submit(Request(p_stop, max_new_tokens=8, stop_token=stop))
    r_next = sched.submit(Request(p_next, max_new_tokens=6))
    rs = sched._queue[0]
    pages_before_stop = []
    while not rs.done:
        pages_before_stop = list(sched.pool.allocated(0))
        sched.step()
    assert rs.finish_reason == "stop" and rs.tokens == solo[:4]
    # The step in flight decoded a row of the stopped request.
    assert rs.decode_steps == len(rs.tokens)
    assert sched._flights and sched.num_active == 1
    logits = sched.last_decode_logits
    assert int(np.argmax(np.asarray(logits)[0, -1, : cfg.vocab_size])) == stop
    sched.step()  # admits the next request, completes the dropped row
    assert rs.tokens == solo[:4]
    assert sched.last_plan.decode_rows == ()
    assert sched.last_decode_logits is logits
    assert set(sched.pool.allocated(0)) & set(pages_before_stop), "pages should pass on"
    sched.run()
    assert not sched._flights and sched.num_active == 0
    assert sched.result(r_stop).tokens == solo[:4]
    assert sched.result(r_next).tokens == _solo(cfg, params, p_next, 6)
