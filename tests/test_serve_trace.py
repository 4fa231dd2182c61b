"""The serving step's own spans and counters: every host phase of a step is
a ``serve.*`` profiler span nested in ``serve.step`` and adds its wall time
to ``Scheduler.phase_time_s``; ``host_time_s`` is the step less its blocks
on the device; the first token is stamped once it has reached the host; and
host arrays handed to the device are never aliased by it."""
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import lm
from repro.models.schema import init_params
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx

# The spans of a chunked step that streams a prompt's last chunk and decodes.
# Its first token is read with the step's decode tokens, in
# ``serve.wait.decode``: no ``serve.wait.first_token``.
CHUNKED_SPANS = {
    "serve.step", "serve.admit", "serve.chunk", "serve.pages",
    "serve.decode", "serve.wait.decode", "serve.emit",
}


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_config("llama3.2-3b").reduced()
    params = init_params(lm.model_schema(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=n).astype(np.int32)


def _warm(cfg, params, chunk_budget):
    """A scheduler whose programs for a 20-token prompt are compiled."""
    sched = Scheduler(
        cfg, params, ShardingCtx.null(),
        SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=chunk_budget),
    )
    sched.submit(Request(_prompt(cfg, 20), max_new_tokens=3))
    sched.run()
    return sched


def _serve_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#")[0]
                    if name.startswith("serve."):
                        spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def test_step_spans_nest_in_the_host_plane(dense_model, tmp_path):
    cfg, params = dense_model
    sched = _warm(cfg, params, chunk_budget=16)
    sched.submit(Request(_prompt(cfg, 20, seed=1), max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    spans = _serve_spans(tmp_path)
    assert {n for n, _, _ in spans} == CHUNKED_SPANS
    steps = [(s, e) for n, s, e in spans if n == "serve.step"]
    # a 16-token chunk; the last 4 tokens, the first token and a decode;
    # the third token's decode
    assert len(steps) == 3
    for name, s, e in spans:
        if name != "serve.step":
            assert any(s0 <= s <= e <= e0 for s0, e0 in steps), name


def test_phase_time_fits_in_the_step(dense_model):
    cfg, params = dense_model
    sched = _warm(cfg, params, chunk_budget=16)
    sched.submit(Request(_prompt(cfg, 20, seed=2), max_new_tokens=3))
    while sched.pending or sched.num_active:
        before, host0 = dict(sched.phase_time_s), sched.host_time_s
        t0 = time.perf_counter()
        sched.step()
        wall = time.perf_counter() - t0
        delta = {k: v - before.get(k, 0.0) for k, v in sched.phase_time_s.items()}
        step = delta.pop("serve.step")
        assert 0 < step <= wall
        assert 0 <= sum(delta.values()) <= step
        assert 0 <= sched.host_time_s - host0 <= step
    stats = sched.stats()
    assert set(stats["phase_time_s"]) == CHUNKED_SPANS
    assert stats["host_time_s"] == sched.host_time_s >= 0


def test_verify_wait_is_left_out_of_host_time(dense_model):
    """A speculative step blocks on its verify logits inside
    ``serve.verify``; that block is a ``serve.wait.*`` phase, so
    ``host_time_s`` does not count it."""
    cfg, params = dense_model
    sched = Scheduler(
        cfg, params, ShardingCtx.null(),
        SchedulerConfig(n_slots=2, cache_len=64, page_size=8, chunk_budget=16,
                        speculative=True, draft_k=4),
    )
    sched.submit(Request(np.tile(_prompt(cfg, 5), 4), max_new_tokens=12))
    sched.run()
    assert sched.total_spec_steps > 0
    p = sched.phase_time_s
    assert 0 < p["serve.wait.verify"] <= p["serve.verify"] < p["serve.step"]
    assert 0 <= sched.host_time_s <= p["serve.step"] - p["serve.wait.verify"]


@pytest.mark.parametrize("chunk_budget", [16, None], ids=["chunked", "whole_prompt"])
def test_first_token_stamped_after_sample_returns(dense_model, chunk_budget):
    """A first token reaches the host only when ``sample`` returns; the
    stamp may not come before that, on either prefill path."""
    cfg, params = dense_model
    sched = _warm(cfg, params, chunk_budget=chunk_budget)
    sample = sched.programs.sample

    def slow_sample(*args):
        out = np.asarray(sample(*args))
        time.sleep(0.05)
        return out

    sched.programs.sample = slow_sample
    rid = sched.submit(Request(_prompt(cfg, 20, seed=3), max_new_tokens=3))
    rs = sched._queue[-1]
    while not rs.tokens:
        t_step = time.perf_counter()
        sched.step()
    assert rs.t_first_token >= t_step + 0.05
    assert rs.t_tokens[0] == rs.t_first_token
    sched.run()
    rs = sched.result(rid)
    assert rs.t_submit <= rs.t_admit <= rs.t_first_token <= rs.t_finish


def test_host_arrays_put_on_the_device_are_copied(dense_model):
    """The executor mutates its page table and token mirrors in place,
    possibly before the program that reads them has run: what ``put``
    hands the device must not follow those writes."""
    cfg, params = dense_model
    sched = _warm(cfg, params, chunk_budget=16)
    pt = np.zeros((2, 8), np.int32)
    row = sched._put(pt[1, :4])
    whole = sched._put(pt)
    pt[...] = 9
    assert not np.asarray(row).any() and not np.asarray(whole).any()
