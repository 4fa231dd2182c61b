"""The system under test, driven through ``Scheduler.submit`` / ``step``.

The harness builds the Scheduler the way ``experiments/serve.py`` does
(weights on the device, the mesh from ``serving_ctx``), warms up exactly
the programs the cell's traffic can reach, then sends the traffic and
stamps every token itself: a token is stamped when the ``step()`` that
emitted it returns, and a request's wait is counted from when it was due.
"""
from __future__ import annotations

import gc
import math
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from chipbench import trace as tracing
from chipbench.traffic import Req
from chipbench.weights import Dims, program_params


@dataclass
class StepRec:
    t0: float
    t1: float
    plan_s: float
    decode_ctx: tuple[int, ...]  # live tokens of each decoded row
    chunk: tuple[int, int] | None  # (start, real tokens) of the prefill chunk
    pages_in_use: int
    pages_reserved: int
    traced: bool = False


@dataclass
class Track:
    req: Req
    due: float  # absolute perf_counter time
    submitted: float
    rs: object  # the scheduler's RequestState
    stamps: list[float] = field(default_factory=list)


def model_config(c: dict, name: str):
    from repro.configs.base import ModelConfig

    m = Dims.from_config(c)
    return ModelConfig(
        name=name, family="dense", n_layers=m.layers, d_model=m.d, n_heads=m.heads,
        n_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.ff, vocab_size=m.vocab,
        qk_norm=m.qk_norm, qkv_bias=m.qkv_bias, rope_theta=m.theta, norm_eps=m.eps,
        act="silu", tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_backend=c["attn_backend"], compute_dtype=c["torch_dtype"],
        vocab_pad_multiple=c.get("vocab_pad_multiple", 128),
    )


def mesh_shape(c: dict) -> tuple[int, int] | None:
    d, mdl = c.get("mesh", [1, 1])
    return None if d * mdl == 1 else (int(d), int(mdl))


def n_pages(c: dict, sched: dict) -> int:
    """Pages of the pool: what the chip's KV budget holds, at most the
    capacity of every slot at once."""
    m = Dims.from_config(c)
    shards = (mesh_shape(c) or (1, 1))[1]
    page_bytes = sched["page_size"] * m.layers * 2 * (m.kv_heads // shards) * m.head_dim * 2
    per_slot = math.ceil(sched["cache_len"] / sched["page_size"])
    return min(sched["n_slots"] * per_slot, c["kv_pool_bytes_per_chip"] // page_bytes)


def build(cell, seed: int):
    """(scheduler, model config, mesh context) for ``cell`` with weights
    from ``seed``."""
    from repro.experiments.serve import serving_ctx

    cfg = model_config(cell.config, cell.config_name)
    sctx = serving_ctx(mesh_shape(cell.config))
    params = program_params(seed, Dims.from_config(cell.config), cfg, sctx)
    return build_scheduler(cell, cfg, sctx, params, seed), cfg, sctx


def build_scheduler(cell, cfg, sctx, params, seed: int):
    from repro.serve.scheduler import Scheduler, SchedulerConfig

    c, s = cell.config, cell.traffic["scheduler"]
    return Scheduler(
        cfg, params, sctx,
        SchedulerConfig(
            n_slots=s["n_slots"], cache_len=s["cache_len"], page_size=s["page_size"],
            n_pages=n_pages(c, s), chunk_budget=s["chunk_budget"], min_chunk=s["min_chunk"],
            preemption=s["preemption"], seed=seed & 0x7FFFFFFF, mesh_shape=mesh_shape(c),
        ),
    )


def warm_lengths(lo: int, hi: int, s: dict, max_pages: int) -> list[int]:
    """Fewest prompt lengths in [lo, hi] whose chunked prefill meets every
    (chunk bucket, page bucket) pair that any length in the range meets;
    the program's own planner decides the buckets."""
    from repro.serve.plan import chunk_bucket, page_bucket

    page = s["page_size"]
    reach: dict[int, set] = {}
    for n in range(lo, hi + 1):
        start, pairs = 0, set()
        while start < n:
            b, k = chunk_bucket(n - start, chunk_budget=s["chunk_budget"], min_chunk=s["min_chunk"])
            pairs.add((b, page_bucket(-(-(start + k) // page), max_pages)))
            start += k
        reach[n] = pairs
    todo, chosen = set().union(*reach.values()), []
    while todo:
        n = max(reach, key=lambda n: (len(reach[n] & todo), n))
        chosen.append(n)
        todo -= reach[n]
    return sorted(chosen)


def warm_up(sched, mix: dict, vocab: int, seed: int) -> list[int]:
    """Compile (or load) the decode program and every chunk program the
    mix can reach, by serving one short request per chosen length."""
    from repro.serve.request import Request

    lens = warm_lengths(
        mix["prompt_len"]["min"], mix["prompt_len"]["max"], mix["scheduler"], sched.mem.max_pages
    )
    rng = np.random.default_rng([seed, 99])
    for n in lens:
        sched.submit(Request(rng.integers(0, vocab, size=n).astype(np.int32), max_new_tokens=2))
    sched.run()
    return lens


class Window:
    """Sends a mix's requests and steps the scheduler until the window
    closes; records every step and stamps every token."""

    def __init__(self, sched, mix: dict, reqs: list[Req], seconds: float):
        self.sched = sched
        self.mix = mix
        self.reqs = reqs
        self.seconds = seconds
        self.steps: list[StepRec] = []
        self.tracks: list[Track] = []
        self.t_start = self.t_open = self.t_close = 0.0
        self.trace_dir: str | None = None
        self.gc_pauses: list[tuple[float, float, int]] = []  # start, seconds, generation
        self._gc_t0 = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append(
                (self._gc_t0, time.perf_counter() - self._gc_t0, info["generation"])
            )

    def _submit(self, req: Req, due: float) -> Track:
        from repro.serve.request import Request

        rid = self.sched.submit(
            Request(req.prompt, max_new_tokens=req.max_new, temperature=self.mix["temperature"])
        )
        rs = self.sched._queue[-1]
        if rs.rid != rid:
            raise RuntimeError(f"submitted request {rid} is not at the queue's tail")
        tr = Track(req=req, due=due, submitted=time.perf_counter(), rs=rs)
        self.tracks.append(tr)
        return tr

    def _step(self, inflight: list[Track]) -> list[Track]:
        """One scheduler step; returns the tracks it finished."""
        import jax

        sched = self.sched
        plan0 = sched.plan_time_s
        with jax.profiler.TraceAnnotation(tracing.STEP):
            t0 = time.perf_counter()
            sched.step()
            t1 = time.perf_counter()
        lp = sched.last_plan
        by_slot = {tr.rs.slot: tr.rs for tr in inflight if tr.rs.slot is not None}
        ctx = tuple(
            by_slot[r].prompt_len + len(by_slot[r].tokens) - 1 for r in lp.decode_rows
        )
        pages = sched.mem.stats()
        self.steps.append(StepRec(
            t0, t1, sched.plan_time_s - plan0, ctx,
            None if lp.chunk is None else (lp.chunk.start, lp.chunk.n_real),
            pages.get("pages_in_use", 0), pages.get("pages_reserved", 0),
        ))
        done = []
        for tr in inflight:
            n = len(tr.rs.tokens)
            if n > len(tr.stamps):
                tr.stamps.extend([t1] * (n - len(tr.stamps)))
            if tr.rs.done:
                done.append(tr)
        return done

    def run(self, trace_dir: str | None = None, trace_s: float = 3.0) -> None:
        gc.callbacks.append(self._on_gc)
        try:
            self._run(trace_dir, trace_s)
        finally:
            gc.callbacks.remove(self._on_gc)

    def _run(self, trace_dir: str | None, trace_s: float) -> None:
        import jax

        mix, sched = self.mix, self.sched
        self.t_start = time.perf_counter()
        self.t_open = self.t_start + mix["ramp_s"]
        self.t_close = self.t_open + self.seconds
        t_trace = self.t_open + max(0.0, (self.seconds - trace_s) / 2)
        tracing_now = False
        queue = deque(self.reqs)
        inflight: list[Track] = []
        closed = mix["loop"] == "closed"
        if closed:
            with jax.profiler.TraceAnnotation(tracing.GENERATE):
                for _ in range(mix["clients"]):
                    inflight.append(self._submit(queue.popleft(), self.t_start))
        while True:
            now = time.perf_counter()
            if now >= self.t_close:
                break
            if trace_dir and not tracing_now and now >= t_trace and self.trace_dir is None:
                tracing.start(trace_dir)
                self.trace_dir, tracing_now = trace_dir, True
                t_trace_on = time.perf_counter()
            if tracing_now and now >= t_trace + trace_s:
                tracing.stop()
                tracing_now = False
                for st in self.steps:
                    st.traced = st.t0 >= t_trace_on
            if not closed:
                with jax.profiler.TraceAnnotation(tracing.GENERATE):
                    while queue and self.t_start + queue[0].due_s <= now:
                        r = queue.popleft()
                        inflight.append(self._submit(r, self.t_start + r.due_s))
            if sched.pending or sched.num_active:
                done = self._step(inflight)
                if done:
                    inflight = [t for t in inflight if not t.rs.done]
                    if closed:
                        with jax.profiler.TraceAnnotation(tracing.GENERATE):
                            t_done = self.steps[-1].t1
                            for _ in done:
                                inflight.append(self._submit(queue.popleft(), t_done))
            elif queue and not closed:
                time.sleep(max(0.0, min(self.t_start + queue[0].due_s, self.t_close) - now))
            else:
                break
        if tracing_now:
            tracing.stop()
            for st in self.steps:
                st.traced = st.t0 >= t_trace_on

    # -- what the window measured ---------------------------------------------
    def window_steps(self) -> list[StepRec]:
        return [s for s in self.steps if s.t0 >= self.t_open and s.t1 <= self.t_close]

    def tokens_in_window(self) -> int:
        lo, hi = self.t_open, self.t_close
        return sum(1 for tr in self.tracks for t in tr.stamps if lo <= t <= hi)

    def ttft_s(self) -> list[float]:
        """From due to first token, for every request due in the window; one
        with no first token by the close counts at its wait so far."""
        return [
            (tr.stamps[0] if tr.stamps and tr.stamps[0] <= self.t_close else self.t_close) - tr.due
            for tr in self.tracks if self.t_open <= tr.due < self.t_close
        ]

    def itl_s(self) -> list[float]:
        """Gaps between consecutive tokens of a request that end in the window."""
        lo, hi = self.t_open, self.t_close
        return [
            b - a for tr in self.tracks for a, b in zip(tr.stamps, tr.stamps[1:]) if lo <= b <= hi
        ]

    def attempted(self) -> int:
        return sum(1 for tr in self.tracks if self.t_open <= tr.due < self.t_close)

    def lateness_s(self) -> list[float]:
        return [tr.submitted - tr.due for tr in self.tracks]

    def finished(self) -> list[Track]:
        return [tr for tr in self.tracks if tr.rs.done and tr.stamps and tr.stamps[-1] <= self.t_close]
