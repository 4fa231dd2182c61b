"""Continuous-batching serving core: the executor over plan / program /
memory layers.

Four layers with narrow interfaces: **plan** (serve/plan.py) makes pure
host-side decisions from plain values plus MemoryManager capacity
queries (no JAX); **programs** (serve/programs.py) owns every jitted
program plus trace accounting and sharding glue; **memory**
(serve/memory.py) fronts the refcounted PagePool(s), CoW forks, prefix
index, and host page-table mirror — with a `data` mesh axis the pool
splits into per-shard sub-pools aligned with the GSPMD blocks of the
page-axis-sharded pool leaves, so `data > 1` partitions state instead
of replicating it; the **Scheduler** here (with executors in
admission.py / chunk_exec.py / preempt.py / spec_exec.py) owns request
lifecycle and loops plan → execute → observe, publishing each step's
decisions as an immutable `BatchPlan` (`last_plan`) and planner time as
`plan_time_s`; each host phase of a step is a named span (`_phase`), a
profiler annotation on the device trace's clock whose wall time adds up
in `phase_time_s`. Semantics are unchanged from the pre-split scheduler
and pinned by the serve suites: greedy outputs token-identical to
`generate_static`, one trace per program bucket, inactive slots decode
garbage into trash pages behind frozen positions.
"""
from __future__ import annotations

import contextlib
import heapq
import time
from collections import deque
from dataclasses import dataclass, replace as _dc_replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention import live_pages
from repro.models import blocks as blk
from repro.models import lm
from repro.serve import admission, chunk_exec, preempt, spec_exec
from repro.serve import plan as planlib
from repro.serve.draft import Drafter, NgramDrafter
from repro.serve.memory import MemoryManager
from repro.serve.pages import PageLayout, cdiv, model_page_span
from repro.serve.programs import ProgramRegistry, paged_cache_bytes
from repro.serve.request import Request, RequestState, RequestStatus
from repro.serve.step import (
    decode_state_shardings,
    init_decode_state,
    init_paged_decode_state,
)
from repro.sharding.rules import ShardingCtx, get_profile

_RECURRENT_KINDS = {"rglru", "mlstm", "slstm"}


@dataclass
class SchedulerConfig:
    n_slots: int = 4  # concurrent sequences in the batched decode state
    cache_len: int = 256  # per-slot logical cache slots
    seed: int = 0
    keep_finished: int = 1024  # finished RequestStates retained for result()
    # Paged KV pool, for models whose layers page (model_page_span > 0);
    # n_pages=None sizes it at contiguous capacity parity.
    page_size: int = 16  # tokens per page
    n_pages: int | None = None
    # Pow2 prompt buckets: prefill/admit compile once per bucket
    # (auto-disabled for recurrent models).
    prefill_buckets: bool = True
    min_bucket: int = 8
    # Unified token-budget step: one decode token per slot plus a prefill
    # chunk <= pow2_floor(chunk_budget). None -> whole-prompt prefill.
    chunk_budget: int | None = None
    min_chunk: int = 16
    # "off" reserves the worst case at admission; "swap" / "recompute"
    # admit reservation-free and reclaim LRU pages on OOM (needs chunking).
    preemption: str = "off"
    # Content-address full prompt pages and adopt matches at admission
    # (CoW-protected); fully-paged streaming-capable models only.
    prefix_sharing: bool = True
    # Per-tenant worst-case page quota (None -> unlimited) and stride-
    # scheduled ordering over weights (None -> exact FIFO).
    tenant_quota: int | None = None
    tenant_weights: dict[str, float] | None = None
    # Draft up to draft_k tokens per greedy ACTIVE slot, verify in one
    # all-logits chunk call; drafter=None installs NgramDrafter.
    speculative: bool = False
    draft_k: int = 4
    drafter: Drafter | None = None
    # ("data", "model") mesh: model shards heads/experts per the profile;
    # a data axis dividing n_slots AND n_pages partitions slots and the
    # page pool per shard (serve/memory.py). Tables stay host-side.
    mesh_shape: tuple[int, int] | None = None
    sharding_profile: str = "decode_default"


class Scheduler:
    def __init__(
        self, cfg: ModelConfig, params: Any, sctx: ShardingCtx, sched: SchedulerConfig
    ):
        self.cfg = cfg
        self.params = params
        if sched.mesh_shape is not None and sctx.mesh is None:
            d, m = (int(x) for x in sched.mesh_shape)
            if d * m > 1:
                from repro.launch.mesh import make_test_mesh

                sctx = ShardingCtx(
                    make_test_mesh(data=d, model=m),
                    get_profile(sched.sharding_profile),
                )
        self.sctx = sctx
        self.sched = sched
        n = sched.n_slots
        if sched.preemption not in ("off", "swap", "recompute"):
            raise ValueError(f"unknown preemption policy {sched.preemption!r}")
        if sched.preemption != "off" and sched.chunk_budget is None:
            raise ValueError(
                "preemption requires the unified token-budget step "
                "(set chunk_budget)"
            )
        if sched.tenant_quota is not None and sched.tenant_quota < 1:
            raise ValueError(f"tenant_quota must be >= 1, got {sched.tenant_quota}")
        if sched.tenant_weights is not None and any(
            w <= 0 for w in sched.tenant_weights.values()
        ):
            raise ValueError("tenant_weights must be positive")
        self._chunked = sched.chunk_budget is not None
        if self._chunked and sched.chunk_budget < sched.min_chunk:
            raise ValueError(
                f"chunk_budget {sched.chunk_budget} < min_chunk {sched.min_chunk}"
            )
        # Chunked streaming handles token-only requests; modality prefixes
        # and enc-dec cross caches go through whole-prompt prefill.
        self._stream_capable = self._chunked and not cfg.enc_dec and not cfg.prefix_len
        if sched.speculative and sched.draft_k < 1:
            raise ValueError(f"draft_k must be >= 1, got {sched.draft_k}")
        # Speculation rides chunk_step (token-only decoder stacks); per-
        # request gating (greedy only, no extras) happens in spec_exec.
        self._spec = sched.speculative and not cfg.enc_dec and not cfg.prefix_len
        self._drafter: Drafter | None = None
        if self._spec:
            self._drafter = (
                sched.drafter if sched.drafter is not None else NgramDrafter()
            )

        span = model_page_span(cfg, sched.cache_len)
        self._paged = span > 0
        if self._paged:
            n_pages = (
                sched.n_pages
                if sched.n_pages is not None
                else n * cdiv(span, sched.page_size)
            )
            # Data-parallel pool partitioning kicks in when the data axis
            # divides both the slot count and the pool — otherwise the pool
            # stays single-shard (its leaves replicate over data, exactly
            # the pre-partitioning layout).
            dsize = sctx.axis_size("data")
            d_eff = (
                dsize if dsize > 1 and n_pages % dsize == 0 and n % dsize == 0
                else 1
            )
            if d_eff > 1:
                # Tell the model layer the pool is truly partitioned so
                # shard_map'd paged kernels localize page ids per shard.
                sctx = _dc_replace(sctx, pool_data_shards=d_eff)
                self.sctx = sctx
            layout = PageLayout(
                page_size=sched.page_size, n_pages=n_pages, span=span,
                data_shards=d_eff,
            )
            self.mem = MemoryManager(layout, n)
            state = init_paged_decode_state(cfg, n, sched.cache_len, layout, sctx=sctx)
        else:
            self.mem = MemoryManager(None, n)
            state = init_decode_state(cfg, n, sched.cache_len, sctx=sctx)
            state["pos"] = jnp.zeros((n,), jnp.int32)
        self._layer_shardings = decode_state_shardings(
            cfg, n, sched.cache_len, sctx, pages=self.mem.layout
        )
        if self._layer_shardings is not None:
            from repro.models.schema import shard_tree

            self.params = shard_tree(params, lm.model_schema(cfg), sctx)

        # The program registry owns every jitted program.
        self._layouts = blk.stack_layouts(cfg, sched.cache_len, paged=self._paged)
        caps = blk.stack_paged_caps(cfg, sched.cache_len) if self._paged else None
        self.programs = ProgramRegistry(
            cfg, sctx,
            cache_len=sched.cache_len, layouts=self._layouts, caps=caps,
            layer_shardings=self._layer_shardings,
            page_size=sched.page_size if self._paged else 0, paged=self._paged,
        )
        if self._paged:
            state["page_table"] = self._put(self.mem.pt)
        self._states: dict[str, Any] = state
        self._tokens = np.zeros((n, 1), np.int32)  # next input token per slot
        self._temps = np.zeros((n,), np.float32)
        self._active_mask = np.zeros((n,), bool)
        self._pos_host = np.zeros((n,), np.int64)  # tokens cached per slot

        kinds = set(cfg.block_pattern) | set(cfg.first_blocks)
        self._bucketed = sched.prefill_buckets and not (kinds & _RECURRENT_KINDS)
        # Rejected draft positions leave inert garbage in dense/MLA caches,
        # but corrupt state that *advanced* (recurrent carries, windowed
        # ring folds) — those archs roll back by snapshot replay.
        self._needs_replay = bool(kinds & _RECURRENT_KINDS) or (
            "local_attn" in kinds
        )
        # Prefix sharing needs every stateful leaf behind the page table:
        # only fully dense-paged streaming models share.
        self._sharing = (
            sched.prefix_sharing
            and self._paged
            and self._stream_capable
            and kinds <= {"attn_mlp", "attn_moe"}
            and kinds <= blk.paged_kv_kinds(cfg)
        )
        self._slot_worst: dict[int, tuple[str, int]] = {}  # slot -> (tenant, pages)
        self._tenant_pass: dict[str, float] = {}  # stride-scheduling virtual time

        self._queue: deque[RequestState] = deque()
        self._preempted: deque[RequestState] = deque()  # resume before admits
        self._active: dict[int, RequestState] = {}  # slot -> request
        self._free_slots: list[int] = list(range(n))
        heapq.heapify(self._free_slots)
        self._finished: dict[int, RequestState] = {}
        self._next_rid = 0
        self._key = jax.random.PRNGKey(sched.seed)

        self.total_decode_steps = 0
        self.total_chunk_steps = 0
        self.total_spec_steps = 0  # verify calls (one slot each)
        self.total_spec_replays = 0  # partial-accept rollback replays
        self.spec_fallbacks = 0  # drafts dropped for lack of pages
        self.drafted_tokens_total = 0
        self.accepted_tokens_total = 0
        self.deferred_admissions = 0  # pool-backpressure events
        self.quota_deferrals = 0  # tenant-quota skip events
        self.preemptions_total = 0
        self.prefix_hits = 0  # admissions that adopted >= 1 indexed page
        self.prefix_hit_tokens = 0  # prompt tokens satisfied by adoption
        self.finished_total = 0  # cumulative, survives keep_finished eviction
        self.generated_tokens_total = 0
        self.last_decode_logits: jax.Array | None = None
        self.last_plan: planlib.BatchPlan = planlib.BatchPlan()
        self.plan_time_s = 0.0  # cumulative time inside plan-layer calls
        # Pages of one full-width paged layer that the Pallas decode kernel's
        # walk reads, and the table's pages, summed over slots and decode
        # steps; None where no such walk runs (the XLA gather reads the table).
        counted = self._paged and cfg.attn_backend == "pallas"
        self.decode_pages_walked: int | None = 0 if counted else None
        self.decode_pages_table: int | None = 0 if counted else None
        if counted:
            self._trash_rows = np.array([self.mem.trash_of(s) for s in range(n)])
        self.phase_time_s: dict[str, float] = {}  # cumulative time per step span
        self._ev: dict[str, Any] = {
            "admits": [], "chunk": None, "verifies": [], "rows": (),
            "preempted": [],
        }

    # -- layer glue ----------------------------------------------------------
    def _plan(self, fn, *args, **kw):
        """Run a plan-layer function, accounting its wall time."""
        t = time.perf_counter()
        out = fn(*args, **kw)
        self.plan_time_s += time.perf_counter() - t
        return out

    @contextlib.contextmanager
    def _phase(self, name: str, **meta):
        """A span of the step: a profiler annotation (recorded only while a
        trace runs; ``meta`` such as ``rid`` rides in the event's name)
        whose wall time adds to ``phase_time_s[name]``. The spans are
        ``serve.step`` around the whole step and, inside it,
        ``serve.admit``, ``serve.chunk``, ``serve.wait.first_token``,
        ``serve.verify`` (holding ``serve.wait.verify``), ``serve.pages``,
        ``serve.decode``, ``serve.wait.decode`` and ``serve.emit``;
        ``serve.wait.*`` are the blocks on the device."""
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **meta):
                yield
        finally:
            self.phase_time_s[name] = (
                self.phase_time_s.get(name, 0.0) + time.perf_counter() - t
            )

    @property
    def host_time_s(self) -> float:
        """Cumulative host time of the steps: ``serve.step`` less the time
        it blocked on the device (``serve.wait.*``)."""
        p = self.phase_time_s
        return p.get("serve.step", 0.0) - sum(
            v for k, v in p.items() if k.startswith("serve.wait.")
        )

    def _put(self, x):
        return self.programs.put(x)

    def _constrain_layers(self, layers):
        return self.programs.constrain_layers(layers)

    @property
    def pool(self):
        return self.mem.pool

    @property
    def pages(self):
        return self.mem.layout

    @property
    def _pt(self):
        return self.mem.pt

    @property
    def _slot_keys(self):
        return self.mem.slot_keys

    @property
    def _slot_reg(self):
        return self.mem.slot_reg

    # -- client API ----------------------------------------------------------
    def submit(self, request: Request) -> int:
        """Queue a request; returns its request id."""
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(
            RequestState(request=request, rid=rid, t_submit=time.perf_counter())
        )
        return rid

    def reset_rng(self, seed: int) -> None:
        self._key = jax.random.PRNGKey(seed)

    def set_drafter(self, drafter: Drafter) -> None:
        """Swap the draft proposer. No-op with speculation off."""
        if self._spec:
            self._drafter = drafter

    @property
    def pending(self) -> int:
        return len(self._queue) + len(self._preempted)

    @property
    def num_active(self) -> int:
        return len(self._active)

    def result(self, rid: int) -> RequestState:
        rs = self._finished.get(rid)
        if rs is not None:
            return rs
        in_flight = (
            any(r.rid == rid for r in self._active.values())
            or any(r.rid == rid for r in self._queue)
            or any(r.rid == rid for r in self._preempted)
        )
        if in_flight:
            raise KeyError(f"request {rid} is not finished yet")
        if 0 <= rid < self._next_rid:
            raise KeyError(
                f"request {rid} finished but its result was evicted "
                f"(keep_finished={self.sched.keep_finished}); raise "
                "keep_finished or collect results as requests retire (run())"
            )
        raise KeyError(f"unknown request id {rid}")

    def run(self) -> list[RequestState]:
        """Drive steps until queue and slots drain; returns finished states
        for the requests in flight at call time, in submission order
        (collected as requests retire, surviving keep_finished)."""
        in_flight = (
            {rs.rid for rs in self._queue}
            | {rs.rid for rs in self._active.values()}
            | {rs.rid for rs in self._preempted}
        )
        results: dict[int, RequestState] = {}
        while self._queue or self._active or self._preempted:
            self.step()
            for rid in list(in_flight):
                rs = self._finished.get(rid)
                if rs is not None:
                    results[rid] = rs
                    in_flight.discard(rid)
        return [results[r] for r in sorted(results)]

    # -- one scheduling iteration --------------------------------------------
    def step(self) -> bool:
        """One plan → execute → observe iteration: admit/resume, stream at
        most one prefill chunk, per-slot speculative verifies, then one
        decode step over the remaining rows. The decisions taken are
        published as `last_plan`. Returns True if any program ran."""
        with self._phase("serve.step"):
            self._ev = {
                "admits": [], "chunk": None, "verifies": [], "rows": (),
                "preempted": [],
            }
            try:
                return self._step()
            finally:
                e = self._ev
                self.last_plan = planlib.BatchPlan(
                    admitted=tuple(e["admits"]), chunk=e["chunk"],
                    verifies=tuple(e["verifies"]), decode_rows=e["rows"],
                    preempted=tuple(e["preempted"]),
                )

    def _step(self) -> bool:
        with self._phase("serve.admit"):
            self._admit_pending()
        ran = False
        if self._chunked:
            ran = self._prefill_chunk_step()
        handled: set[int] = set()
        if self._spec and self._active_mask.any():
            handled = self._spec_step()
            ran = ran or bool(handled)
        # Slots that already emitted via verify sit out this decode: their
        # cleared mask freezes pos and per-slot states exactly like a
        # PREFILLING slot's.
        rows = self._plan(planlib.decode_rows, self._active_mask, handled)
        self._ev["rows"] = rows
        if not rows:
            return ran
        if self._paged:
            with self._phase("serve.pages"):
                self._grow_pages(skip=handled)
                if self._sharing:
                    # CoW guard: decode writes one token per ACTIVE slot at
                    # its current position — fork first if that page is
                    # shared (the scheduler's write pattern keeps this a
                    # no-op, but the invariant is enforced here, not
                    # assumed).
                    for slot, rs in list(self._active.items()):
                        if rs.status is RequestStatus.ACTIVE and slot not in handled:
                            p = int(self._pos_host[slot])
                            self._apply_cow(self.mem.prepare_write(slot, p, p + 1))
                self._states["page_table"] = self._put(self.mem.pt)
                if self.decode_pages_table is not None:
                    self._count_decode_pages()

        with self._phase("serve.decode"):
            mask = np.zeros_like(self._active_mask)
            mask[list(rows)] = True
            self._key, sub = jax.random.split(self._key)
            logits, self._states = self.programs.decode(
                self.params, self._states, self._put(self._tokens), self._put(mask)
            )
            self.last_decode_logits = logits
            cols = self.programs.sample(
                logits[:, -1, :], jnp.asarray(self._temps), sub
            )
        with self._phase("serve.wait.decode"):
            cols = np.asarray(cols)
        self.total_decode_steps += 1

        with self._phase("serve.emit"):
            now = time.perf_counter()
            for slot, rs in list(self._active.items()):
                if rs.status is not RequestStatus.ACTIVE or slot in handled:
                    continue  # still streaming its prompt in, or emitted via spec
                rs.decode_steps += 1
                self._pos_host[slot] += 1
                tok = int(cols[slot])
                rs.tokens.append(tok)
                rs.t_tokens.append(now)
                self._tokens[slot, 0] = tok
                self._maybe_finish(rs, now)
        return True

    def _count_decode_pages(self) -> None:
        """Add this decode step's pages to ``decode_pages_walked`` and
        ``decode_pages_table``, from the host mirrors of the page table and
        of the positions the step decodes at (no device read)."""
        mem = self.mem
        walked = live_pages(
            mem.pt[:, 0], self._pos_host, page=mem.page_size,
            n_lp=mem.max_pages, trash=self._trash_rows, xp=np,
        )
        self.decode_pages_walked += int(walked.sum())
        self.decode_pages_table += mem.pt.size

    # -- chunked prefill (executor in serve/chunk_exec.py) -------------------
    def _prefill_chunk_step(self) -> bool:
        return chunk_exec.prefill_chunk_step(self)

    def _finish_prefill(self, rs: RequestState, logits: jax.Array) -> None:
        chunk_exec.finish_prefill(self, rs, logits)

    # -- speculative decoding (executor in serve/spec_exec.py) ---------------
    def _spec_step(self) -> set[int]:
        return spec_exec.spec_step(self)

    def _verify_slot(self, slot: int, rs: RequestState, draft: np.ndarray) -> bool:
        return spec_exec.verify_slot(self, slot, rs, draft)

    # -- pages & preemption (executor in serve/preempt.py) -------------------
    def _apply_cow(self, forks: list[tuple[int, int, int]]) -> None:
        preempt.apply_cow(self, forks)

    def _ensure_pages(self, slot: int, n_total: int, rid: int | None = None) -> bool:
        return preempt.ensure_pages(self, slot, n_total, rid=rid)

    def _grow_pages(self, skip: set[int] = frozenset()) -> None:
        preempt.grow_pages(self, skip=skip)

    def _can_preempt(self, rs: RequestState) -> bool:
        return preempt.can_preempt(self, rs)

    def _preempt_lru(
        self, protect: int, requester_rid: int | None = None,
        shard: int | None = None,
    ) -> bool:
        return preempt.preempt_lru(
            self, protect, requester_rid=requester_rid, shard=shard
        )

    def _preempt_slot(self, slot: int) -> None:
        preempt.preempt_slot(self, slot)

    # -- admission (executor in serve/admission.py) --------------------------
    def _bucket_len(self, token_len: int) -> int:
        """Power-of-two padded token count (plan layer; identity when
        bucketing is off)."""
        return self._plan(
            planlib.bucket_len, token_len,
            bucketed=self._bucketed, min_bucket=self.sched.min_bucket,
            cache_len=self.sched.cache_len, prefix_len=self.cfg.prefix_len or 0,
            long_ok=bool(self.cfg.supports_long_context or self.cfg.window_size),
        )

    def _worst_pages(self, rs: RequestState) -> int:
        """Worst-case page footprint of a request (0 when not paged)."""
        if not self._paged:
            return 0
        req = rs.request
        prompt_len = req.prompt.shape[0] + (self.cfg.prefix_len or 0)
        return self.mem.pages_for_len(prompt_len + req.max_new_tokens)

    def _tenant_pages(self, tenant: str) -> int:
        """Worst-case pages currently charged to ``tenant``'s slots."""
        return sum(w for t, w in self._slot_worst.values() if t == tenant)

    def _pick_next(self, blocked: set[str]) -> RequestState | None:
        """Weighted-fair pick (plan-layer stride scheduling)."""
        rid = self._plan(
            planlib.pick_next,
            [planlib.QueueView(rs.rid, rs.request.tenant) for rs in self._queue],
            blocked, self._tenant_pass,
        )
        if rid is None:
            return None
        for rs in self._queue:
            if rs.rid == rid:
                return rs
        return None  # pragma: no cover - rid came from the queue

    def _charge_tenant(self, rs: RequestState) -> None:
        req = rs.request
        weights = self.sched.tenant_weights or {}
        self._tenant_pass = self._plan(
            planlib.charge_tenant, self._tenant_pass, req.tenant,
            req.prompt.shape[0] + req.max_new_tokens,
            weights.get(req.tenant, 1.0),
        )

    def _admit_pending(self) -> None:
        admission.admit_pending(self)

    def _admit(self, rs: RequestState) -> bool:
        return admission.admit(self, rs)

    def _check_fits(self, rs: RequestState, prompt_len: int) -> int:
        return admission.check_fits(self, rs, prompt_len)

    def _admit_streaming(self, rs: RequestState) -> bool:
        return admission.admit_streaming(self, rs)

    def _try_resume(self, rs: RequestState) -> bool:
        return admission.try_resume(self, rs)

    def _admit_prefill(self, rs: RequestState) -> bool:
        return admission.admit_prefill(self, rs)

    def _maybe_finish(self, rs: RequestState, now: float) -> None:
        req = rs.request
        reason = None
        if req.stop_token >= 0 and rs.tokens[-1] == req.stop_token:
            reason = "stop"
        elif len(rs.tokens) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        slot = rs.slot
        assert slot is not None
        self._active_mask[slot] = False
        self._tokens[slot, 0] = 0
        del self._active[slot]
        heapq.heappush(self._free_slots, slot)
        self._pos_host[slot] = 0
        self._slot_worst.pop(slot, None)
        if self._paged:
            # Free pages and trash-point the table row so the retired slot's
            # frozen-position garbage writes can never touch a future tenant
            # of these pages; indexed pages park in the pool's cached list
            # for the next same-prefix admission.
            self.mem.release(slot)
        rs.status = RequestStatus.FINISHED
        rs.finish_reason = reason
        rs.t_finish = now
        self._finished[rs.rid] = rs
        self.finished_total += 1
        self.generated_tokens_total += len(rs.tokens)
        # Bound retention for long-running serving.
        while len(self._finished) > self.sched.keep_finished:
            self._finished.pop(next(iter(self._finished)))

    def stats(self) -> dict[str, Any]:
        out = {
            # Cumulative — monotone even after keep_finished eviction.
            "finished": self.finished_total,
            "generated_tokens": self.generated_tokens_total,
            "retained": len(self._finished),
            "decode_steps": self.total_decode_steps,
            "chunk_steps": self.total_chunk_steps,
            "spec_steps": self.total_spec_steps,
            "spec_replays": self.total_spec_replays,
            "spec_fallbacks": self.spec_fallbacks,
            "drafted_tokens": self.drafted_tokens_total,
            "accepted_tokens": self.accepted_tokens_total,
            "decode_traces": self.decode_traces,
            "prefill_traces": self.prefill_traces,
            "admit_traces": self.admit_traces,
            "chunk_traces": self.chunk_traces,
            "swap_traces": self.swap_traces,
            "cow_traces": self.cow_traces,
            "verify_traces": self.verify_traces,
            "pending": self.pending,
            "active": self.num_active,
            "deferred_admissions": self.deferred_admissions,
            "quota_deferrals": self.quota_deferrals,
            "preemptions": self.preemptions_total,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "plan_time_s": self.plan_time_s,
            "phase_time_s": dict(self.phase_time_s),
            "host_time_s": self.host_time_s,
            "decode_pages_walked": self.decode_pages_walked,
            "decode_pages_table": self.decode_pages_table,
            # Program calls on the paged state that updated the pool in
            # place (state donated) and that took it undonated (a copy).
            "pool_inplace_calls": self.programs.pool_inplace_calls,
            "pool_copy_calls": self.programs.pool_copy_calls,
        }
        out["mesh"] = (
            None if self.sctx.mesh is None else dict(self.sctx.mesh.shape)
        )
        out["mesh_devices"] = self.sctx.device_count()
        if self._paged:
            out["pages"] = self.mem.stats()
        return out

    def paged_cache_bytes(self) -> dict[str, int]:
        """Actual vs contiguous-equivalent cache bytes (see programs.py)."""
        return paged_cache_bytes(
            self.cfg, self.sched.cache_len, self.sched.n_slots, self._states,
            self._layer_shardings, self.sctx, self.mem,
        )


def _delegate_trace(name: str):
    return property(
        lambda self: getattr(self.programs, name),
        lambda self, v: setattr(self.programs, name, v),
    )


def _delegate_prog(name: str):
    return property(lambda self: getattr(self.programs, name))


# Trace counters live on the program registry (incremented inside jit
# trace bodies); the historical Scheduler attributes stay as delegates,
# as do the historical names of the jitted callables.
for _n in (
    "decode_traces", "prefill_traces", "admit_traces", "chunk_traces",
    "swap_traces", "cow_traces", "verify_traces",
):
    setattr(Scheduler, _n, _delegate_trace(_n))
for _old, _new in (
    ("_decode", "decode"), ("_prefill", "prefill"), ("_admit_jit", "admit"),
    ("_chunk_jit", "chunk"), ("_verify_jit", "verify"),
    ("_setpos_jit", "setpos"), ("_reset_jit", "reset"), ("_cow_jit", "cow"),
    ("_swap_out_jit", "swap_out"), ("_swap_in_jit", "swap_in"),
    ("_sample", "sample"),
):
    setattr(Scheduler, _old, _delegate_prog(_new))
del _n, _old, _new
