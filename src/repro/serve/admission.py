"""Admission executor: queue → slot transitions over the layered core.

Free functions over a :class:`~repro.serve.scheduler.Scheduler`. Ordering
(stride-fair tenant picks) and capacity backpressure are plan-layer
decisions; page commitments go through the memory layer; slot resets,
prefill grafts, and swap-ins run through the program registry. The
scheduler calls only :func:`admit_pending` once per step.

Capacity checks peek the free-slot heap's minimum — the slot the
subsequent pop returns — so with a data-partitioned pool the check runs
against the shard that would actually back the admission (identical
behavior on a single shard).
"""
from __future__ import annotations

import heapq
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import plan as planlib
from repro.serve.request import RequestState, RequestStatus


def admit_pending(s) -> None:
    # Preempted requests resume first; a *deferred* resume blocks fresh
    # admissions too — otherwise younger requests would keep taking the
    # pages the preempted request is waiting for and starve it.
    while s._free_slots and s._preempted:
        if not try_resume(s, s._preempted[0]):
            return
        s._preempted.popleft()
    sc = s.sched
    if sc.tenant_quota is None and not sc.tenant_weights:
        # Single-tenant: exact FIFO (the historical admission order).
        while s._free_slots and s._queue:
            rs = s._queue[0]
            if not admit(s, rs):
                break
            s._queue.popleft()
        return
    # Multi-tenant: weighted-fair ordering with per-tenant page quotas. A
    # quota-blocked tenant is skipped (its requests keep FIFO order within
    # the tenant) while others continue to admit; pool backpressure blocks
    # everyone (FIFO fairness of the pool itself).
    blocked: set[str] = set()
    while s._free_slots and s._queue:
        rs = s._pick_next(blocked)
        if rs is None:
            break
        tenant = rs.request.tenant
        if s._paged and sc.tenant_quota is not None:
            n_worst = s._worst_pages(rs)
            if n_worst > sc.tenant_quota:
                raise RuntimeError(
                    f"request {rs.rid} needs {n_worst} pages worst-case, "
                    f"more than tenant {tenant!r}'s whole quota "
                    f"({sc.tenant_quota}); raise tenant_quota or lower "
                    "max_new_tokens"
                )
            if s._tenant_pages(tenant) + n_worst > sc.tenant_quota:
                blocked.add(tenant)
                s.quota_deferrals += 1
                continue
        if not admit(s, rs):
            break
        # identity, not ==: Request's dataclass __eq__ compares prompt
        # arrays elementwise
        for i, q in enumerate(s._queue):
            if q is rs:
                del s._queue[i]
                break
        s._charge_tenant(rs)


def streams(s, rs: RequestState) -> bool:
    """Whether ``rs`` is admitted by streaming its prompt in chunks (else
    by whole-prompt prefill: modality prefixes, enc-dec, no chunking)."""
    return s._stream_capable and not rs.request.extras


def admit(s, rs: RequestState) -> bool:
    if streams(s, rs):
        return admit_streaming(s, rs)
    return admit_prefill(s, rs)


def check_fits(s, rs: RequestState, prompt_len: int) -> int:
    """Shared admission validation; returns the worst-case page count."""
    req = rs.request
    assert (
        prompt_len + req.max_new_tokens <= s.sched.cache_len
        or s.cfg.supports_long_context
        or s.cfg.window_size
    ), (
        f"cache_len {s.sched.cache_len} too small for "
        f"{prompt_len}+{req.max_new_tokens}"
    )
    if not s._paged:
        return 0
    n_worst = s.mem.pages_for_len(prompt_len + req.max_new_tokens)
    if n_worst > s.mem.n_pages // s.mem.data_shards:
        # Never admissible even into an empty (shard of the) pool: fail
        # fast instead of deferring forever (run() would spin).
        raise RuntimeError(
            f"request {rs.rid} needs {n_worst} pages worst-case "
            f"({prompt_len}+{req.max_new_tokens} tokens @ "
            f"{s.mem.page_size}/page) but the pool has only "
            f"{s.mem.n_pages // s.mem.data_shards} per shard; raise "
            "n_pages or lower max_new_tokens"
        )
    return n_worst


def admit_streaming(s, rs: RequestState) -> bool:
    """Assign a slot and start streaming the prompt in chunks, adopting any
    indexed prefix pages first (their tokens are skipped, not recomputed).
    Under worst-case reservations this is where OOM backpressure defers;
    reservation-free admission always proceeds (chunks reserve as they
    stream, preempting on demand)."""
    req = rs.request
    prompt_len = req.prompt.shape[0]
    n_worst = check_fits(s, rs, prompt_len)
    slot = s._free_slots[0]  # heap min == the slot the pop below returns
    if s._paged and not s._plan(
        planlib.can_admit_streaming, s.mem, slot, n_worst,
        reservation_free=s.sched.preemption != "off",
    ):
        s.deferred_admissions += 1
        return False
    heapq.heappop(s._free_slots)
    start = 0
    if s._paged:
        s.mem.reserve(slot, 0)
        if s._sharing:
            src_len = (
                len(rs.replay_tokens)
                if rs.replay_tokens is not None
                else prompt_len
            )
            # Adoption is capped below the streamed source so at least one
            # token still streams: the final chunk's logits seed the first
            # sampled token.
            start = s.mem.adopt(slot, req.prompt, src_len)
            if start:
                s.prefix_hits += 1
                s.prefix_hit_tokens += start
        if s.sched.preemption == "off" and not s.mem.extend_to(slot, n_worst):
            # Adoption revives cached pages (no longer evictable), but it
            # adopts at least as many pages as it revives, so the
            # pre-checked headroom still covers the remainder; this
            # rollback is defensive.
            s.mem.release(slot)
            heapq.heappush(s._free_slots, slot)
            s.deferred_admissions += 1
            return False
        s._slot_worst[slot] = (req.tenant, n_worst)
    layers, pos = s.programs.reset(
        s._states["layers"], s._states["pos"], jnp.asarray(slot, jnp.int32),
        jnp.asarray(start, jnp.int32),
    )
    s._states["layers"] = layers
    s._states["pos"] = pos
    s._pos_host[slot] = start
    rs.slot = slot
    rs.prompt_len = prompt_len
    rs.chunk_pos = start
    rs.adopted_tokens = start
    rs.status = RequestStatus.PREFILLING
    rs.t_admit = time.perf_counter()
    s._active[slot] = rs
    s._ev["admits"].append(
        planlib.AdmitPlan(
            rs.rid, "streaming", slot,
            n_worst if s.sched.preemption == "off" else 0,
        )
    )
    return True


def try_resume(s, rs: RequestState) -> bool:
    """Re-admit a preempted request: swap its snapshot back in, or restart
    streaming (recompute). False defers (not enough pages)."""
    if rs.swap is None:
        # recompute: restart chunk streaming over prompt + generated tokens
        return admit_streaming(s, rs)
    snap, pos_v = rs.swap
    need = s.mem.pages_for_len(pos_v)
    slot = s._free_slots[0]  # heap min == the slot the pop below returns
    if not s._plan(planlib.can_resume_swap, s.mem, slot, need):
        s.deferred_admissions += 1
        return False
    heapq.heappop(s._free_slots)
    s.mem.reserve(slot, 0)
    if not s.mem.extend_to(slot, need):  # pragma: no cover - race-free
        raise RuntimeError("pool accounting violated availability check")
    s.mem.grow(slot, need)
    layers, pos = s.programs.swap_in(
        s._states["layers"], s._states["pos"], jax.tree.map(s._put, snap),
        s._put(s.mem.pt[slot]), jnp.asarray(slot, jnp.int32),
        jnp.asarray(pos_v, jnp.int32),
    )
    s._states["layers"] = layers
    s._states["pos"] = pos
    s._pos_host[slot] = pos_v
    rs.swap = None
    rs.slot = slot
    s._slot_worst[slot] = (rs.request.tenant, s._worst_pages(rs))
    rs.status = RequestStatus.ACTIVE
    rs.t_admit = time.perf_counter()
    s._place(slot, rs.tokens[-1])
    s._temps[slot] = rs.request.temperature
    s._active_mask[slot] = True
    s._active[slot] = rs
    s._ev["admits"].append(planlib.AdmitPlan(rs.rid, "resume_swap", slot, need))
    return True


def admit_prefill(s, rs: RequestState) -> bool:
    """Whole-prompt prefill + graft at admission (also the fallback for
    modality-prefix / enc-dec requests when chunked streaming is on).
    Returns False to defer on pool backpressure."""
    req = rs.request
    prompt_len = req.prompt.shape[0] + (s.cfg.prefix_len or 0)
    n_reserve = check_fits(s, rs, prompt_len)
    page_ids_arr = None
    slot = s._free_slots[0]  # heap min == the slot the pop below returns
    if s._paged and not s._plan(planlib.can_admit_prefill, s.mem, slot, n_reserve):
        # OOM backpressure: not enough headroom in the slot's shard for
        # this request's worst case — defer admission (FIFO preserved;
        # live pages are never reclaimed or aliased).
        s.deferred_admissions += 1
        return False
    heapq.heappop(s._free_slots)
    if s._paged:
        s.mem.reserve(slot, n_reserve)
        s._slot_worst[slot] = (req.tenant, n_reserve)
        s.mem.grow(slot, s.mem.pages_for_len(prompt_len))
        page_ids_arr = s._put(s.mem.pt[slot])

    tok_len = req.prompt.shape[0]
    pad_to = s._bucket_len(tok_len)
    toks = np.asarray(req.prompt)
    if pad_to != tok_len:
        toks = np.concatenate([toks, np.zeros(pad_to - tok_len, np.int32)])
    batch = {"tokens": s._put(toks[None, :])}
    for k, v in req.extras.items():
        batch[k] = jnp.asarray(v)
    if s._bucketed:
        batch["logit_pos"] = jnp.asarray(prompt_len - 1, jnp.int32)
    logits, pstates = s.programs.prefill(s.params, batch)

    plen_t = jnp.asarray(prompt_len, jnp.int32)
    slot_t = jnp.asarray(slot, jnp.int32)
    if s._paged:
        layers, pos = s.programs.admit(
            s._states["layers"], s._states["pos"], pstates["layers"],
            slot_t, page_ids_arr, plen_t,
        )
    else:
        layers, pos = s.programs.admit(
            s._states["layers"], s._states["pos"], pstates["layers"],
            slot_t, plen_t,
        )
    s._states["layers"] = layers
    s._states["pos"] = pos
    s._pos_host[slot] = prompt_len

    rs.t_admit = time.perf_counter()
    s._key, sub = jax.random.split(s._key)
    s._tok = s.programs.sample(
        logits[:, -1, :], jnp.full((1,), req.temperature, jnp.float32), sub,
        s._tok, s._put(np.array([slot], np.int32)),
    )
    with s._phase("serve.wait.first_token", rid=rs.rid):
        vec, rs.prefill_logits = jax.device_get((s._tok, logits[:, -1:, :]))
        now = time.perf_counter()  # the first token is on the host
    rs.slot = slot
    rs.prompt_len = prompt_len
    rs.status = RequestStatus.ACTIVE
    s._temps[slot] = req.temperature
    s._active_mask[slot] = True
    s._active[slot] = rs
    s._ev["admits"].append(planlib.AdmitPlan(rs.rid, "prefill", slot, n_reserve))
    # A 1-token request (or an immediate stop) retires before ever riding
    # the decode step, freeing the slot for this admission loop.
    s._emit(rs, int(vec[slot, 0]), now)
    return True
