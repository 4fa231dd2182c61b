"""Page-growth and preemption executor over the layered core.

Free functions over a :class:`~repro.serve.scheduler.Scheduler`. Victim
choice is a plan-layer decision (:func:`repro.serve.plan.pick_victim`);
page reclamation goes through the memory layer; swap snapshots run
through the program registry. With a data-partitioned pool, reclamation
for a growing slot only considers victims in the *same* data shard —
pages never migrate across shards.
"""
from __future__ import annotations

import heapq

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import plan as planlib
from repro.serve.request import RequestState, RequestStatus


def apply_cow(s, forks: list[tuple[int, int, int]]) -> None:
    """Materialise ``MemoryManager.prepare_write`` forks on device (the
    table mirror is already re-pointed)."""
    if not forks:
        return
    src = jnp.asarray([old for _, old, _ in forks], jnp.int32)
    dst = jnp.asarray([new for _, _, new in forks], jnp.int32)
    s._states["layers"] = s.programs.cow(s._states["layers"], src, dst)


def ensure_pages(s, slot: int, n_total: int, rid: int | None = None) -> bool:
    """Make ``slot``'s reservation cover ``n_total`` pages. Under worst-case
    reservations this always holds; reservation-free, extend incrementally,
    first completing the step in flight (its finishing requests free pages),
    then reclaiming victims' pages (within the slot's data shard), until it
    can be backed. False also when the slot's own request finished as the
    step in flight completed."""
    if s.sched.preemption == "off":
        return True  # admission reserved the worst case
    shard = s.mem.shard_of(slot) if s.mem.data_shards > 1 else None
    while not s.mem.extend_to(slot, n_total):
        if s._drain():
            if slot not in s._active:
                return False
            continue
        if not preempt_lru(s, protect=slot, requester_rid=rid, shard=shard):
            return False
    return True


def grow_pages(s, skip: set[int] = frozenset()) -> None:
    """Allocate the page backing the position each decoding slot writes
    this step — preempting first when reservation-free, including the
    growing slot *itself* when everyone else's pages are pinned."""
    for slot in np.flatnonzero(s._active_mask).tolist():
        # A slot may stop decoding mid-loop: preempted, or finished as the
        # step in flight completed.
        if slot in skip or not s._active_mask[slot]:
            continue
        rs = s._active[slot]
        need = s.mem.pages_for_len(int(s._pos_host[slot]) + 1)
        if need <= s.mem.held(slot):
            continue
        if not ensure_pages(s, slot, need, rid=rs.rid):
            if not s._active_mask[slot]:
                continue
            if can_preempt(s, rs):
                preempt_slot(s, slot)
                continue
            raise RuntimeError(
                f"slot {slot}: cannot back page growth to {need} and the "
                "request is not preemptable (recompute cannot replay "
                "modality extras); use preemption=\"swap\" or a larger "
                "pool for such workloads"
            )
        s.mem.grow(slot, need)


def can_preempt(s, rs: RequestState) -> bool:
    """Swap restores any slot verbatim; recompute replays tokens through
    chunked streaming, which cannot re-feed modality extras."""
    if s.sched.preemption == "swap":
        return True
    return s._stream_capable and not rs.request.extras


def preempt_lru(
    s, protect: int, requester_rid: int | None = None, shard: int | None = None
) -> bool:
    """Reclaim a victim's pages: plan-layer pick (least-recently-(re)admitted
    preemptable ACTIVE slot, else a *younger* PREFILLING streamer — see
    plan.pick_victim). Returns False when none exists. The step in flight
    completes first: a victim's tokens must all be on the host."""
    s._drain()
    views = [
        planlib.SlotView(
            slot=sl, rid=rs.rid,
            status="active" if rs.status is RequestStatus.ACTIVE
            else "prefilling",
            t_admit=rs.t_admit, preemptable=can_preempt(s, rs),
            shard=s.mem.shard_of(sl) if s._paged else 0,
        )
        for sl, rs in s._active.items()
    ]
    victim = s._plan(
        planlib.pick_victim, views,
        protect=protect, requester_rid=requester_rid, shard=shard,
    )
    if victim is None:
        return False
    preempt_slot(s, victim)
    return True


def preempt_slot(s, slot: int) -> None:
    rs = s._active[slot]
    assert not rs.queued, "a preemption victim's tokens must be on the host"
    if rs.status is RequestStatus.PREFILLING:
        # A parked streamer restarts from chunk 0 on resume under either
        # policy; pages it registered in the prefix index survive in the
        # pool's cached list, so the restart re-adopts them.
        rs.chunk_pos = 0
    elif s.sched.preemption == "swap":
        snap = s.programs.swap_out(
            s._states["layers"], s._put(s.mem.pt[slot]),
            jnp.asarray(slot, jnp.int32),
        )
        rs.swap = (jax.tree.map(np.asarray, snap), int(s._pos_host[slot]))
    else:  # recompute
        rs.replay_tokens = np.concatenate(
            [np.asarray(rs.request.prompt, np.int32),
             np.asarray(rs.tokens[:-1], np.int32)]
        )
        rs.chunk_pos = 0
    rs.status = RequestStatus.PREEMPTED
    rs.preemptions += 1
    s.preemptions_total += 1
    s._ev["preempted"].append(rs.rid)
    s._active_mask[slot] = False
    del s._active[slot]
    heapq.heappush(s._free_slots, slot)
    s.mem.release(slot)
    s._pos_host[slot] = 0
    s._slot_worst.pop(slot, None)
    rs.slot = None
    s._preempted.append(rs)
