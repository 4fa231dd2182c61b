"""Chunked-prefill executor: stream one prompt chunk per unified step.

Free functions over a :class:`~repro.serve.scheduler.Scheduler`. Chunk
sizing comes from the plan layer (:func:`repro.serve.plan.plan_chunk`),
page backing from the memory layer, and the chunk program from the
registry.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import plan as planlib
from repro.serve.request import RequestState, RequestStatus


def prefill_chunk_step(s) -> bool:
    """Stream one prompt chunk for the oldest PREFILLING slot. Chunk sizes
    come from the fixed power-of-two bucket set (plan layer), so the
    loaded system never meets a shape the idle warmup didn't compile;
    per-step work stays bounded by chunk_budget + n_slots. Returns True
    if a chunk program ran."""
    prefilling = sorted(
        (rs for rs in s._active.values()
         if rs.status is RequestStatus.PREFILLING),
        key=lambda r: r.rid,
    )
    if not prefilling:
        return False
    sc = s.sched
    rs = prefilling[0]
    slot = rs.slot
    src = (
        rs.replay_tokens
        if rs.replay_tokens is not None
        else np.asarray(rs.request.prompt)
    )
    with s._phase("serve.chunk", rid=rs.rid):
        cp = s._plan(
            planlib.plan_chunk, slot, rs.rid, rs.chunk_pos, len(src) - rs.chunk_pos,
            chunk_budget=sc.chunk_budget, min_chunk=sc.min_chunk,
            mem=s.mem if s._paged else None,
        )
        start, n_real = cp.start, cp.n_real

        page_ids = None
        if s._paged:
            if not s._ensure_pages(slot, cp.need_pages, rid=rs.rid):
                s.deferred_admissions += 1
                return False
            s.mem.grow(slot, cp.need_pages)
            if s._sharing:
                # Fork any shared page in the chunk's write range before the
                # chunk program touches it (steady-state no-op: chunks only
                # write at or past the first unadopted position).
                s._apply_cow(s.mem.prepare_write(slot, start, start + n_real))
            # The chunk only attends to pages covering [0, start + n_real);
            # the power-of-two page bucket keeps the gather/kernel cost
            # tracking the live prefix, not the table width.
            page_ids = s._put(s.mem.pt[slot, : cp.n_lp])

        toks = src[start : start + n_real].astype(np.int32)
        if n_real < cp.bucket:
            toks = np.concatenate([toks, np.zeros(cp.bucket - n_real, np.int32)])
        args = [
            s.params, s._states["layers"], s._states["pos"], s._put(toks[None, :]),
            jnp.asarray(slot, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(n_real, jnp.int32),
        ]
        if s._paged:
            args.append(page_ids)
        logits, layers, pos = s.programs.chunk(*args)
        s._states["layers"] = layers
        s._states["pos"] = pos
        rs.chunk_pos += n_real
        s._pos_host[slot] = rs.chunk_pos
        s.total_chunk_steps += 1
        s._ev["chunk"] = cp
        if s._sharing and slot in s.mem.slot_keys:
            # Register newly-completed full prompt pages in the prefix index
            # (first writer wins; adopted pages are already indexed).
            s.mem.register_progress(slot, rs.chunk_pos)
    if rs.chunk_pos == len(src):
        finish_prefill(s, rs, logits)
    return True


def finish_prefill(s, rs: RequestState, logits: jax.Array) -> None:
    """The prompt is fully streamed: join the decode batch. The first
    token is stamped once it has reached the host."""
    slot = rs.slot
    req = rs.request
    if rs.replay_tokens is not None:
        # Recompute resume: the last generated token was never fed back; it
        # is the next decode input, not a fresh sample.
        rs.replay_tokens = None
        s._tokens[slot, 0] = rs.tokens[-1]
        now = time.perf_counter()
    else:
        s._key, sub = jax.random.split(s._key)
        with s._phase("serve.wait.first_token", rid=rs.rid):
            first = int(
                np.asarray(
                    s.programs.sample(
                        logits[:, -1, :],
                        jnp.full((1,), req.temperature, jnp.float32),
                        sub,
                    )
                )[0]
            )
            now = time.perf_counter()  # the first token is on the host
            rs.prefill_logits = np.asarray(logits[:, -1:, :])
        rs.tokens = [first]
        rs.t_first_token = now
        rs.t_tokens.append(now)
        s._tokens[slot, 0] = first
    rs.status = RequestStatus.ACTIVE
    s._temps[slot] = req.temperature
    s._active_mask[slot] = True
    s._maybe_finish(rs, now)
