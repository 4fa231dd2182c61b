"""Speculative-decoding executor: draft + verify on top of the layered core.

Free functions over a :class:`~repro.serve.scheduler.Scheduler` — sizing
comes from the plan layer (:func:`repro.serve.plan.plan_verify`), page
backing from the memory layer, and the verify/chunk/setpos programs from
the registry. Kept out of scheduler.py so the core loop stays slim;
nothing here owns state.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import plan as planlib
from repro.serve.request import RequestState, RequestStatus


def drafts(s, rs: RequestState) -> bool:
    """Whether ``rs`` speculates: an ACTIVE greedy request without modality
    extras, with >= 1 token of budget beyond this step's guaranteed
    emission (tokens still on the device count as emitted)."""
    req = rs.request
    return (
        rs.status is RequestStatus.ACTIVE
        and req.temperature <= 0.0
        and not req.extras
        and s._plan(
            planlib.spec_budget, req.max_new_tokens, len(rs.tokens) + rs.queued
        ) >= 1
    )


def spec_step(s) -> set[int]:
    """Draft + verify for every eligible ACTIVE slot (greedy only, no
    modality extras, >= 1 token of budget beyond this step's guaranteed
    emission); returns the slots that emitted here (they sit out this
    step's decode). A slot whose draft can't get page backing falls back
    to plain decoding for this step (``spec_fallbacks``)."""
    handled: set[int] = set()
    for slot in sorted(s._active):
        rs = s._active.get(slot)
        # It may have been preempted by an earlier verify. A slot with a
        # token still on the device (it joined the decode while the step
        # before was in flight) decodes plainly: drafts need every token.
        if rs is None or rs.queued or not drafts(s, rs):
            continue
        req = rs.request
        with s._phase("serve.verify", rid=rs.rid):
            ctx = np.concatenate(
                [np.asarray(req.prompt, np.int32), np.asarray(rs.tokens, np.int32)]
            )
            budget = planlib.spec_budget(req.max_new_tokens, len(rs.tokens))
            k = min(s.sched.draft_k, budget)
            draft = np.asarray(s._drafter.propose(ctx, k), np.int32).reshape(-1)[:k]
            if draft.size and verify_slot(s, slot, rs, draft):
                handled.add(slot)
    return handled


def verify_slot(s, slot: int, rs: RequestState, draft: np.ndarray) -> bool:
    """Score ``[pending token, draft...]`` in one all-logits chunk call and
    emit the longest greedy-matching run plus the model's own next token —
    between 1 and k+1 tokens, token-identical to plain decoding. Returns
    False (slot decodes plainly this step) only when the draft can't get
    page backing.

    Invariant in and out: the cache holds ``prompt + generated - 1``
    tokens and the slot's next decode input is the last generated token,
    not yet fed. Greedy logits at chunk index ``i`` answer "what follows
    token i", so index ``accepted`` supplies the bonus/correction token."""
    vp = s._plan(
        planlib.plan_verify, slot, rs.rid, int(s._pos_host[slot]), len(draft),
        draft_k=s.sched.draft_k, mem=s.mem if s._paged else None,
    )
    k, start, n_real = vp.k, vp.start, vp.n_real
    page_ids = None
    if s._paged:
        if vp.need_pages > s.mem.held(slot):
            if not s._ensure_pages(slot, vp.need_pages, rid=rs.rid):
                s.spec_fallbacks += 1
                return False
            s.mem.grow(slot, vp.need_pages)
        if s._sharing:
            # Defensive CoW guard, like the decode step's: the verify range
            # starts past any shared prompt page (steady-state no-op).
            s._apply_cow(s.mem.prepare_write(slot, start, start + n_real))
        page_ids = s._put(s.mem.pt[slot, : vp.n_lp])

    # Pre-verify snapshot for rollback-by-replay (recurrent carries,
    # windowed ring folds). Taken *after* CoW so forked pages are in it;
    # JAX array immutability makes this a free reference.
    snap = s._states["layers"] if s._needs_replay else None

    toks = np.zeros(vp.bucket, np.int32)
    toks[0] = rs.tokens[-1]
    toks[1:n_real] = draft
    toks_dev = s._put(toks[None, :])
    slot_t = jnp.asarray(slot, jnp.int32)
    start_t = jnp.asarray(start, jnp.int32)
    args = [
        s.params, s._states["layers"], s._states["pos"], toks_dev,
        slot_t, start_t, jnp.asarray(n_real, jnp.int32),
    ]
    if s._paged:
        args.append(page_ids)
    logits, layers, pos = s.programs.verify(*args)
    s._ev["verifies"].append(vp)

    # Greedy acceptance on host, matching the sample program's cast + argmax.
    with s._phase("serve.wait.verify", rid=rs.rid):
        lg = np.asarray(logits[0, :n_real, : s.cfg.vocab_size]).astype(np.float32)
    greedy = lg.argmax(axis=-1).astype(np.int32)
    accept = 0
    while accept < k and greedy[accept] == draft[accept]:
        accept += 1
    emitted = [int(t) for t in draft[:accept]] + [int(greedy[accept])]
    n_new = accept + 1  # tokens the cache should have gained

    if accept == k:
        # Full acceptance: the verify pass already cached exactly the
        # accepted run and set pos = start + n_real.
        s._states["layers"] = layers
        s._states["pos"] = pos
    else:
        if s._paged:
            # Return the pages grown for rejected positions (always private:
            # sharing only covers the prompt prefix). Under worst-case
            # reservations the backing stays owed to this slot;
            # reservation-free, it returns to the pool.
            keep = s.mem.pages_for_len(start + n_new)
            removed = s.mem.truncate(
                slot, keep, keep_reservation=s.sched.preemption == "off"
            )
            if removed:
                n_lp = planlib.page_bucket(keep, s.mem.max_pages)
                page_ids = s._put(s.mem.pt[slot, :n_lp])
        if s._needs_replay:
            # State advanced through rejected tokens (recurrence) or
            # rejected writes folded onto live ring entries: re-run the
            # accepted run from the snapshot through the chunk program
            # (chunk_len is traced — no fresh compile per accept count).
            rargs = [
                s.params, snap, s._states["pos"], toks_dev, slot_t, start_t,
                jnp.asarray(n_new, jnp.int32),
            ]
            if s._paged:
                rargs.append(page_ids)
            _, rlayers, rpos = s.programs.chunk(*rargs)
            s._states["layers"] = rlayers
            s._states["pos"] = rpos
            s.total_spec_replays += 1
        else:
            # Dense/MLA: garbage past the accepted position is inert under
            # positional masks; only the position needs fixing.
            s._states["layers"] = layers
            s._states["pos"] = s.programs.setpos(
                pos, slot_t, jnp.asarray(start + n_new, jnp.int32)
            )

    s._pos_host[slot] = start + n_new
    rs.spec_steps += 1
    rs.drafted += k
    rs.accepted += accept
    s.total_spec_steps += 1
    s.drafted_tokens_total += k
    s.accepted_tokens_total += accept
    now = time.perf_counter()
    for tok in emitted:
        s._emit(rs, tok, now)
        if rs.done:
            break  # stop token mid-run: drop the rest, as plain decode would
    else:
        s._place(slot, rs.tokens[-1])
    return True
