"""Request lifecycle types for the continuous-batching scheduler.

A ``Request`` is what a client submits: a prompt plus per-request decoding
policy (max_new_tokens, stop token, temperature). ``RequestState`` is the
scheduler's record of one request as it moves queued -> active -> finished,
including its generated tokens and timing/throughput stats.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any

import numpy as np


class RequestStatus(enum.Enum):
    QUEUED = "queued"  # in the admission queue, no slot yet
    PREFILLING = "prefilling"  # holds a slot, prompt streaming in by chunks
    ACTIVE = "active"  # prefilled into a slot, decoding
    PREEMPTED = "preempted"  # pages reclaimed mid-decode, awaiting resume
    FINISHED = "finished"  # retired (stop token or length)


@dataclass
class Request:
    """One generation request. ``prompt`` is a (P,) int32 token vector;
    ``extras`` carries per-request modality inputs (``prefix_embeds`` /
    ``enc_embeds``) with a leading batch-1 axis. ``tenant`` names the
    submitting tenant for page quotas / weighted-fair admission (every
    request shares one tenant by default, which disables both)."""

    prompt: np.ndarray
    max_new_tokens: int = 32
    stop_token: int = -1  # -1 => never stop early
    temperature: float = 0.0  # 0 => greedy
    extras: dict[str, Any] = field(default_factory=dict)
    tenant: str = "default"

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, dtype=np.int32)
        if self.prompt.ndim != 1:
            raise ValueError(f"prompt must be (P,), got {self.prompt.shape}")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


@dataclass
class RequestState:
    """Scheduler-side state of one request."""

    request: Request
    rid: int
    status: RequestStatus = RequestStatus.QUEUED
    slot: int | None = None
    prompt_len: int = 0  # tokens + modality prefix, set at admission
    tokens: list[int] = field(default_factory=list)
    finish_reason: str | None = None  # "stop" | "length"
    prefill_logits: np.ndarray | None = None  # (1, 1, V) last-position logits
    decode_steps: int = 0  # decode iterations this request rode in
    queued: int = 0  # tokens sampled on the device, not yet read by the host
    # Chunked-prefill cursor: prompt tokens already streamed into the cache
    # (counts teacher-forced replay tokens after a recompute resume).
    chunk_pos: int = 0
    replay_tokens: np.ndarray | None = None  # prompt ++ generated, for resume
    preemptions: int = 0
    # Prompt tokens satisfied from the shared prefix index at admission
    # (their pages were adopted, not recomputed — the warm-prefix win).
    adopted_tokens: int = 0
    # Speculative decoding: verify steps this request rode, draft tokens
    # proposed for it, and how many of those the verify pass accepted
    # (each spec step also emits one non-draft bonus token on top).
    spec_steps: int = 0
    drafted: int = 0
    accepted: int = 0
    swap: Any = None  # host-side page/state snapshot while PREEMPTED (swap)
    # Wall-clock stamps (time.perf_counter seconds).
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_finish: float = 0.0
    t_tokens: list[float] = field(default_factory=list)  # per-token stamps

    @property
    def done(self) -> bool:
        return self.status is RequestStatus.FINISHED

    @property
    def ttft_s(self) -> float:
        """Submit -> first token (includes queueing + prefill)."""
        return max(self.t_first_token - self.t_submit, 0.0)

    @property
    def latency_s(self) -> float:
        """Submit -> last token."""
        return max(self.t_finish - self.t_submit, 0.0)

    @property
    def decode_tokens_per_s(self) -> float:
        dt = self.t_finish - self.t_admit
        return len(self.tokens) / dt if dt > 0 else float("inf")

    def inter_token_s(self) -> list[float]:
        """Gaps between consecutive token emissions (the latency a streaming
        client feels mid-generation; long un-chunked prefills of *other*
        requests show up here as spikes)."""
        return [
            b - a for a, b in zip(self.t_tokens, self.t_tokens[1:])
        ]
