"""Serving engine: a thin client of the continuous-batching scheduler.

``generate()`` submits one request per batch row to a ``Scheduler`` and
drains it; requests retire independently (per-request stop token and
max_new_tokens), and the decode hot path is the scheduler's fixed-shape
``(n_slots, 1)`` step. ``generate_static()`` keeps the original static-batch
loop — all rows march in lockstep until every one finishes — as the
reference implementation the scheduler is tested token-for-token against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import blocks as blk
from repro.models import lm
from repro.serve.cache import graft_states
from repro.serve.request import Request
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.serve.step import init_decode_state
from repro.sharding.rules import ShardingCtx


@dataclass
class ServeConfig:
    max_new_tokens: int = 32
    cache_len: int = 256  # decode cache slots (>= prompt + new tokens for dense)
    temperature: float = 0.0  # 0 => greedy
    stop_token: int = -1  # -1 => never stop early
    seed: int = 0
    # Scheduler pass-through: paged KV pool + bucketed prefill + unified
    # token-budget step (the static reference path ignores these — it
    # always runs contiguous rows with whole-prompt prefill).
    page_size: int = 16
    prefill_buckets: bool = True
    n_pages: int | None = None
    chunk_budget: int | None = None  # None -> whole-prompt prefill
    min_chunk: int = 16
    preemption: str = "off"  # "off" | "swap" | "recompute"
    prefix_sharing: bool = True  # adopt indexed prompt-prefix pages
    speculative: bool = False  # drafted multi-token steps (greedy slots)
    draft_k: int = 4  # max draft tokens per verify call
    drafter: Any = None  # Drafter instance; None -> NgramDrafter
    # Sharded stepping: (data, model) test-mesh shape the scheduler builds
    # when the engine's own ShardingCtx has no mesh (None keeps it as-is).
    mesh_shape: tuple[int, int] | None = None
    sharding_profile: str = "decode_default"


@dataclass
class GenerationResult:
    tokens: np.ndarray  # (B, <=max_new_tokens)
    steps: int
    prefill_logits: np.ndarray


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, sctx: ShardingCtx, serve: ServeConfig):
        self.cfg = cfg
        self.params = params
        self.sctx = sctx
        self.serve = serve
        self._prefill = jax.jit(lambda p, b: lm.prefill(p, cfg, b, sctx))
        self._decode = jax.jit(lambda p, s, t: lm.decode_step(p, cfg, s, t, sctx))
        self._schedulers: dict[int, Scheduler] = {}  # keyed by n_slots

    # -- state surgery -------------------------------------------------------
    def _grow_states(self, states: dict[str, Any], prompt_len: int, batch: int) -> dict[str, Any]:
        """Move prefill caches (length S) into serving caches (cache_len)."""
        target = init_decode_state(self.cfg, batch, self.serve.cache_len, start_pos=prompt_len)
        layouts = blk.stack_layouts(self.cfg, self.serve.cache_len, paged=False)
        grafted = graft_states(
            target["layers"], states["layers"], prompt_len, layouts=layouts
        )
        return {"layers": grafted, "pos": jnp.asarray(prompt_len, jnp.int32)}

    # -- generation (continuous-batching path) ------------------------------
    def _sched_for(self, n_slots: int) -> Scheduler:
        # One scheduler per batch size, kept alive so alternating batch
        # shapes reuse their compiled decode/prefill/admit programs.
        if n_slots not in self._schedulers:
            self._schedulers[n_slots] = Scheduler(
                self.cfg, self.params, self.sctx,
                SchedulerConfig(
                    n_slots=n_slots, cache_len=self.serve.cache_len,
                    seed=self.serve.seed,
                    page_size=self.serve.page_size,
                    n_pages=self.serve.n_pages,
                    prefill_buckets=self.serve.prefill_buckets,
                    chunk_budget=self.serve.chunk_budget,
                    min_chunk=self.serve.min_chunk,
                    preemption=self.serve.preemption,
                    prefix_sharing=self.serve.prefix_sharing,
                    speculative=self.serve.speculative,
                    draft_k=self.serve.draft_k,
                    drafter=self.serve.drafter,
                    mesh_shape=self.serve.mesh_shape,
                    sharding_profile=self.serve.sharding_profile,
                ),
            )
        return self._schedulers[n_slots]

    def generate(self, batch: dict[str, Any]) -> GenerationResult:
        cfg, serve = self.cfg, self.serve
        B = batch["tokens"].shape[0]
        prompt_len = batch["tokens"].shape[1] + (cfg.prefix_len or 0)
        assert prompt_len + serve.max_new_tokens <= serve.cache_len or cfg.supports_long_context or cfg.window_size, (
            f"cache_len {serve.cache_len} too small for {prompt_len}+{serve.max_new_tokens}"
        )
        sched = self._sched_for(B)
        sched.reset_rng(serve.seed)
        steps_before = sched.total_decode_steps
        tokens = np.asarray(batch["tokens"])
        extras = {k: np.asarray(v) for k, v in batch.items() if k != "tokens"}
        for i in range(B):
            sched.submit(
                Request(
                    prompt=tokens[i],
                    max_new_tokens=serve.max_new_tokens,
                    stop_token=serve.stop_token,
                    temperature=serve.temperature,
                    extras={k: v[i : i + 1] for k, v in extras.items()},
                )
            )
        finished = sched.run()

        steps = max(len(rs.tokens) for rs in finished)
        out = np.zeros((B, steps), np.int32)
        for i, rs in enumerate(finished):
            row = rs.tokens
            # Early-retired rows pad with their final token so the result
            # stays rectangular; the static path kept decoding instead.
            out[i] = row + [row[-1]] * (steps - len(row))
        if sched.total_decode_steps > steps_before:
            logits = np.asarray(sched.last_decode_logits)
        else:
            # Zero decode steps this call (max_new_tokens == 1 / instant
            # stops): report this batch's prefill logits, like the static
            # path, rather than a stale array from a previous call.
            logits = np.concatenate([rs.prefill_logits for rs in finished], axis=0)
        return GenerationResult(tokens=out, steps=steps, prefill_logits=logits)

    # -- generation (static-batch reference) --------------------------------
    def generate_static(self, batch: dict[str, Any]) -> GenerationResult:
        """The pre-scheduler static loop: one shared position counter, the
        whole batch decodes until its slowest member finishes."""
        cfg, serve = self.cfg, self.serve
        B = batch["tokens"].shape[0]
        prompt_len = batch["tokens"].shape[1] + (cfg.prefix_len or 0)
        assert prompt_len + serve.max_new_tokens <= serve.cache_len or cfg.supports_long_context or cfg.window_size, (
            f"cache_len {serve.cache_len} too small for {prompt_len}+{serve.max_new_tokens}"
        )
        logits, states = self._prefill(self.params, batch)
        states = self._grow_states(states, prompt_len, B)

        key = jax.random.PRNGKey(serve.seed)
        tok = self._sample(logits[:, -1], key)
        out = [np.asarray(tok)[:, 0]]
        done = np.zeros(B, bool)
        steps = 1
        for i in range(serve.max_new_tokens - 1):
            logits, states = self._decode(self.params, states, tok)
            key, sub = jax.random.split(key)
            tok = self._sample(logits[:, -1], sub)
            col = np.asarray(tok)[:, 0]
            out.append(col)
            steps += 1
            if serve.stop_token >= 0:
                done |= col == serve.stop_token
                if done.all():
                    break
        return GenerationResult(
            tokens=np.stack(out, axis=1), steps=steps, prefill_logits=np.asarray(logits)
        )

    def _sample(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        logits = logits[:, : self.cfg.vocab_size].astype(jnp.float32)
        if self.serve.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return jax.random.categorical(key, logits / self.serve.temperature, axis=-1)[
            :, None
        ].astype(jnp.int32)
