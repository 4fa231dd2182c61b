"""Serving step factories: prefill and single-token decode.

``decode``/``long`` shapes lower these (never train_step). Params are bf16
(no masters/optimizer); decode states follow the arch's decode sharding
profile (KV seq over model when kv-heads can't split; recurrent state
matrices over (data, model) for the batch=1 500k cell).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import lm
from repro.models.schema import (
    ParamSpec,
    abstract_params,
    init_params,
    is_spec,
    sharding_tree,
)
from repro.sharding.rules import ShardingCtx, pspec_for


def serve_param_specs(cfg: ModelConfig, sctx: ShardingCtx) -> Any:
    """bf16 serving weights (abstract)."""
    schema = lm.model_schema(cfg)
    return abstract_params(schema, sctx, dtype=jnp.bfloat16)


def decode_state_specs(
    cfg: ModelConfig, shape: ShapeConfig, sctx: ShardingCtx
) -> Any:
    schema = lm.decode_state_schema(cfg, shape.global_batch, shape.seq_len)
    return abstract_params(schema, sctx)


def init_decode_state(
    cfg: ModelConfig,
    batch: int,
    s_max: int,
    start_pos: int = 0,
    sctx: ShardingCtx | None = None,
) -> dict[str, Any]:
    """Real zeroed decode state (smoke tests / serving engine). With a
    meshed ``sctx`` every layer leaf is placed at its profile-resolved
    NamedSharding (heads/kv over model, replicated fallback)."""
    state = _materialise(lm.decode_state_schema(cfg, batch, s_max), sctx)
    state["pos"] = jnp.asarray(start_pos, jnp.int32)
    return state


def init_paged_decode_state(
    cfg: ModelConfig,
    batch: int,
    s_max: int,
    pages,
    sctx: ShardingCtx | None = None,
) -> dict[str, Any]:
    """Decode state whose dense/windowed KV leaves are shared page pools
    (``pages``: a serve.pages.PageLayout); other state kinds stay per-slot.
    With a meshed ``sctx`` the pool leaves shard on kv_heads/head_dim over
    ``model`` (page axes replicated): every device owns its slice of every
    page, so page-table indirection stays a device-local gather."""
    state = _materialise(lm.decode_state_schema(cfg, batch, s_max, pages=pages), sctx)
    state["pos"] = jnp.zeros((batch,), jnp.int32)
    return state


def _materialise(schema: dict[str, Any], sctx: ShardingCtx | None) -> dict[str, Any]:
    """A decode-state schema as arrays. With a mesh each layer leaf is made
    in place at its resolved sharding, so no device ever holds a whole leaf:
    at full width one KV pool of every head is more than a chip holds."""
    key = jax.random.PRNGKey(0)
    if sctx is None or sctx.mesh is None:
        return init_params(schema, key)
    shardings = {k: sctx.replicated() for k in schema}
    shardings["layers"] = sharding_tree(schema["layers"], sctx)
    return jax.jit(lambda k: init_params(schema, k), out_shardings=shardings)(key)


def decode_state_shardings(
    cfg: ModelConfig, batch: int, s_max: int, sctx: ShardingCtx, pages=None
) -> Any:
    """NamedShardings for the batched decode state's ``layers`` subtree
    (None without a mesh) — the scheduler pins every step program's output
    layout to these so state placement never drifts between steps."""
    if sctx.mesh is None:
        return None
    schema = lm.decode_state_schema(cfg, batch, s_max, pages=pages)
    return sharding_tree(schema["layers"], sctx)


def fresh_slot_layers(cfg: ModelConfig, s_max: int) -> Any:
    """Batch-1 layer states a chunked prefill (re)starts a slot from.

    Zeroed storage with the recurrence log-stabilisers at their
    empty-recurrence values (xLSTM ``m`` at -1e30, sLSTM ``n`` at 1e-6) —
    the state a from-scratch prefill would initialise internally, so
    streaming chunk 0 against a freshly reset (or recompute-resumed) slot
    is numerically the same computation."""
    from repro.models import blocks as blk

    layers = init_decode_state(cfg, 1, s_max)["layers"]
    return blk.fresh_stack_states(cfg, layers)


def token_specs(shape: ShapeConfig, sctx: ShardingCtx) -> jax.ShapeDtypeStruct:
    B = shape.global_batch
    if sctx.mesh is None:
        return jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return jax.ShapeDtypeStruct(
        (B, 1),
        jnp.int32,
        sharding=NamedSharding(
            sctx.mesh, pspec_for((B, 1), ("batch", None), sctx.profile, sctx.mesh)
        ),
    )


def make_decode_step(cfg: ModelConfig, sctx: ShardingCtx) -> Callable:
    def serve_step(params, states, token):
        logits, new_states = lm.decode_step(params, cfg, states, token, sctx)
        # Greedy next token: keeps the lowered program end-to-end (sampling
        # strategies live in the engine, not the hot step).
        next_tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None].astype(jnp.int32)
        return next_tok, logits, new_states

    return serve_step


def make_prefill_step(cfg: ModelConfig, sctx: ShardingCtx) -> Callable:
    def prefill_step(params, batch):
        return lm.prefill(params, cfg, batch, sctx)

    return prefill_step
