"""Shared layers: norms, embeddings, RoPE, dense FFNs, chunked cross-entropy.

Numerics policy (uniform across the framework):
  * params stored in ``cfg.param_dtype`` (fp32 master for training, bf16 ok
    for pure serving)
  * matmuls run in ``cfg.compute_dtype`` (bf16) with fp32 accumulation
    (``preferred_element_type``)
  * softmax / norms / recurrence states / losses in fp32
"""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.schema import ParamSpec
from repro.sharding.rules import ShardingCtx, constrain

F32 = jnp.float32


def cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# -- norms --------------------------------------------------------------------
def rmsnorm_schema(d: int) -> dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def rmsnorm(p: dict[str, Any], x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + 0.0) * p["scale"].astype(F32)).astype(x.dtype)


def layernorm_schema(d: int) -> dict[str, ParamSpec]:
    return {
        "scale": ParamSpec((d,), ("embed",), init="ones"),
        "bias": ParamSpec((d,), ("embed",), init="zeros"),
    }


def layernorm(p: dict[str, Any], x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(F32) + p["bias"].astype(F32)).astype(x.dtype)


def groupnorm_heads(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head normalisation (..., nh, dh) used by xLSTM blocks."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# -- embedding / unembedding ----------------------------------------------------
def embedding_schema(cfg: ModelConfig) -> dict[str, ParamSpec]:
    v = cfg.padded_vocab
    sch = {"tok": ParamSpec((v, cfg.d_model), ("vocab", "embed"), init="embed", scale=0.02)}
    if not cfg.tie_embeddings:
        sch["unembed"] = ParamSpec(
            (cfg.d_model, v), ("embed", "vocab"), init="normal", scale=0.02
        )
    return sch


def embed_tokens(p: dict[str, Any], cfg: ModelConfig, tokens: jax.Array, sctx: ShardingCtx) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cdt(cfg))
    return constrain(x, ("batch", "seq", "embed_act"), sctx)


def unembed_weight(p: dict[str, Any], cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return p["tok"].T
    return p["unembed"]


# -- activations / dense FFN -----------------------------------------------------
def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name}")


def mlp_schema(cfg: ModelConfig, d_ff: int | None = None) -> dict[str, ParamSpec]:
    ff = d_ff or cfg.d_ff
    d = cfg.d_model
    return {
        "gate": ParamSpec((d, ff), ("embed", "mlp")),
        "up": ParamSpec((d, ff), ("embed", "mlp")),
        "down": ParamSpec((ff, d), ("mlp", "embed")),
    }


def mlp(p: dict[str, Any], cfg: ModelConfig, x: jax.Array, sctx: ShardingCtx) -> jax.Array:
    dt = cdt(cfg)
    g = jnp.einsum("...d,df->...f", x, p["gate"].astype(dt), preferred_element_type=dt)
    u = jnp.einsum("...d,df->...f", x, p["up"].astype(dt), preferred_element_type=dt)
    h = (_act(cfg.act, g.astype(F32)) * u.astype(F32)).astype(dt)
    h = constrain(h, ("batch", "seq", "mlp"), sctx)
    # Row-parallel matmul: with the mlp dim TP-sharded the output is a
    # cross-shard partial sum. Emitting it at the compute dtype makes the
    # Megatron all-reduce ride in bf16 (half the ICI bytes of an fp32
    # reduce); the MXU still accumulates fp32 internally per shard.
    with jax.named_scope("mlp.down_proj"):
        y = jnp.einsum("...f,fd->...d", h, p["down"].astype(dt), preferred_element_type=dt)
        return constrain(y.astype(dt), ("batch", "seq", "embed_act"), sctx)


# -- RoPE ------------------------------------------------------------------------
def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (S,) shared across the
    batch, or (B, S) per-sequence (continuous-batching decode, where every
    slot sits at its own position)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions.astype(F32)[..., None] * freqs  # (..., S, d/2)
    # Insert singleton head axes so the seq axis of `angles` lines up with
    # the seq axis of x (which may carry trailing head dims). Shared (S,)
    # positions rely on right-aligned broadcast over the batch axes; batched
    # positions already carry them, so only the head axes are missing.
    if positions.ndim <= 1:
        n_insert = x.ndim - angles.ndim - 1
    else:
        n_insert = x.ndim - positions.ndim - 1
    for _ in range(n_insert):
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(
        x.dtype
    )


# -- chunked cross-entropy ---------------------------------------------------------
def chunked_softmax_xent(
    x: jax.Array,  # (B, S, d) final hidden states
    w_unembed: jax.Array,  # (d, V)
    labels: jax.Array,  # (B, S) int32; -1 = masked
    cfg: ModelConfig,
    sctx: ShardingCtx,
) -> tuple[jax.Array, jax.Array]:
    """Per-token xent without ever materialising (B, S, V) in fp32.

    Scans over sequence blocks of ``cfg.xent_chunk``: each block computes
    bf16 logits (B, C, V), fp32 logsumexp, gathers the label logit, and
    discards the block. Returns (sum_loss, n_valid_tokens).
    """
    B, S, d = x.shape
    V = w_unembed.shape[-1]
    chunk = max(1, min(cfg.xent_chunk, S))
    n_chunks = S // chunk
    rem = S - n_chunks * chunk
    dt = cdt(cfg)
    w = w_unembed.astype(dt)

    def block_loss(xb: jax.Array, lb: jax.Array) -> tuple[jax.Array, jax.Array]:
        logits = jnp.einsum("bcd,dv->bcv", xb, w, preferred_element_type=F32)
        logits = constrain(logits, ("batch", "seq", "vocab"), sctx)
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B, C)
        lbl = jnp.clip(lb, 0, V - 1)
        gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
        valid = (lb >= 0).astype(F32)
        return jnp.sum((lse - gold) * valid), jnp.sum(valid)

    if n_chunks > 0:
        xs = x[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, d).swapaxes(0, 1)
        ls = labels[:, : n_chunks * chunk].reshape(B, n_chunks, chunk).swapaxes(0, 1)

        def body(carry, inp):
            xb, lb = inp
            s, n = block_loss(xb, lb)
            return (carry[0] + s, carry[1] + n), None

        unroll = bool(int(os.environ.get("REPRO_UNROLL_SCANS", "0")))
        (total, count), _ = jax.lax.scan(
            body, (jnp.zeros((), F32), jnp.zeros((), F32)), (xs, ls),
            unroll=True if unroll else 1,
        )
    else:
        total, count = jnp.zeros((), F32), jnp.zeros((), F32)
    if rem:
        s, n = block_loss(x[:, n_chunks * chunk :], labels[:, n_chunks * chunk :])
        total, count = total + s, count + n
    return total, count


def logits_for_positions(
    x: jax.Array, w_unembed: jax.Array, cfg: ModelConfig, sctx: ShardingCtx
) -> jax.Array:
    """Full logits for small (decode) token counts: (B, Q, V), the vocabulary
    split over ``model`` under tensor parallelism."""
    with jax.named_scope("head.logits"):
        logits = jnp.einsum(
            "bqd,dv->bqv", x, w_unembed.astype(cdt(cfg)), preferred_element_type=F32
        )
        return constrain(logits, ("batch", None, "vocab"), sctx)


# -- misc -----------------------------------------------------------------------
def causal_conv1d_train(x: jax.Array, w: jax.Array, b: jax.Array | None) -> jax.Array:
    """Depthwise causal conv. x: (B, S, C), w: (K, C)."""
    K, C = w.shape
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=F32)
    for i in range(K):  # K is tiny (4); unrolled adds, no gather needed
        out = out + pad[:, i : i + x.shape[1], :].astype(F32) * w[i].astype(F32)
    if b is not None:
        out = out + b.astype(F32)
    return out.astype(x.dtype)


def causal_conv1d_step(
    x_t: jax.Array, conv_state: jax.Array, w: jax.Array, b: jax.Array | None
) -> tuple[jax.Array, jax.Array]:
    """One decode step. x_t: (B, C); conv_state: (B, K-1, C) past inputs."""
    K, C = w.shape
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, K, C)
    out = jnp.einsum("bkc,kc->bc", window.astype(F32), w.astype(F32))
    if b is not None:
        out = out + b.astype(F32)
    return out.astype(x_t.dtype), window[:, 1:, :]
