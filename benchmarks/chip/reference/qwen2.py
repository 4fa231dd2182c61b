"""Plain float32 forward pass of Qwen2 (dense), layer by layer.

Follows ``Qwen2ForCausalLM`` of Hugging Face transformers: token embedding
(unscaled), then per layer RMSNorm, q/k/v projections with a bias (o
without), RoPE (rotate-half, theta from the config), grouped-query attention with a
causal mask and scale head_dim ** -0.5, o projection, residual; RMSNorm,
SwiGLU MLP (silu(gate) * up, then down), residual; final RMSNorm and an
untied head. Every product runs in float32 at the highest precision.

Departures, none of them from the published mathematics: several
sequences are packed into one row, kept apart by a segment mask with
positions restarting at each; and only the rows asked for reach the head.
Weights come in as a dict of float32 arrays named as in the checkpoints,
with the head axes split out (``q_proj`` is (hidden, heads, head_dim)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (T, heads, head_dim); pos: (T,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def attention(q, k, v, pos, seg):
    """Causal attention within each segment; seg < 0 marks padding."""
    heads, kv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    s = jnp.einsum("qhe,khe->hqk", q, k) * q.shape[-1] ** -0.5
    ok = (seg[:, None] == seg[None, :]) & (pos[None, :] <= pos[:, None]) & (seg[:, None] >= 0)
    s = jnp.where(ok[None], s, -1e30)
    return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def layer(w, x, pos, seg, *, eps, theta):
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, w["input_layernorm"], eps)
        q = jnp.einsum("td,dhe->the", h, w["q_proj"]) + w["q_bias"]
        k = jnp.einsum("td,dhe->the", h, w["k_proj"]) + w["k_bias"]
        v = jnp.einsum("td,dhe->the", h, w["v_proj"]) + w["v_bias"]
        q = rope(q, pos, theta)
        k = rope(k, pos, theta)
        x = x + jnp.einsum("the,hed->td", attention(q, k, v, pos, seg), w["o_proj"])
        h = rmsnorm(x, w["post_attention_layernorm"], eps)
        g = jnp.einsum("td,df->tf", h, w["gate_proj"])
        u = jnp.einsum("td,df->tf", h, w["up_proj"])
        return x + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, w["down_proj"])


@jax.jit
def embed(g, tokens):
    return g["embed_tokens"][tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(g, x, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x[rows], g["norm"], eps) @ g["lm_head"]
