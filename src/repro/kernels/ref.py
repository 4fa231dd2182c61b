"""Pure-jnp oracles for every kernel in this package.

These are the ground truth the interpret-mode kernels are asserted against
(tests/test_kernels.py sweeps shapes and dtypes). They are deliberately
naive — full softmax, step-by-step recurrences, per-group python loops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30


# -- attention ----------------------------------------------------------------
def sdpa_ref(
    q: jax.Array,  # (BH, Sq, D)
    k: jax.Array,  # (BH, Sk, D)
    v: jax.Array,
    *, causal: bool = True, window: int = 0, sm_scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    sm = sm_scale if sm_scale is not None else D ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(F32), k.astype(F32)) * sm
    q_pos = q_offset + jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m = m & (k_pos <= q_pos)
    if window:
        m = m & (k_pos > q_pos - window)
    s = jnp.where(m[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(F32)).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,  # (BKV, G, D)
    k: jax.Array,  # (BKV, T, D)
    v: jax.Array,
    k_pos: jax.Array,  # (T,)
    cur_pos: jax.Array,
    *, window: int = 0, sm_scale: float | None = None,
) -> jax.Array:
    D = q.shape[-1]
    sm = sm_scale if sm_scale is not None else D ** -0.5
    s = jnp.einsum("bgd,btd->bgt", q.astype(F32), k.astype(F32)) * sm
    valid = (k_pos <= cur_pos) & (k_pos >= 0)
    if window:
        valid = valid & (k_pos > cur_pos - window)
    s = jnp.where(valid[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgt,btd->bgd", p, v.astype(F32)).astype(q.dtype)


def _paged_gather(pool: jax.Array, page_table: jax.Array, n_lp: int) -> jax.Array:
    """(B, n_lp * page, KV, D): each slot's first ``n_lp`` table pages."""
    B = page_table.shape[0]
    g = pool[page_table[:, :n_lp]]  # (B, n_lp, page, KV, D)
    return g.reshape(B, -1, *pool.shape[2:]).astype(F32)


def paged_decode_attention_ref(
    q: jax.Array,  # (B, KV, G, D)
    k_pool: jax.Array,  # (P+1, page, KV, D)
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages)
    cur_pos: jax.Array,  # (B,)
    *, n_lp: int, window: int = 0, sm_scale: float | None = None,
) -> jax.Array:
    """One query token per slot against its table's first ``n_lp`` pages;
    logical slot s holds position s, or in a ring of ``window`` the latest
    position p <= cur_pos with p % window == s."""
    D = q.shape[-1]
    sm = sm_scale if sm_scale is not None else D ** -0.5
    k = _paged_gather(k_pool, page_table, n_lp)
    v = _paged_gather(v_pool, page_table, n_lp)
    idx = jnp.arange(k.shape[1])[None, :]
    cur = cur_pos[:, None]
    k_pos = idx
    if window:
        k_pos = jnp.where(idx < window, cur - ((cur - idx) % window), -1)
    valid = (k_pos <= cur) & (k_pos >= 0)
    if window:
        valid = valid & (k_pos > cur - window)
    s = jnp.einsum("bkgd,btkd->bkgt", q.astype(F32), k) * sm
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p, v).astype(q.dtype)


def paged_chunk_attention_ref(
    q: jax.Array,  # (B, KV, C*G, D), row r = c*G + g
    k_pool: jax.Array,  # (P+1, page, KV, D)
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages)
    start: jax.Array,  # (B,) tokens cached before the chunk
    *, n_lp: int, group: int, sm_scale: float | None = None,
) -> jax.Array:
    """Chunk token c of slot b, at position start[b] + c, against logical
    slots 0 .. start[b] + c of its table's first ``n_lp`` pages."""
    D = q.shape[-1]
    sm = sm_scale if sm_scale is not None else D ** -0.5
    k = _paged_gather(k_pool, page_table, n_lp)
    v = _paged_gather(v_pool, page_table, n_lp)
    q_pos = start[:, None] + jnp.arange(q.shape[2])[None, :] // group  # (B, R)
    valid = jnp.arange(k.shape[1])[None, None, :] <= q_pos[:, :, None]  # (B, R, T)
    s = jnp.einsum("bkrd,btkd->bkrt", q.astype(F32), k) * sm
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkrt,btkd->bkrd", p, v).astype(q.dtype)


# -- RG-LRU ---------------------------------------------------------------
def rglru_ref(a: jax.Array, b: jax.Array, h0: jax.Array | None = None) -> jax.Array:
    """Sequential h_t = a_t h_{t-1} + b_t. a/b: (B, T, D)."""
    B, T, D = a.shape
    h = jnp.zeros((B, D), F32) if h0 is None else h0.astype(F32)

    def step(h, t):
        h = a[:, t].astype(F32) * h + b[:, t].astype(F32)
        return h, h

    _, hs = jax.lax.scan(step, h, jnp.arange(T))
    return hs.swapaxes(0, 1).astype(a.dtype)


# -- mLSTM ---------------------------------------------------------------
def mlstm_ref(
    q: jax.Array,  # (BH, S, dh) pre-scaled
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (BH, S)
    f_pre: jax.Array,
) -> jax.Array:
    BH, S, dh = q.shape

    def step(carry, t):
        C, n, m = carry
        qt, kt, vt = q[:, t].astype(F32), k[:, t].astype(F32), v[:, t].astype(F32)
        at = i_pre[:, t].astype(F32)
        lf = -jax.nn.softplus(-f_pre[:, t].astype(F32))
        m_new = jnp.maximum(lf + m, at)
        fp = jnp.exp(lf + m - m_new)
        ip = jnp.exp(at - m_new)
        C = C * fp[:, None, None] + ip[:, None, None] * jnp.einsum("bd,be->bde", kt, vt)
        n = n * fp[:, None] + ip[:, None] * kt
        num = jnp.einsum("bd,bde->be", qt, C)
        den = jnp.einsum("bd,bd->b", qt, n)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[:, None]
        return (C, n, m_new), h

    init = (
        jnp.zeros((BH, dh, dh), F32),
        jnp.zeros((BH, dh), F32),
        jnp.full((BH,), -1e30, F32),
    )
    _, hs = jax.lax.scan(step, init, jnp.arange(S))
    return hs.swapaxes(0, 1).astype(q.dtype)


# -- grouped matmul ---------------------------------------------------------
def gmm_ref(lhs: jax.Array, rhs: jax.Array, group_map: jax.Array, blk_m: int) -> jax.Array:
    """Per-m-block dense matmul against the mapped group's rhs."""
    M, K = lhs.shape
    out = []
    for i in range(M // blk_m):
        g = int(group_map[i])
        out.append(lhs[i * blk_m : (i + 1) * blk_m].astype(F32) @ rhs[g].astype(F32))
    return jnp.concatenate(out, axis=0).astype(lhs.dtype)
