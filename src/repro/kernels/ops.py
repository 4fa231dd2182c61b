"""Jit'd public wrappers around the Pallas kernels.

* ``flash_attention`` — differentiable (custom_vjp over the fwd/bwd kernels),
  accepts model-layout (B, S, H, D) tensors with GQA broadcast, folds heads
  into the grid dim.
* ``decode_attention_op`` — model-layout decode step.
* ``rglru_op`` / ``mlstm_op`` — recurrence wrappers.
* ``moe_gmm_op`` — grouped matmul with block padding.

``interpret`` is True on the CPU backend (tests validate the kernels through
the Pallas interpreter); on a TPU backend the same code compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import mlstm as _ml
from . import moe_gmm as _gmm
from . import rglru as _rg


def default_interpret() -> bool:
    """True on the CPU, False on a TPU, and an error on any other backend:
    an interpreted kernel on an accelerator would hide the device."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for 'tpu' or interpret on 'cpu'; the "
            f"default backend is {backend!r}"
        )
    return backend == "cpu"


# ==========================================================================
# Flash attention (differentiable)
# ==========================================================================
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, blk_q, blk_k):
    o, _ = _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        interpret=default_interpret(),
    )
    return o


def _flash_fwd(q, k, v, causal, window, blk_q, blk_k):
    o, lse = _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, blk_q=blk_q, blk_k=blk_k,
        interpret=default_interpret(),
    )
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, blk_q, blk_k, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, interpret=default_interpret(),
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    *, causal: bool = True, window: int = 0, blk_q: int = 128, blk_k: int = 128,
) -> jax.Array:
    """Model-layout flash attention with GQA broadcast. Returns (B, S, H, D)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, G, D)).reshape(B, S, H, D)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, S, KV, G, D)).reshape(B, S, H, D)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    o = _flash(fold(q), fold(k), fold(v), causal, window, blk_q, blk_k)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


# ==========================================================================
# Decode attention
# ==========================================================================
def decode_attention_op(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, T, KV, D)
    v_cache: jax.Array,
    k_pos: jax.Array,  # (T,)
    cur_pos: jax.Array,
    *, window: int = 0, blk_k: int = 256,
) -> jax.Array:
    B, _, H, D = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    o = _dec.decode_attention(
        qf, kf, vf, k_pos, cur_pos, window=window, blk_k=blk_k,
        interpret=default_interpret(),
    )
    return o.reshape(B, KV, G, D).reshape(B, 1, H, D)


def paged_decode_attention_op(
    q: jax.Array,  # (B, 1, H, D)
    k_pool: jax.Array,  # (P+1, page, KV, D) shared page pool, or (L, P+1, ...) stacked
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32
    cur_pos: jax.Array,  # (B,) int32
    layer: jax.Array | None = None,  # int32 scalar: the stacked pools' layer
    *, n_lp: int, window: int = 0,
) -> jax.Array:
    """Model-layout paged decode: KV blocks gathered via the page table."""
    B, _, H, D = q.shape
    KV = k_pool.shape[-2]
    G = H // KV
    qf = q.reshape(B, H, D).reshape(B, KV, G, D)
    o = _dec.paged_decode_attention(
        qf, k_pool, v_pool, page_table, cur_pos, layer, n_lp=n_lp, window=window,
        interpret=default_interpret(),
    )
    return o.reshape(B, 1, H, D)


def paged_chunk_attention_op(
    q: jax.Array,  # (B, C, H, D) chunk queries
    k_pool: jax.Array,  # (P+1, page, KV, D) shared page pool (chunk K/V written), or stacked
    v_pool: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32
    start: jax.Array,  # (B,) int32: tokens cached before the chunk
    layer: jax.Array | None = None,  # int32 scalar: the stacked pools' layer
    *, n_lp: int,
) -> jax.Array:
    """Model-layout chunked-prefill attention over the paged KV (dense
    layers). The chunk's own K/V must already be scattered into the pool."""
    B, C, H, D = q.shape
    KV = k_pool.shape[-2]
    G = H // KV
    # Row order c*G + g per kv-head: (B, C, KV, G, D) -> (B, KV, C*G, D).
    qf = q.reshape(B, C, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(B, KV, C * G, D)
    o = _dec.paged_chunk_attention(
        qf, k_pool, v_pool, page_table, start, layer, n_lp=n_lp, group=G,
        interpret=default_interpret(),
    )
    return o.reshape(B, KV, C, G, D).transpose(0, 2, 1, 3, 4).reshape(B, C, H, D)


# --------------------------------------------------------------------------
# Paged kernels under a mesh: per-shard shard_map wrappers
# --------------------------------------------------------------------------
# GSPMD cannot partition a pallas_call, so under a multi-device mesh the
# paged kernels run inside shard_map: each shard calls the single-device op
# on its local q rows / head slice / pool slice. The caller (the attention
# layer) resolves the PartitionSpecs from the actual operand shapes and
# mesh; ``pool_spec`` covers the pools as passed, with a leading layer
# entry for stacked pools, whose ``layer`` scalar every shard reads
# replicated. ``localize_pages`` is set only when the pool is truly
# partitioned across data shards (host page ids are then global — shard d
# owns rows [d * rows_local, (d + 1) * rows_local) of the pool, each block
# ending in its own trash row — so the local table is
# ``global - d * rows_local``).
def _localized(page_table: jax.Array, pool_rows_local: int) -> jax.Array:
    d = jax.lax.axis_index("data").astype(jnp.int32)
    return page_table - d * pool_rows_local


def _shard_paged(op, q, k_pool, v_pool, page_table, vec, layer, *, mesh,
                 q_spec, pool_spec, table_spec, vec_spec, localize_pages):
    """Run a paged op per shard: pool rows ``shape[-4]`` are what a data
    shard owns when ``localize_pages``."""
    rows_local = k_pool.shape[-4] // (mesh.shape["data"] if localize_pages else 1)

    def body(qs, ks, vs, pt, v, *ly):
        if localize_pages:
            pt = _localized(pt, rows_local)
        return op(qs, ks, vs, pt, v, *ly)

    args = (q, k_pool, v_pool, page_table, vec)
    specs = (q_spec, pool_spec, pool_spec, table_spec, vec_spec)
    if layer is not None:
        args, specs = args + (layer,), specs + (P(),)
    return jax.shard_map(
        body, mesh=mesh, in_specs=specs, out_specs=q_spec, check_vma=False,
    )(*args)


def paged_decode_attention_sharded(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    cur_pos: jax.Array,
    layer: jax.Array | None = None,
    *,
    n_lp: int,
    window: int = 0,
    mesh,
    q_spec,
    pool_spec,
    table_spec,
    vec_spec,
    localize_pages: bool = False,
) -> jax.Array:
    """``paged_decode_attention_op`` run per-shard under ``mesh``."""
    return _shard_paged(
        functools.partial(paged_decode_attention_op, n_lp=n_lp, window=window),
        q, k_pool, v_pool, page_table, cur_pos, layer, mesh=mesh,
        q_spec=q_spec, pool_spec=pool_spec, table_spec=table_spec,
        vec_spec=vec_spec, localize_pages=localize_pages,
    )


def paged_chunk_attention_sharded(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    start: jax.Array,
    layer: jax.Array | None = None,
    *,
    n_lp: int,
    mesh,
    q_spec,
    pool_spec,
    table_spec,
    vec_spec,
) -> jax.Array:
    """``paged_chunk_attention_op`` run per-shard under ``mesh``. Chunks
    are single-slot (B == 1), so only the head/model axis partitions —
    the caller falls back to the XLA gather path when the pool is
    data-partitioned."""
    return _shard_paged(
        functools.partial(paged_chunk_attention_op, n_lp=n_lp),
        q, k_pool, v_pool, page_table, start, layer, mesh=mesh,
        q_spec=q_spec, pool_spec=pool_spec, table_spec=table_spec,
        vec_spec=vec_spec, localize_pages=False,
    )


# ==========================================================================
# Recurrences
# ==========================================================================
def rglru_op(a: jax.Array, b: jax.Array, h0: jax.Array | None = None, **kw) -> jax.Array:
    return _rg.rglru_scan_kernel(a, b, h0, interpret=default_interpret(), **kw)


def mlstm_op(
    q: jax.Array,  # (B, S, nh, dh) NOT pre-scaled
    k: jax.Array,
    v: jax.Array,
    i_pre: jax.Array,  # (B, S, nh)
    f_pre: jax.Array,
    *, chunk: int = 64,
) -> jax.Array:
    B, S, nh, dh = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * nh, S, dh)
    foldg = lambda x: x.transpose(0, 2, 1).reshape(B * nh, S)
    h = _ml.mlstm_chunk_kernel(
        fold(q), fold(k), fold(v), foldg(i_pre), foldg(f_pre),
        chunk=chunk, interpret=default_interpret(),
    )
    return h.reshape(B, nh, S, dh).transpose(0, 2, 1, 3)


# ==========================================================================
# MoE grouped matmul
# ==========================================================================
def moe_gmm_op(
    lhs: jax.Array,  # (M, K), rows sorted by group, boundaries % blk_m == 0
    rhs: jax.Array,  # (G, K, N)
    group_sizes: jax.Array,  # (G,) multiples of blk_m summing to M
    *, blk_m: int = 128, blk_n: int = 128,
) -> jax.Array:
    M = lhs.shape[0]
    gm = _gmm.pad_group_sizes_to_blocks(group_sizes, blk_m, M)
    return _gmm.gmm(lhs, rhs, gm, blk_m=blk_m, blk_n=blk_n, interpret=default_interpret())
