"""Attention: GQA/MQA/MHA (+qk-norm, +qkv-bias, windows, prefix-LM, cross)
and DeepSeek-style MLA with compressed-KV decode.

Three execution modes share one weight schema:
  * ``train``   — full-sequence, query-block-chunked softmax attention (the
                  XLA-native flash equivalent; the Pallas kernel is the TPU
                  hot path, selected with backend="pallas")
  * ``prefill`` — train-mode math + returns the KV cache
  * ``decode``  — one query token against the cache (ring buffer for
                  windowed layers so 500k-context hybrids stay O(window))
"""
from __future__ import annotations

import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import F32, apply_rope, cdt, rmsnorm, rmsnorm_schema
from repro.models.schema import ParamSpec
from repro.sharding.rules import ShardingCtx, constrain

NEG_INF = -1e30
# Logical axes of a stacked page pool: (layer, pool rows, page, kv, hd).
_POOL_AXES = ("layer", "pages", None, "kv_heads", "head_dim")


def _pallas_ok(sctx: ShardingCtx) -> bool:
    """Single-device: Pallas kernels are called directly (GSPMD cannot
    partition a pallas_call). Under a multi-device mesh the *paged*
    kernels instead run per-shard via shard_map when the operands
    partition cleanly (``_paged_kernel_specs``); other kernel call sites
    (flash prefill) still route through the partitionable XLA paths."""
    return sctx.device_count() == 1


def _paged_kernel_specs(
    sctx: ShardingCtx, *, B: int, H: int, KV: int, total_pages: int,
    batch_sharded: bool,
):
    """PartitionSpecs to run a paged Pallas kernel per-shard under the
    current mesh, or None when the operands don't partition cleanly (the
    XLA gather path handles those layouts through GSPMD). The pools are
    the layer stack's, read at a replicated layer index.

    The head axis splits over ``model`` when it divides both q and KV
    heads. The batch axis (decode only: ``batch_sharded``) splits over
    ``data`` together with the pool's page axis — but only when the pool
    is *truly* partitioned (``sctx.pool_data_shards``), because only then
    do host page ids localize per shard (shard-local sub-pools with their
    own trash rows). A replicated pool under ``data > 1`` still works:
    each data shard keeps the full pool and its slice of slots.
    """
    from jax.sharding import PartitionSpec as P

    if sctx.mesh is None or sctx.device_count() == 1:
        return None
    msize, dsize = sctx.axis_size("model"), sctx.axis_size("data")
    if sctx.device_count() != msize * dsize:
        return None  # extra mesh axes (pod) in play — XLA path
    if msize > 1 and (H % msize or KV % msize):
        return None
    m = "model" if msize > 1 else None
    d = None
    localize = False
    if dsize > 1:
        if not batch_sharded or B % dsize:
            return None
        d = "data"
        localize = sctx.pool_data_shards == dsize and total_pages % dsize == 0
    pages = "data" if localize else None
    return {
        "mesh": sctx.mesh,
        "q_spec": P(d, None, m, None),
        "pool_spec": P(None, pages, None, m, None),  # (layer, rows, page, KV, D)
        "table_spec": P(d, None),
        "vec_spec": P(d),
        "localize_pages": localize,
    }


# ==========================================================================
# Schemas
# ==========================================================================
def gqa_schema(cfg: ModelConfig, cross: bool = False) -> dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    sch: dict[str, Any] = {
        "wq": ParamSpec((d, nq, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, nkv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((nq, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        sch["bq"] = ParamSpec((nq, hd), ("heads", "head_dim"), init="zeros")
        sch["bk"] = ParamSpec((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
        sch["bv"] = ParamSpec((nkv, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        sch["q_norm"] = {"scale": ParamSpec((hd,), (None,), init="ones")}
        sch["k_norm"] = {"scale": ParamSpec((hd,), (None,), init="ones")}
    return sch


def mla_schema(cfg: ModelConfig) -> dict[str, Any]:
    m = cfg.mla
    d, nq = cfg.d_model, cfg.n_heads
    qk = m.nope_dim + m.rope_dim
    return {
        "wq_a": ParamSpec((d, m.q_lora), ("embed", "q_lora")),
        "q_norm": {"scale": ParamSpec((m.q_lora,), (None,), init="ones")},
        "wq_b": ParamSpec((m.q_lora, nq, qk), ("q_lora", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, m.kv_lora + m.rope_dim), ("embed", "kv_lora")),
        "kv_norm": {"scale": ParamSpec((m.kv_lora,), (None,), init="ones")},
        "wk_b": ParamSpec((m.kv_lora, nq, m.nope_dim), ("kv_lora", "heads", "head_dim")),
        "wv_b": ParamSpec((m.kv_lora, nq, m.v_dim), ("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((nq, m.v_dim, d), ("heads", "head_dim", "embed")),
    }


def attention_schema(cfg: ModelConfig, cross: bool = False) -> dict[str, Any]:
    if cfg.attn_kind == "mla" and not cross:
        return mla_schema(cfg)
    return gqa_schema(cfg, cross=cross)


# ==========================================================================
# Caches
# ==========================================================================
class KVCache(NamedTuple):
    """Dense GQA cache. ``k``/``v``: (B, S_max, n_kv, hd). For windowed layers
    S_max == window and writes wrap (ring buffer). Paged, ``k``/``v`` are the
    whole layer stack's page pools, (L, P+1, page, n_kv, hd), read and
    written at the block's layer index."""

    k: jax.Array
    v: jax.Array


class MLACache(NamedTuple):
    """Compressed cache: ``ckv``: (B, S_max, kv_lora); ``krope``: (B, S_max, rope_dim)."""

    ckv: jax.Array
    krope: jax.Array


def init_kv_cache(cfg: ModelConfig, batch: int, s_max: int, windowed: bool) -> dict[str, ParamSpec]:
    hd = cfg.resolved_head_dim
    length = min(cfg.window_size, s_max) if windowed and cfg.window_size else s_max
    seq_axis = "window" if windowed and cfg.window_size else "kv_seq"
    return {
        "k": ParamSpec((batch, length, cfg.n_kv_heads, hd), ("batch", seq_axis, "kv_heads", "head_dim"), dtype=jnp.bfloat16, init="zeros"),
        "v": ParamSpec((batch, length, cfg.n_kv_heads, hd), ("batch", seq_axis, "kv_heads", "head_dim"), dtype=jnp.bfloat16, init="zeros"),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, s_max: int) -> dict[str, ParamSpec]:
    m = cfg.mla
    return {
        "ckv": ParamSpec((batch, s_max, m.kv_lora), ("batch", "kv_seq", "kv_lora"), dtype=jnp.bfloat16, init="zeros"),
        "krope": ParamSpec((batch, s_max, m.rope_dim), ("batch", "kv_seq", None), dtype=jnp.bfloat16, init="zeros"),
    }


# ==========================================================================
# Masking
# ==========================================================================
def _mask(
    q_pos: jax.Array,  # (Q,) int32 absolute positions
    k_pos: jax.Array,  # (K,)
    kind: str,  # causal | bidir | prefix | window
    window: int = 0,
    prefix_len: int = 0,
    k_valid: jax.Array | None = None,  # (K,) bool extra validity (ring buffers)
) -> jax.Array:
    q = q_pos[:, None]
    k = k_pos[None, :]
    if kind == "bidir":
        m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    elif kind == "causal":
        m = k <= q
    elif kind == "prefix":
        m = (k <= q) | (k < prefix_len)
    elif kind == "window":
        m = (k <= q) & (k > q - window)
    else:
        raise ValueError(f"unknown mask kind {kind}")
    if k_valid is not None:
        m = m & k_valid[None, :]
    return m


# ==========================================================================
# Core softmax attention (query-block chunked — XLA flash equivalent)
# ==========================================================================
def _sdpa_chunked(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, KV, D)
    v: jax.Array,  # (B, T, KV, Dv)
    q_pos: jax.Array,  # (S,)
    k_pos: jax.Array,  # (T,)
    mask_kind: str,
    cfg: ModelConfig,
    sctx: ShardingCtx,
    window: int = 0,
    prefix_len: int = 0,
    scale: float | None = None,
) -> jax.Array:
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV  # queries per kv head
    sc = scale if scale is not None else D ** -0.5
    # KV heads are broadcast to the full H layout so the contraction keeps a
    # single head axis. With heads TP-sharded, a (KV, G) split would force
    # XLA to reshard inside the chunk loop (measured: per-chunk all-reduces);
    # the broadcast fuses into the dot and keeps TP to one all-reduce at the
    # o-projection.
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, H, D)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, KV, G, Dv)).reshape(B, T, H, Dv)
    k = constrain(k, ("batch", None, "heads", None), sctx)
    v = constrain(v, ("batch", None, "heads", None), sctx)
    # Query-chunk size adapts to a fp32-score budget so long-context prefill
    # can never materialise a multi-GB score block on one chip.
    b_loc = sctx.local_size(B, "batch")
    h_loc = sctx.local_size(H, "heads")
    budget = 256 * 2**20
    fit = budget // max(b_loc * h_loc * T * 4, 1)
    chunk = max(1, min(cfg.attn_q_chunk, S, max(64, int(fit))))

    def block(qb: jax.Array, qpb: jax.Array) -> jax.Array:
        # qb: (B, C, H, D)
        s = jnp.einsum("bchd,bthd->bhct", qb, k, preferred_element_type=F32) * sc
        m = _mask(qpb, k_pos, mask_kind, window=window, prefix_len=prefix_len)
        s = jnp.where(m[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhct,bthe->bche", p.astype(cdt(cfg)), v, preferred_element_type=F32)
        return o.astype(cdt(cfg))  # (B, C, H, Dv)

    n_chunks = S // chunk
    rem = S - n_chunks * chunk
    outs = []
    if n_chunks > 0:
        qs = q[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, H, D)
        qs = jnp.moveaxis(qs, 1, 0)  # (n, B, C, H, D)
        qp = q_pos[: n_chunks * chunk].reshape(n_chunks, chunk)
        if bool(int(os.environ.get("REPRO_UNROLL_SCANS", "0"))):
            o = jnp.stack([block(qs[i], qp[i]) for i in range(n_chunks)])
        else:
            o = jax.lax.map(lambda args: block(*args), (qs, qp))
        outs.append(jnp.moveaxis(o, 0, 1).reshape(B, n_chunks * chunk, H, Dv))
    if rem:
        outs.append(block(q[:, n_chunks * chunk :], q_pos[n_chunks * chunk :]))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out


def _sdpa_span(
    q: jax.Array,  # (B, C, H, D) query span (C == 1 for decode)
    k: jax.Array,  # (B, T, KV, D)
    v: jax.Array,  # (B, T, KV, Dv)
    k_pos: jax.Array,  # (B, T) absolute positions held in each row's cache slots
    q_pos: jax.Array,  # (B, C) absolute positions of the query tokens
    cfg: ModelConfig,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    """Masked attention of a query span against position-tagged cache slots.

    Validity is purely positional — ``k_pos`` entries of -1 (never-written
    ring slots, padded chunk tails) and entries beyond each query's causal
    horizon are masked, so the same routine serves single-token decode and
    multi-token chunked prefill over dense, windowed, and paged layouts.
    """
    B, C, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    Dv = v.shape[-1]
    sc = scale if scale is not None else D ** -0.5
    if G > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, KV, G, D)).reshape(B, T, H, D)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, KV, G, Dv)).reshape(B, T, H, Dv)
    s = jnp.einsum("bchd,bthd->bhct", q, k, preferred_element_type=F32) * sc
    kp = k_pos[:, None, :]  # (B, 1, T)
    qp = q_pos[:, :, None]  # (B, C, 1)
    valid = (kp <= qp) & (kp >= 0)
    if window:
        valid = valid & (kp > qp - window)
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhct,bthe->bche", p.astype(cdt(cfg)), v, preferred_element_type=F32)
    return o.astype(cdt(cfg))  # (B, C, H, Dv)


def _sdpa_decode(
    q: jax.Array,  # (B, 1, H, D)
    k: jax.Array,  # (B, T, KV, D)
    v: jax.Array,  # (B, T, KV, Dv)
    k_pos: jax.Array,  # (B, T) absolute positions held in each row's cache slots
    cur_pos: jax.Array,  # (B,): position of each row's query token
    cfg: ModelConfig,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    return _sdpa_span(q, k, v, k_pos, cur_pos[:, None], cfg, window=window, scale=scale)


# ==========================================================================
# Chunked-prefill cache streaming (one slot, C tokens per program)
# ==========================================================================
def _chunk_attend(
    q: jax.Array,  # (1, C, H, D)
    k: jax.Array,  # (1, C, KV, D) chunk keys (rope applied)
    v: jax.Array,  # (1, C, KV, Dv)
    cache: KVCache,
    cfg: ModelConfig,
    sctx: ShardingCtx,
    *,
    qpos: jax.Array,  # (C,) absolute positions of the chunk tokens
    valid_tok: jax.Array,  # (C,) True for real (non-padded) tokens
    start: jax.Array,  # scalar: tokens already cached before this chunk
    chunk_len: jax.Array,  # scalar: number of real tokens in the chunk
    window: int,
    page_table: jax.Array | None,  # (1, max_pages) when the leaf is paged
    layer: jax.Array | None,  # the stacked pools' layer when the leaf is paged
) -> tuple[jax.Array, KVCache]:
    dt = cdt(cfg)
    B, C = q.shape[0], q.shape[1]
    q_pos_b = jnp.broadcast_to(qpos[None, :], (B, C))

    if page_table is not None:
        # The pools are the whole stack's (L, P+1, page, KV, D): this layer
        # reads and writes them at ``layer`` and nothing is sliced out.
        page = cache.k.shape[2]
        max_pages = page_table.shape[1]
        trash = cache.k.shape[1] - 1
        if window:
            n_lp = min(-(-window // page), max_pages)
            # Read the pre-write ring plus the chunk keys side by side.
            sel = page_table[:, :n_lp]
            T = n_lp * page
            kold = cache.k[layer, sel].reshape(B, T, *cache.k.shape[3:]).astype(dt)
            vold = cache.v[layer, sel].reshape(B, T, *cache.v.shape[3:]).astype(dt)
            k_pos_old = _ring_positions(T, window, start - 1)
            k_pos_c = jnp.where(valid_tok, qpos, -1)
            kk = jnp.concatenate([kold, k.astype(dt)], axis=1)
            vv = jnp.concatenate([vold, v.astype(dt)], axis=1)
            k_pos = jnp.concatenate([k_pos_old, k_pos_c])[None, :]
            out = _sdpa_span(q, kk, vv, k_pos, q_pos_b, cfg, window=window)
            # Ring write: only the last min(window, chunk_len) real tokens
            # survive; everything else (pads, ring-evicted early tokens)
            # goes to the trash page so no live page is ever aliased.
            keep = valid_tok & (qpos >= start + chunk_len - window)
            lslot = qpos % window
            pid = jnp.where(keep, page_table[0, lslot // page], trash)
            off = lslot % page
            ck = cache.k.at[layer, pid, off].set(k[0].astype(cache.k.dtype))
            cv = cache.v.at[layer, pid, off].set(v[0].astype(cache.v.dtype))
        else:
            # Dense: scatter the chunk into its pages first (pads -> trash),
            # then attend over the whole table — stale or trash-backed slots
            # fall out of the positional mask automatically.
            #
            # Shared-page invariant: with prefix sharing a table entry may
            # map a page other slots also read. This write is safe because
            # the scheduler (a) only streams chunks at or past the slot's
            # first unadopted position and (b) runs PagePool.prepare_write
            # over [start, start + chunk_len) before launching the chunk,
            # forking any still-shared page — so every page written here is
            # exclusively owned (refcount 1) by the time the program runs.
            pid = jnp.where(valid_tok, page_table[0, qpos // page], trash)
            off = qpos % page
            ck = cache.k.at[layer, pid, off].set(k[0].astype(cache.k.dtype))
            cv = cache.v.at[layer, pid, off].set(v[0].astype(cache.v.dtype))
            specs = None
            if cfg.attn_backend == "pallas" and not _pallas_ok(sctx):
                # Chunks are single-slot (B == 1): only the head axis can
                # partition, so a data-partitioned pool falls back to XLA.
                specs = _paged_kernel_specs(
                    sctx, B=B, H=q.shape[2], KV=ck.shape[3],
                    total_pages=ck.shape[1], batch_sharded=False,
                )
            if cfg.attn_backend == "pallas" and _pallas_ok(sctx):
                from repro.kernels import ops as _kops

                out = _kops.paged_chunk_attention_op(
                    q, ck, cv, page_table, jnp.broadcast_to(start, (B,)), layer,
                    n_lp=max_pages,
                ).astype(dt)
            elif specs is not None:
                from repro.kernels import ops as _kops

                specs.pop("localize_pages")
                out = _kops.paged_chunk_attention_sharded(
                    q, ck, cv, page_table, jnp.broadcast_to(start, (B,)), layer,
                    n_lp=max_pages, **specs,
                ).astype(dt)
            else:
                sel = page_table  # (B, max_pages)
                T = max_pages * page
                kg = ck[layer, sel].reshape(B, T, *ck.shape[3:]).astype(dt)
                vg = cv[layer, sel].reshape(B, T, *cv.shape[3:]).astype(dt)
                k_pos = jnp.broadcast_to(
                    jnp.arange(T, dtype=jnp.int32)[None, :], (B, T)
                )
                out = _sdpa_span(q, kg, vg, k_pos, q_pos_b, cfg)
        ck = constrain(ck, _POOL_AXES, sctx)
        cv = constrain(cv, _POOL_AXES, sctx)
        return out, KVCache(ck, cv)

    # Contiguous per-slot row.
    T = cache.k.shape[1]
    if window:
        k_pos_old = _ring_positions(T, T, start - 1)[None, :]
        k_pos_c = jnp.where(valid_tok, qpos, -1)[None, :]
        kk = jnp.concatenate([cache.k.astype(dt), k.astype(dt)], axis=1)
        vv = jnp.concatenate([cache.v.astype(dt), v.astype(dt)], axis=1)
        k_pos = jnp.concatenate([k_pos_old, k_pos_c], axis=1)
        out = _sdpa_span(q, kk, vv, k_pos, q_pos_b, cfg, window=window)
        keep = valid_tok & (qpos >= start + chunk_len - T)
        wslot = jnp.where(keep, qpos % T, T)  # T is out of bounds -> dropped
        ck = cache.k.at[0, wslot].set(k[0].astype(cache.k.dtype), mode="drop")
        cv = cache.v.at[0, wslot].set(v[0].astype(cache.v.dtype), mode="drop")
        seq_axis = "window"
    else:
        wslot = jnp.where(valid_tok, qpos, T)  # out of bounds -> dropped
        ck = cache.k.at[0, wslot].set(k[0].astype(cache.k.dtype), mode="drop")
        cv = cache.v.at[0, wslot].set(v[0].astype(cache.v.dtype), mode="drop")
        k_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        out = _sdpa_span(q, ck.astype(dt), cv.astype(dt), k_pos, q_pos_b, cfg)
        seq_axis = "kv_seq"
    ck = constrain(ck, ("batch", seq_axis, "kv_heads", "head_dim"), sctx)
    cv = constrain(cv, ("batch", seq_axis, "kv_heads", "head_dim"), sctx)
    return out, KVCache(ck, cv)


def _ring_positions(T: int, window: int, cur: jax.Array) -> jax.Array:
    """Absolute position held by each of T ring slots after ``cur + 1``
    tokens: slot i holds the latest p <= cur with p % window == i; negative
    (never written) and out-of-ring slots report -1."""
    idx = jnp.arange(T, dtype=jnp.int32)
    pos = cur - ((cur - idx) % window)
    return jnp.where((idx < window) & (pos >= 0), pos, -1)


# ==========================================================================
# GQA attention block
# ==========================================================================
def _project_qkv(p, cfg, x, xkv=None):
    dt = cdt(cfg)
    xkv = x if xkv is None else xkv
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt), preferred_element_type=dt)
    k = jnp.einsum("bsd,dhe->bshe", xkv, p["wk"].astype(dt), preferred_element_type=dt)
    v = jnp.einsum("bsd,dhe->bshe", xkv, p["wv"].astype(dt), preferred_element_type=dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def gqa_attention(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    mode: str,  # train | prefill | chunk | decode
    positions: jax.Array,  # (S,) absolute positions of x's tokens
    mask_kind: str = "causal",
    window: int = 0,
    prefix_len: int = 0,
    cache: KVCache | None = None,
    cur_pos: jax.Array | None = None,  # scalar, decode/chunk only
    use_rope: bool = True,
    page_table: jax.Array | None = None,  # (B, max_pages) int32, paged decode only
    chunk_len: jax.Array | None = None,  # valid tokens in a chunk (chunk mode)
    layer: jax.Array | None = None,  # int32 scalar: the stacked pools' layer (paged)
    sctx: ShardingCtx,
) -> tuple[jax.Array, KVCache | None]:
    dt = cdt(cfg)
    q, k, v = _project_qkv(p, cfg, x)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None), sctx)

    new_cache: KVCache | None = None
    use_pallas = (
        cfg.attn_backend == "pallas"
        and _pallas_ok(sctx)
        and mode != "decode"
        and mask_kind in ("causal", "bidir")
        and not (cfg.prefix_lm and cfg.prefix_len)
        and x.shape[1] % min(128, x.shape[1]) == 0
    )
    if mode == "chunk":
        # Chunked prefill for ONE slot (B == 1): x holds C tokens at absolute
        # positions cur_pos .. cur_pos + C - 1, of which the first chunk_len
        # are real (the tail is bucket padding). The chunk's K/V stream into
        # the slot's cache — shared page pool (paged) or contiguous row —
        # and the queries attend to the already-cached prefix plus the
        # chunk itself, with purely positional validity. Windowed layers
        # read the pre-write ring and the chunk keys side by side so that
        # in-window positions evicted by later chunk colleagues stay
        # visible to earlier queries.
        assert cache is not None and cur_pos is not None and chunk_len is not None
        B, C = q.shape[0], q.shape[1]
        start = jnp.asarray(cur_pos, jnp.int32)  # tokens already cached
        idx_c = jnp.arange(C, dtype=jnp.int32)
        qpos = start + idx_c  # (C,)
        valid_tok = idx_c < chunk_len  # (C,)
        out, new_cache = _chunk_attend(
            q, k, v, cache, cfg, sctx,
            qpos=qpos, valid_tok=valid_tok, start=start, chunk_len=chunk_len,
            window=window, page_table=page_table, layer=layer,
        )
    elif mode == "decode" and page_table is not None:
        assert cache is not None and cur_pos is not None
        # Paged decode: the cache is the stack's shared page pools
        # (L, P+1, page, kv, hd), and this layer's slot's logical token s
        # lives at [layer, page_table[b, s // page], s % page]. Retired slots' table
        # rows all point at the trash page (index P), so their frozen-pos
        # garbage writes can never corrupt a live tenant's pages. With
        # prefix sharing, pages can additionally be mapped by several
        # live slots (refcounted); this one-token write is still safe:
        # decode positions sit past the prompt, adopted/indexed pages
        # cover only full *prompt* pages, and the scheduler runs
        # PagePool.prepare_write (copy-on-write fork) on the write
        # position before every decode step — a written page is always
        # refcount-1 private by the time this program runs.
        B = q.shape[0]
        page = cache.k.shape[2]
        max_pages = page_table.shape[1]
        pos_v = jnp.broadcast_to(jnp.atleast_1d(cur_pos), (B,)).astype(jnp.int32)
        wslot = pos_v % window if window else pos_v  # logical write slot
        rows = jnp.arange(B)
        pid = page_table[rows, wslot // page]  # (B,) physical page per slot
        off = wslot % page
        ck = cache.k.at[layer, pid, off].set(k[:, 0].astype(cache.k.dtype))
        cv = cache.v.at[layer, pid, off].set(v[:, 0].astype(cache.v.dtype))
        ck = constrain(ck, _POOL_AXES, sctx)
        cv = constrain(cv, _POOL_AXES, sctx)
        new_cache = KVCache(ck, cv)
        # Windowed layers ring-fold into the leading ceil(window/page)
        # table entries — a bounded page working set regardless of how
        # wide the table is for dense layers.
        n_lp = min(-(-window // page), max_pages) if window else max_pages
        specs = None
        if cfg.attn_backend == "pallas" and not _pallas_ok(sctx):
            specs = _paged_kernel_specs(
                sctx, B=B, H=q.shape[2], KV=ck.shape[3],
                total_pages=ck.shape[1], batch_sharded=True,
            )
        if cfg.attn_backend == "pallas" and _pallas_ok(sctx):
            from repro.kernels import ops as _kops

            out = _kops.paged_decode_attention_op(
                q, ck, cv, page_table, pos_v, layer, n_lp=n_lp, window=window
            ).astype(dt)
        elif specs is not None:
            from repro.kernels import ops as _kops

            out = _kops.paged_decode_attention_sharded(
                q, ck, cv, page_table, pos_v, layer, n_lp=n_lp, window=window,
                **specs,
            ).astype(dt)
        else:
            sel = page_table[:, :n_lp]  # (B, n_lp)
            T = n_lp * page
            kg = ck[layer, sel].reshape(B, T, *ck.shape[3:]).astype(dt)
            vg = cv[layer, sel].reshape(B, T, *cv.shape[3:]).astype(dt)
            idx = jnp.arange(T, dtype=jnp.int32)
            if window:
                k_pos = pos_v[:, None] - ((pos_v[:, None] - idx[None, :]) % window)
                k_pos = jnp.where(idx[None, :] < window, k_pos, -1)
            else:
                k_pos = jnp.broadcast_to(idx[None, :], (B, T))
            out = _sdpa_decode(q, kg, vg, k_pos, pos_v, cfg, window=window)
    elif mode == "decode":
        assert cache is not None and cur_pos is not None
        B, T = cache.k.shape[0], cache.k.shape[1]
        # cur_pos is a scalar (classic static batch: every row at the same
        # position) or (B,) (continuous batching: each slot at its own
        # position). Both run the same per-row scatter program.
        pos_v = jnp.broadcast_to(jnp.atleast_1d(cur_pos), (B,)).astype(jnp.int32)
        slot = pos_v % T if window else pos_v
        rows = jnp.arange(B)
        ck = cache.k.at[rows, slot].set(k[:, 0].astype(cache.k.dtype))
        cv = cache.v.at[rows, slot].set(v[:, 0].astype(cache.v.dtype))
        ck = constrain(ck, ("batch", "window" if window else "kv_seq", "kv_heads", "head_dim"), sctx)
        cv = constrain(cv, ("batch", "window" if window else "kv_seq", "kv_heads", "head_dim"), sctx)
        new_cache = KVCache(ck, cv)
        # Positions held by each row's cache slots, derived analytically:
        #   full cache: slot i holds position i;
        #   ring buffer: slot i holds the latest p <= cur_pos with p % T == i
        #   (negative -> never written; masked in _sdpa_decode).
        idx = jnp.arange(T, dtype=jnp.int32)
        if window:
            k_pos = pos_v[:, None] - ((pos_v[:, None] - idx[None, :]) % T)
        else:
            k_pos = jnp.broadcast_to(idx[None, :], (B, T))
        out = _sdpa_decode(q, ck.astype(dt), cv.astype(dt), k_pos, pos_v, cfg, window=window)
    else:
        if mode == "prefill":
            new_cache = KVCache(k.astype(jnp.bfloat16), v.astype(jnp.bfloat16))
        kind = "window" if window else mask_kind
        if use_pallas:
            # TPU hot path: the Pallas flash kernel (fwd + bwd custom_vjp).
            from repro.kernels import ops as _kops

            blk = min(128, q.shape[1])
            out = _kops.flash_attention(
                q, k, v, causal=(kind != "bidir"), window=window,
                blk_q=blk, blk_k=blk,
            )
        else:
            out = _sdpa_chunked(
                q, k, v, positions, positions, kind, cfg, sctx,
                window=window, prefix_len=prefix_len,
            )
    # Row-parallel o-projection: bf16 output => bf16 TP all-reduce.
    with jax.named_scope("attn.out_proj"):
        y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dt), preferred_element_type=dt)
        y = constrain(y.astype(dt), ("batch", "seq", "embed_act"), sctx)
    return y, new_cache


def cross_attention(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d) decoder states
    enc_kv: KVCache,  # precomputed from encoder output
    sctx: ShardingCtx,
) -> jax.Array:
    """Decoder->encoder attention (bidirectional over encoder frames)."""
    dt = cdt(cfg)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dt), preferred_element_type=F32).astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    B, S, H, D = q.shape
    k, v = enc_kv.k.astype(dt), enc_kv.v.astype(dt)
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, S, KV, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", qh, k, preferred_element_type=F32) * (D ** -0.5)
    pmat = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btke->bskge", pmat.astype(dt), v, preferred_element_type=F32)
    o = o.reshape(B, S, H, D).astype(dt)
    y = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(dt), preferred_element_type=F32)
    return constrain(y.astype(dt), ("batch", "seq", "embed_act"), sctx)


def encoder_kv(p: dict[str, Any], cfg: ModelConfig, enc_out: jax.Array) -> KVCache:
    dt = cdt(cfg)
    k = jnp.einsum("btd,dhe->bthe", enc_out, p["wk"].astype(dt), preferred_element_type=F32).astype(jnp.bfloat16)
    v = jnp.einsum("btd,dhe->bthe", enc_out, p["wv"].astype(dt), preferred_element_type=F32).astype(jnp.bfloat16)
    if cfg.qkv_bias:
        k = (k.astype(dt) + p["bk"].astype(dt)).astype(jnp.bfloat16)
        v = (v.astype(dt) + p["bv"].astype(dt)).astype(jnp.bfloat16)
    return KVCache(k, v)


# ==========================================================================
# MLA (DeepSeek-V2)
# ==========================================================================
def mla_attention(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,
    *,
    mode: str,
    positions: jax.Array,
    cache: MLACache | None = None,
    cur_pos: jax.Array | None = None,
    chunk_len: jax.Array | None = None,  # valid tokens in a chunk (chunk mode)
    sctx: ShardingCtx,
) -> tuple[jax.Array, MLACache | None]:
    m = cfg.mla
    dt = cdt(cfg)
    B, S, _ = x.shape
    H = cfg.n_heads
    scale = (m.nope_dim + m.rope_dim) ** -0.5

    # Query path: low-rank down -> norm -> up, split nope/rope.
    q_c = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(dt), preferred_element_type=F32).astype(dt)
    q_c = rmsnorm(p["q_norm"], q_c, cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", q_c, p["wq_b"].astype(dt), preferred_element_type=F32).astype(dt)
    q_nope, q_rope = q[..., : m.nope_dim], q[..., m.nope_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # KV path: compressed latent + shared rope key.
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(dt), preferred_element_type=F32).astype(dt)
    ckv, k_rope = kv[..., : m.kv_lora], kv[..., m.kv_lora :]
    ckv = rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)  # (B, S, rope)

    new_cache: MLACache | None = None
    if mode in ("decode", "chunk"):
        assert cache is not None and cur_pos is not None
        T = cache.ckv.shape[1]
        if mode == "chunk":
            # One slot's prompt chunk (B == 1): scatter the S compressed
            # latents at positions cur_pos .. cur_pos + chunk_len - 1 (the
            # padded tail is dropped), then run the absorbed path with
            # per-query positional validity over the whole row.
            assert chunk_len is not None
            start = jnp.asarray(cur_pos, jnp.int32)
            qpos = start + jnp.arange(S, dtype=jnp.int32)
            wslot = jnp.where(jnp.arange(S) < chunk_len, qpos, T)
            ckv_all = cache.ckv.at[0, wslot].set(
                ckv[0].astype(cache.ckv.dtype), mode="drop"
            )
            krope_all = cache.krope.at[0, wslot].set(
                k_rope[0].astype(cache.krope.dtype), mode="drop"
            )
            q_pos = jnp.broadcast_to(qpos[None, :], (B, S))
        else:
            pos_v = jnp.broadcast_to(jnp.atleast_1d(cur_pos), (B,)).astype(jnp.int32)
            rows = jnp.arange(B)
            ckv_all = cache.ckv.at[rows, pos_v].set(ckv[:, 0].astype(cache.ckv.dtype))
            krope_all = cache.krope.at[rows, pos_v].set(k_rope[:, 0].astype(cache.krope.dtype))
            q_pos = pos_v[:, None]
        ckv_all = constrain(ckv_all, ("batch", "kv_seq", "kv_lora"), sctx)
        new_cache = MLACache(ckv_all, krope_all)
        # Absorbed decode: score against the compressed cache directly.
        q_abs = jnp.einsum("bshe,rhe->bshr", q_nope, p["wk_b"].astype(dt), preferred_element_type=F32).astype(dt)
        s = jnp.einsum("bshr,btr->bhst", q_abs, ckv_all.astype(dt), preferred_element_type=F32)
        s = s + jnp.einsum("bshe,bte->bhst", q_rope, krope_all.astype(dt), preferred_element_type=F32)
        valid = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]  # (B, S, T)
        s = jnp.where(valid[:, None], s * scale, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        ctx_c = jnp.einsum("bhst,btr->bshr", pr.astype(dt), ckv_all.astype(dt), preferred_element_type=F32).astype(dt)
        o = jnp.einsum("bshr,rhe->bshe", ctx_c, p["wv_b"].astype(dt), preferred_element_type=F32).astype(dt)
    else:
        k_nope = jnp.einsum("bsr,rhe->bshe", ckv, p["wk_b"].astype(dt), preferred_element_type=F32).astype(dt)
        v = jnp.einsum("bsr,rhe->bshe", ckv, p["wv_b"].astype(dt), preferred_element_type=F32).astype(dt)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.rope_dim))], axis=-1
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = _sdpa_chunked(
            q_full, k_full, v, positions, positions, "causal", cfg, sctx, scale=scale
        )
        o = out
        if mode == "prefill":
            new_cache = MLACache(ckv.astype(jnp.bfloat16), k_rope.astype(jnp.bfloat16))
    y = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(dt), preferred_element_type=F32)
    return constrain(y.astype(dt), ("batch", "seq", "embed_act"), sctx), new_cache
