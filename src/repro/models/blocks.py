"""Block assembly: pre-norm residual blocks of every kind, plus the scanned
pattern-group machinery that turns 26..88-layer stacks into a single
``lax.scan`` over stacked weights (fast compiles, one remat lever).

A config's layer stack = ``first_blocks`` (unscanned, e.g. DeepSeek-V2's
dense layer 0) followed by ``n_pattern_groups`` repetitions of
``block_pattern`` (scanned). Each pattern element owns its params stacked on
a leading "layer" axis.
"""
from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models.attention import KVCache, MLACache
from repro.models.layers import cdt, mlp, mlp_schema, rmsnorm, rmsnorm_schema
from repro.models.recurrent import MLSTMState, RGLRUState, SLSTMState
from repro.models.schema import LeafLayout, ParamSpec, layout_for_spec, stack_specs
from repro.sharding.rules import ShardingCtx

F32 = jnp.float32


# ==========================================================================
# Per-kind schemas
# ==========================================================================
def block_schema(cfg: ModelConfig, kind: str) -> dict[str, Any]:
    d = cfg.d_model
    if kind in ("attn_mlp", "local_attn"):
        return {
            "ln1": rmsnorm_schema(d),
            "attn": attn_mod.attention_schema(cfg),
            "ln2": rmsnorm_schema(d),
            "mlp": mlp_schema(cfg),
        }
    if kind == "attn_moe":
        return {
            "ln1": rmsnorm_schema(d),
            "attn": attn_mod.attention_schema(cfg),
            "ln2": rmsnorm_schema(d),
            "moe": moe_mod.moe_schema(cfg),
        }
    if kind == "rglru":
        return {
            "ln1": rmsnorm_schema(d),
            "rec": rec_mod.rglru_schema(cfg),
            "ln2": rmsnorm_schema(d),
            "mlp": mlp_schema(cfg),
        }
    if kind == "mlstm":
        return {"ln": rmsnorm_schema(d), "core": rec_mod.mlstm_schema(cfg)}
    if kind == "slstm":
        return {"ln": rmsnorm_schema(d), "core": rec_mod.slstm_schema(cfg)}
    if kind == "cross_attn_mlp":
        return {
            "ln1": rmsnorm_schema(d),
            "attn": attn_mod.gqa_schema(cfg),
            "ln_x": rmsnorm_schema(d),
            "xattn": attn_mod.gqa_schema(cfg, cross=True),
            "ln2": rmsnorm_schema(d),
            "mlp": mlp_schema(cfg),
        }
    raise ValueError(f"unknown block kind {kind}")


def paged_kv_kinds(cfg: ModelConfig) -> set[str]:
    """Block kinds whose decode KV caches live in the serving page pool.

    Dense GQA and windowed attention page; MLA compressed caches,
    recurrent states, and enc-dec cross blocks keep their per-slot
    layout behind the same cache interface.
    """
    kinds = {"local_attn"}
    if cfg.attn_kind != "mla":
        kinds |= {"attn_mlp", "attn_moe"}
    return kinds & (set(cfg.block_pattern) | set(cfg.first_blocks))


def _paged_kv_pool_schema(cfg: ModelConfig, pages) -> dict[str, ParamSpec]:
    """Pool-shaped KV leaves: (n_pages + 1, page_size, n_kv, head_dim).

    The +data_shards pages are per-shard trash pages all unused
    page-table entries point at (see serve/pages.py). The page axis
    carries the "pages" logical name: decode profiles shard it over
    data when the pool is data-partitioned (total_pages divisible —
    each data shard then owns a contiguous sub-pool ending in its own
    trash page), falling back to replication otherwise. Heads keep
    their TP sharding.
    """
    hd = cfg.resolved_head_dim
    shape = (pages.total_pages, pages.page_size, cfg.n_kv_heads, hd)
    axes = ("pages", None, "kv_heads", "head_dim")
    return {
        "k": ParamSpec(shape, axes, dtype=jnp.bfloat16, init="zeros"),
        "v": ParamSpec(shape, axes, dtype=jnp.bfloat16, init="zeros"),
    }


def block_state_schema(
    cfg: ModelConfig, kind: str, batch: int, s_max: int, pages=None
) -> dict[str, Any] | None:
    """Decode-state schema for one block (None when stateless).

    With ``pages`` (a serve.pages.PageLayout), paged kinds store their KV
    as a shared page pool instead of per-slot rows; everything else is
    unchanged.
    """
    if pages is not None and kind in paged_kv_kinds(cfg):
        return _paged_kv_pool_schema(cfg, pages)
    if kind in ("attn_mlp", "attn_moe"):
        if cfg.attn_kind == "mla":
            return attn_mod.init_mla_cache(cfg, batch, s_max)
        return attn_mod.init_kv_cache(cfg, batch, s_max, windowed=False)
    if kind == "local_attn":
        return attn_mod.init_kv_cache(cfg, batch, s_max, windowed=True)
    if kind == "rglru":
        return rec_mod.init_rglru_state(cfg, batch)
    if kind == "mlstm":
        return rec_mod.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return rec_mod.init_slstm_state(cfg, batch)
    if kind == "cross_attn_mlp":
        self_c = attn_mod.init_kv_cache(cfg, batch, s_max, windowed=False)
        hd = cfg.resolved_head_dim
        cross_c = {
            "k": ParamSpec((batch, cfg.enc_seq, cfg.n_kv_heads, hd), ("batch", "frames", "kv_heads", "head_dim"), dtype=jnp.bfloat16, init="zeros"),
            "v": ParamSpec((batch, cfg.enc_seq, cfg.n_kv_heads, hd), ("batch", "frames", "kv_heads", "head_dim"), dtype=jnp.bfloat16, init="zeros"),
        }
        return {"self": self_c, "cross": cross_c}
    raise ValueError(f"unknown block kind {kind}")


def _state_to_struct(kind: str, cfg: ModelConfig, raw: dict[str, Any] | None):
    """Wrap a raw state dict into the typed containers the block fns expect."""
    if raw is None:
        return None
    if kind in ("attn_mlp", "attn_moe"):
        if cfg.attn_kind == "mla":
            return MLACache(ckv=raw["ckv"], krope=raw["krope"])
        return KVCache(k=raw["k"], v=raw["v"])
    if kind == "local_attn":
        return KVCache(k=raw["k"], v=raw["v"])
    if kind == "rglru":
        return RGLRUState(h=raw["h"], conv=raw["conv"])
    if kind == "mlstm":
        return MLSTMState(C=raw["C"], n=raw["n"], m=raw["m"], conv=raw["conv"])
    if kind == "slstm":
        return SLSTMState(c=raw["c"], n=raw["n"], h=raw["h"], m=raw["m"])
    if kind == "cross_attn_mlp":
        return {
            "self": KVCache(k=raw["self"]["k"], v=raw["self"]["v"]),
            "cross": KVCache(k=raw["cross"]["k"], v=raw["cross"]["v"]),
        }
    raise ValueError(kind)


def _state_to_raw(kind: str, cfg: ModelConfig, st) -> dict[str, Any] | None:
    if st is None:
        return None
    if isinstance(st, KVCache):
        return {"k": st.k, "v": st.v}
    if isinstance(st, MLACache):
        return {"ckv": st.ckv, "krope": st.krope}
    if isinstance(st, RGLRUState):
        return {"h": st.h, "conv": st.conv}
    if isinstance(st, MLSTMState):
        return {"C": st.C, "n": st.n, "m": st.m, "conv": st.conv}
    if isinstance(st, SLSTMState):
        return {"c": st.c, "n": st.n, "h": st.h, "m": st.m}
    if isinstance(st, dict) and "self" in st:
        return {
            "self": {"k": st["self"].k, "v": st["self"].v},
            "cross": {"k": st["cross"].k, "v": st["cross"].v},
        }
    raise ValueError(f"unexpected state {type(st)}")


# ==========================================================================
# Block application
# ==========================================================================
class BlockIO(NamedTuple):
    x: jax.Array
    aux: jax.Array  # accumulated aux loss (MoE load balance)


def apply_block(
    p: dict[str, Any],
    cfg: ModelConfig,
    kind: str,
    io: BlockIO,
    *,
    mode: str,
    positions: jax.Array,
    cur_pos: jax.Array | None,
    state_raw: dict[str, Any] | None,
    mask_kind: str,
    sctx: ShardingCtx,
    enc_out: jax.Array | None = None,
    page_table: jax.Array | None = None,
    chunk_len: jax.Array | None = None,
    layer: jax.Array | None = None,
) -> tuple[BlockIO, dict[str, Any] | None]:
    """One block. A paged block's ``state_raw`` holds the whole stack's
    page pools, read and written at ``layer``."""
    x, aux = io
    st = _state_to_struct(kind, cfg, state_raw)
    if page_table is not None and kind not in paged_kv_kinds(cfg):
        page_table = None
    eps = cfg.norm_eps
    new_st = None

    if kind in ("attn_mlp", "attn_moe", "local_attn"):
        window = cfg.window_size if kind == "local_attn" else 0
        h = rmsnorm(p["ln1"], x, eps)
        if cfg.attn_kind == "mla" and kind != "local_attn":
            a, new_st = attn_mod.mla_attention(
                p["attn"], cfg, h, mode=mode, positions=positions,
                cache=st, cur_pos=cur_pos, chunk_len=chunk_len, sctx=sctx,
            )
        else:
            a, new_st = attn_mod.gqa_attention(
                p["attn"], cfg, h, mode=mode, positions=positions,
                mask_kind=mask_kind, window=window,
                prefix_len=cfg.prefix_len if cfg.prefix_lm else 0,
                cache=st, cur_pos=cur_pos, page_table=page_table,
                chunk_len=chunk_len, layer=layer, sctx=sctx,
            )
        x = x + a
        h = rmsnorm(p["ln2"], x, eps)
        if kind == "attn_moe":
            f, moe_aux = moe_mod.moe_ffn(p["moe"], cfg, h, sctx)
            aux = aux + moe_aux
        else:
            f = mlp(p["mlp"], cfg, h, sctx)
        x = x + f

    elif kind == "rglru":
        h = rmsnorm(p["ln1"], x, eps)
        r, new_st = rec_mod.rglru_block(
            p["rec"], cfg, h, mode=mode, state=st, chunk_len=chunk_len, sctx=sctx
        )
        x = x + r
        h = rmsnorm(p["ln2"], x, eps)
        x = x + mlp(p["mlp"], cfg, h, sctx)

    elif kind == "mlstm":
        h = rmsnorm(p["ln"], x, eps)
        r, new_st = rec_mod.mlstm_block(
            p["core"], cfg, h, mode=mode, state=st, chunk_len=chunk_len, sctx=sctx
        )
        x = x + r

    elif kind == "slstm":
        h = rmsnorm(p["ln"], x, eps)
        r, new_st = rec_mod.slstm_block(
            p["core"], cfg, h, mode=mode, state=st, chunk_len=chunk_len, sctx=sctx
        )
        x = x + r

    elif kind == "cross_attn_mlp":
        if mode == "chunk":
            raise NotImplementedError(
                "chunked prefill does not support enc-dec blocks; the "
                "scheduler streams such requests through whole-prompt prefill"
            )
        h = rmsnorm(p["ln1"], x, eps)
        a, new_self = attn_mod.gqa_attention(
            p["attn"], cfg, h, mode=mode, positions=positions, mask_kind="causal",
            cache=st["self"] if st else None,
            cur_pos=cur_pos, sctx=sctx,
        )
        x = x + a
        h = rmsnorm(p["ln_x"], x, eps)
        if mode == "decode":
            assert st is not None and "cross" in st, "decode needs a prefilled encoder cache"
            cross_kv = st["cross"]
        else:
            assert enc_out is not None, "enc-dec train/prefill needs encoder output"
            cross_kv = attn_mod.encoder_kv(p["xattn"], cfg, enc_out)
        x = x + attn_mod.cross_attention(p["xattn"], cfg, h, cross_kv, sctx)
        h = rmsnorm(p["ln2"], x, eps)
        x = x + mlp(p["mlp"], cfg, h, sctx)
        if mode in ("prefill", "decode"):
            new_st = {
                "self": new_self if new_self is not None else (st["self"] if st else None),
                "cross": cross_kv,
            }
        else:
            new_st = None

    else:
        raise ValueError(kind)

    return BlockIO(x=x, aux=aux), _state_to_raw(kind, cfg, new_st)


# ==========================================================================
# Stacks: first blocks (unscanned) + pattern groups (scanned)
# ==========================================================================
def stack_schema(cfg: ModelConfig) -> dict[str, Any]:
    sch: dict[str, Any] = {}
    if cfg.first_blocks:
        sch["first"] = {
            f"b{i}": block_schema(cfg, k) for i, k in enumerate(cfg.first_blocks)
        }
    n_groups = cfg.n_pattern_groups
    sch["groups"] = {
        f"g{i}": stack_specs(block_schema(cfg, k), n_groups)
        for i, k in enumerate(cfg.block_pattern)
    }
    return sch


def stack_state_schema(
    cfg: ModelConfig, batch: int, s_max: int, pages=None
) -> dict[str, Any]:
    sch: dict[str, Any] = {}
    if cfg.first_blocks:
        sch["first"] = {
            f"b{i}": block_state_schema(cfg, k, batch, s_max, pages=pages)
            for i, k in enumerate(cfg.first_blocks)
        }
    n_groups = cfg.n_pattern_groups
    sch["groups"] = {
        f"g{i}": stack_specs(block_state_schema(cfg, k, batch, s_max, pages=pages), n_groups)
        for i, k in enumerate(cfg.block_pattern)
    }
    return sch


# Per-kind overrides turning a zeroed state into the *empty-recurrence*
# state: the log-space stabilisers must start at their identity values or a
# chunked prefill resuming from a freshly reset slot diverges from a
# from-scratch prefill (which initialises these internally).
_FRESH_STATE_OVERRIDES: dict[str, dict[str, float]] = {
    "mlstm": {"m": -1e30},
    "slstm": {"n": 1e-6, "m": -1e30},
}


def fresh_stack_states(cfg: ModelConfig, states: dict[str, Any]) -> dict[str, Any]:
    """Rewrite a zero-initialised stack state pytree into the state a
    chunked prefill starts from at position 0 (see overrides above).
    Works on both per-slot (batch-1) and stacked-group layouts."""

    def patch(kind: str, st):
        ov = _FRESH_STATE_OVERRIDES.get(kind)
        if st is None or ov is None:
            return st
        return {
            k: (jnp.full_like(v, ov[k]) if k in ov else v) for k, v in st.items()
        }

    out: dict[str, Any] = {}
    if "first" in states:
        out["first"] = {
            f"b{i}": patch(kind, states["first"][f"b{i}"])
            for i, kind in enumerate(cfg.first_blocks)
        }
    out["groups"] = {
        f"g{i}": patch(kind, states["groups"][f"g{i}"])
        for i, kind in enumerate(cfg.block_pattern)
    }
    return out


def _block_layouts(
    cfg: ModelConfig, kind: str, s_max: int, paged: bool, stacked: bool
) -> dict[str, Any] | None:
    """Per-leaf :class:`LeafLayout` metadata for one block's decode state.

    Pool leaves are tagged ``paged`` with their logical token capacity;
    everything else derives its layout from the ParamSpec axis *names*
    (``window`` -> ring, ``kv_seq``/``frames`` -> dense, neither -> copy),
    so leaves with coinciding shapes can never be confused. ``stacked``
    group leaves carry their leading "layer" axis in the stacked spec,
    which shifts the derived axis indices automatically.
    """
    if paged and kind in paged_kv_kinds(cfg):
        cap = cfg.window_size if kind == "local_attn" else s_max
        lay = LeafLayout("paged", cap=cap)
        return {"k": lay, "v": lay}
    raw = block_state_schema(cfg, kind, 1, s_max)
    if stacked:
        raw = stack_specs(raw, 1)  # layer axis name only; count is irrelevant
    return jax.tree.map(layout_for_spec, raw, is_leaf=lambda x: isinstance(x, ParamSpec))


def stack_layouts(cfg: ModelConfig, s_max: int, paged: bool = True) -> dict[str, Any]:
    """A pytree congruent with ``stack_state_schema`` whose leaves are
    :class:`LeafLayout` records. Stacking adds a leading layer axis but not
    tree structure, so the per-block layouts line up with stacked group
    states (their axis indices account for the layer axis)."""
    sch: dict[str, Any] = {}
    if cfg.first_blocks:
        sch["first"] = {
            f"b{i}": _block_layouts(cfg, k, s_max, paged, stacked=False)
            for i, k in enumerate(cfg.first_blocks)
        }
    sch["groups"] = {
        f"g{i}": _block_layouts(cfg, k, s_max, paged, stacked=True)
        for i, k in enumerate(cfg.block_pattern)
    }
    return sch


def stack_paged_caps(cfg: ModelConfig, s_max: int) -> dict[str, Any]:
    """Int view of :func:`stack_layouts`: each leaf's logical capacity when
    paged (0 = per-slot contiguous)."""
    return jax.tree.map(
        lambda lay: lay.cap if lay.kind == "paged" else 0,
        stack_layouts(cfg, s_max, paged=True),
        is_leaf=lambda x: isinstance(x, LeafLayout),
    )


def apply_stack(
    params: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,
    *,
    mode: str,
    positions: jax.Array,
    cur_pos: jax.Array | None = None,
    states: dict[str, Any] | None = None,
    mask_kind: str = "causal",
    sctx: ShardingCtx,
    enc_out: jax.Array | None = None,
    page_table: jax.Array | None = None,
    chunk_len: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, dict[str, Any] | None]:
    """Run the whole layer stack. Returns (x, aux_loss, new_states).

    With a ``page_table``, the paged blocks' KV leaves are page pools shared
    by every slot, stacked over the layers. They ride in the scan's carry
    and each layer updates its rows in place at its index; slicing a
    layer's pool out as the scan's input and stacking it back as its output
    would copy the whole pool every step. Every other state leaf is scanned
    per layer."""
    io = BlockIO(x=x, aux=jnp.zeros((), F32))
    new_states: dict[str, Any] = {"first": {}, "groups": {}}
    want_states = mode in ("prefill", "decode", "chunk")
    pooled = (
        paged_kv_kinds(cfg) if page_table is not None and states is not None
        else set()
    )

    # -- unscanned prefix blocks ------------------------------------------
    for i, kind in enumerate(cfg.first_blocks):
        key = f"b{i}"
        st = states["first"][key] if states is not None else None
        layer = None
        if kind in pooled:  # one layer's pools: a stack of one
            st, layer = jax.tree.map(lambda a: a[None], st), jnp.int32(0)
        io, new_st = apply_block(
            params["first"][key], cfg, kind, io, mode=mode, positions=positions,
            cur_pos=cur_pos, state_raw=st,
            mask_kind=mask_kind, sctx=sctx, enc_out=enc_out, page_table=page_table,
            chunk_len=chunk_len, layer=layer,
        )
        if kind in pooled:
            new_st = jax.tree.map(lambda a: a[0], new_st)
        if want_states:
            new_states["first"][key] = new_st

    # -- scanned pattern groups -------------------------------------------
    keys = [f"g{i}" for i in range(len(cfg.block_pattern))]
    g_states_in = states["groups"] if states is not None else None
    pool_keys = {k for k, kind in zip(keys, cfg.block_pattern) if kind in pooled}
    pools = {k: g_states_in[k] for k in pool_keys}
    scanned = (
        {k: v for k, v in g_states_in.items() if k not in pool_keys}
        if g_states_in is not None else None
    )

    def group_body(carry, per_layer):
        io, pools = carry
        g_params, g_states, layer = per_layer
        pools = dict(pools)
        new_group_states = {}
        for i, kind in enumerate(cfg.block_pattern):
            key = f"g{i}"
            in_pool = key in pool_keys
            st = pools[key] if in_pool else (
                g_states[key] if g_states is not None else None
            )
            io, new_st = apply_block(
                g_params[key], cfg, kind, io, mode=mode, positions=positions,
                cur_pos=cur_pos, state_raw=st,
                mask_kind=mask_kind, sctx=sctx, enc_out=enc_out,
                page_table=page_table, chunk_len=chunk_len,
                layer=layer if in_pool else None,
            )
            if in_pool:
                pools[key] = new_st
            else:
                new_group_states[key] = new_st
        return (io, pools), (new_group_states if want_states else None)

    body = group_body
    if cfg.remat == "full" and mode == "train":
        body = jax.checkpoint(group_body, prevent_cse=False)

    # REPRO_UNROLL_SCANS=1: fully unroll so XLA cost_analysis (which counts
    # while bodies once) sees every layer — used to validate the analytic
    # cost model on small cells (EXPERIMENTS.md SS Dry-run validation).
    unroll = bool(int(os.environ.get("REPRO_UNROLL_SCANS", "0")))
    n_groups = cfg.n_pattern_groups
    (io, pools), scanned_states = jax.lax.scan(
        body, (io, pools),
        (params["groups"], scanned, jnp.arange(n_groups, dtype=jnp.int32)),
        unroll=True if unroll else 1,
    )
    if want_states:
        new_states["groups"] = {
            k: pools[k] if k in pool_keys else scanned_states[k] for k in keys
        }
    if not cfg.first_blocks:
        new_states.pop("first", None)
    return io.x, io.aux, (new_states if want_states else None)
