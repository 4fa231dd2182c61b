"""Mixture-of-experts FFN with TPU-native sort-based dispatch.

GPU MoE stacks (Megablocks) build CSR block-sparse GEMMs; the TPU-native
adaptation here is:

  * tokens stay sharded over the batch axes (pod, data); expert weights are
    sharded over the ``model`` axis (expert parallelism);
  * inside a ``shard_map`` each model-rank sorts its *local* tokens by
    expert id (local sort — no cross-shard sort), keeps pairs routed to its
    local experts up to a static capacity, and runs a grouped matmul
    (``jax.lax.ragged_dot`` — the Pallas ``moe_gmm`` kernel is the TPU hot
    path) over its expert shard;
  * contributions are combined with a single fused ``psum`` over ``model``
    (shared-expert partial sums ride the same reduction). Replacing this
    psum with an all-to-all dispatch/combine is a recorded hillclimb lever.

Capacity semantics: per-rank capacity = ceil(cf * T_local * top_k /
ep_shards), so the expected load fits with slack cf; overflow pairs are
dropped (GShard semantics) and the aux load-balance loss keeps the router
honest. With a single shard (smoke tests) capacity covers every pair, so
nothing is dropped and the layer is exact.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import F32, _act, cdt
from repro.models.schema import ParamSpec
from repro.sharding.rules import ShardingCtx, constrain


# --------------------------------------------------------------------------
def moe_schema(cfg: ModelConfig) -> dict[str, Any]:
    mo = cfg.moe
    d = cfg.d_model
    ffe = mo.d_ff_expert
    sch: dict[str, Any] = {
        "router": ParamSpec((d, mo.n_experts), ("embed", "expert"), dtype=jnp.float32, scale=0.02),
        "w_gate": ParamSpec((mo.n_experts, d, ffe), ("expert", "embed", "expert_mlp")),
        "w_up": ParamSpec((mo.n_experts, d, ffe), ("expert", "embed", "expert_mlp")),
        "w_down": ParamSpec((mo.n_experts, ffe, d), ("expert", "expert_mlp", "embed")),
    }
    if mo.n_shared:
        ffs = mo.n_shared * ffe
        sch["shared"] = {
            "gate": ParamSpec((d, ffs), ("embed", "mlp")),
            "up": ParamSpec((d, ffs), ("embed", "mlp")),
            "down": ParamSpec((ffs, d), ("mlp", "embed")),
        }
    return sch


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _local_moe(
    x: jax.Array,  # (T, d) local tokens
    p: dict[str, Any],
    cfg: ModelConfig,
    e0: int,  # first expert id owned by this rank
    n_local: int,  # experts owned by this rank
    cap: int,  # static pair capacity for this rank
) -> tuple[jax.Array, jax.Array]:
    """Sort-based dispatch + grouped matmul for one expert shard.

    Returns (partial_out (T, d), aux_stats (2E,) = [count_frac | mean_prob]).
    """
    mo = cfg.moe
    dt = cdt(cfg)
    T, d = x.shape
    E, k = mo.n_experts, mo.top_k

    logits = jnp.einsum("td,de->te", x.astype(F32), p["router"].astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)

    pair_e = top_e.reshape(-1)  # (T*k,)
    pair_p = top_p.reshape(-1)
    local = (pair_e >= e0) & (pair_e < e0 + n_local)
    sort_key = jnp.where(local, pair_e, E)  # non-local pairs pushed last
    order = jnp.argsort(sort_key)  # stable
    sel = order[:cap]  # (cap,)
    sel_e = pair_e[sel]
    sel_valid = local[sel]
    sel_p = jnp.where(sel_valid, pair_p[sel], 0.0)
    tok = sel // k  # (cap,) originating token row

    # Group sizes in sorted order; invalid tail goes to a zero dummy expert.
    local_id = jnp.where(sel_valid, sel_e - e0, n_local)
    onehot = jax.nn.one_hot(local_id, n_local + 1, dtype=jnp.int32)
    group_sizes = jnp.sum(onehot, axis=0).astype(jnp.int32)  # (n_local+1,)

    xs = jnp.take(x, tok, axis=0).astype(dt)  # (cap, d)
    pad = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])], axis=0).astype(dt)
    g = jax.lax.ragged_dot(xs, pad(p["w_gate"]), group_sizes, preferred_element_type=F32)
    u = jax.lax.ragged_dot(xs, pad(p["w_up"]), group_sizes, preferred_element_type=F32)
    h = (_act(cfg.act, g) * u).astype(dt)
    y = jax.lax.ragged_dot(h, pad(p["w_down"]), group_sizes, preferred_element_type=F32)
    y = y * sel_p[:, None]  # combine weights (zero for invalid/dropped)

    out = jnp.zeros((T, d), F32).at[tok].add(y)

    # Aux stats for the global load-balance loss: dispatch fractions must be
    # computed over *all* pairs (not just locally-kept ones) so every rank
    # reports identical stats and the psum average is exact.
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1)) / (T * k)
    mean_prob = jnp.mean(probs, axis=0)
    return out, jnp.concatenate([counts, mean_prob])


def _shared_ffn_partial(x: jax.Array, sh: dict[str, Any], cfg: ModelConfig) -> jax.Array:
    """Shared-experts FFN with the mlp dim sharded: produces a partial sum."""
    dt = cdt(cfg)
    g = jnp.einsum("td,df->tf", x, sh["gate"].astype(dt), preferred_element_type=F32)
    u = jnp.einsum("td,df->tf", x, sh["up"].astype(dt), preferred_element_type=F32)
    h = (_act(cfg.act, g) * u).astype(dt)
    return jnp.einsum("tf,fd->td", h, sh["down"].astype(dt), preferred_element_type=F32)


def moe_ffn(
    p: dict[str, Any],
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    sctx: ShardingCtx,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B,S,d), aux_loss scalar)."""
    mo = cfg.moe
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    mesh = sctx.mesh

    ep_axes: tuple[str, ...] = ()
    tok_axes: tuple[str, ...] = ()
    if mesh is not None:
        ep_axes = tuple(
            a for a in sctx.profile.candidates("expert") if a in mesh.shape
        )
        ep_size = 1
        kept = []
        for a in ep_axes:
            if mo.n_experts % (ep_size * mesh.shape[a]) == 0:
                kept.append(a)
                ep_size *= mesh.shape[a]
        ep_axes = tuple(kept)
        tok_axes = tuple(
            a
            for a in sctx.profile.candidates("batch")
            if a in mesh.shape and a not in ep_axes
        )
        tok_size = 1
        kept = []
        for a in tok_axes:
            if (B * S) % (tok_size * mesh.shape[a]) == 0:
                kept.append(a)
                tok_size *= mesh.shape[a]
        tok_axes = tuple(kept)

    ep_shards = 1
    for a in ep_axes:
        ep_shards *= mesh.shape[a]
    tok_shards = 1
    for a in tok_axes:
        tok_shards *= mesh.shape[a]

    t_local = (B * S) // tok_shards
    n_local = mo.n_experts // ep_shards
    cap = min(
        _round_up(int(mo.capacity_factor * t_local * mo.top_k / ep_shards) or 1, 8),
        t_local * mo.top_k,
    )

    if mesh is None:
        out, stats = _local_moe(x_flat, p, cfg, 0, mo.n_experts, cap)
        if mo.n_shared:
            out = out + _shared_ffn_partial(x_flat, p["shared"], cfg)
    else:
        tok_spec = P(tok_axes if tok_axes else None)
        ep_spec = P(ep_axes if ep_axes else None)
        mlp_spec = sctx.spec((1, mo.n_shared * mo.d_ff_expert or 1), (None, "mlp")) if mo.n_shared else None

        in_specs = (
            P(tok_spec[0], None),  # x_flat: tokens sharded, d replicated
            {
                "router": P(None, None),
                "w_gate": P(ep_spec[0], None, None),
                "w_up": P(ep_spec[0], None, None),
                "w_down": P(ep_spec[0], None, None),
                **(
                    {
                        "shared": {
                            "gate": P(None, mlp_spec[1] if len(mlp_spec) > 1 else None),
                            "up": P(None, mlp_spec[1] if len(mlp_spec) > 1 else None),
                            "down": P(mlp_spec[1] if len(mlp_spec) > 1 else None, None),
                        }
                    }
                    if mo.n_shared
                    else {}
                ),
            },
        )
        out_specs = (P(tok_spec[0], None), P())

        def shard_fn(xl: jax.Array, pl: dict[str, Any]) -> tuple[jax.Array, jax.Array]:
            if ep_axes:
                rank = jax.lax.axis_index(ep_axes[0]) if len(ep_axes) == 1 else (
                    jax.lax.axis_index(ep_axes[0]) * mesh.shape[ep_axes[1]]
                    + jax.lax.axis_index(ep_axes[1])
                )
            else:
                rank = 0
            e0 = rank * n_local
            y, stats = _local_moe(xl, pl, cfg, e0, n_local, cap)
            if mo.n_shared:
                y = y + _shared_ffn_partial(xl, pl["shared"], cfg)
            if ep_axes:
                y = jax.lax.psum(y, ep_axes)
            if tok_axes:
                stats = jax.lax.pmean(stats, tok_axes)
            if ep_axes:
                # stats identical on every ep rank; pmean is a cheap no-op
                # correctness guard so out_specs P() is well-formed.
                stats = jax.lax.pmean(stats, ep_axes)
            return y, stats

        out, stats = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )(x_flat, p)

    E = mo.n_experts
    frac, mean_prob = stats[:E], stats[E:]
    aux = E * jnp.sum(frac * mean_prob) * mo.aux_coef
    out = constrain(out.reshape(B, S, d).astype(cdt(cfg)), ("batch", "seq", "embed_act"), sctx)
    return out, aux


def _e0_for_local_rank(rank: int, n_local: int) -> int:
    return rank * n_local
