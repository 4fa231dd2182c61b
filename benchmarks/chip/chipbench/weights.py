"""Weights from the seed, in the published model's own layout.

The benchmark makes the weights, not the program: each leaf of layer
``i`` is drawn from a key folded from the seed, the leaf's fixed index and
``i``, and rounded to bf16, the type it is served in. The program gets all
layers at once, made on the device in one jitted call (``program_params``);
the reference makes layer ``i`` alone, from the same keys (``layer``), so
it takes nothing the program has made.

Leaf names follow the Hugging Face checkpoints of the Qwen families;
shapes split the head axes out: ``q_proj`` is (hidden, heads, head_dim).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

# Fixed leaf indices: a key depends on the index, never on list order.
LEAF_IDS = {
    "embed_tokens": 0, "norm": 1, "lm_head": 2,
    "input_layernorm": 10, "q_proj": 11, "k_proj": 12, "v_proj": 13, "o_proj": 14,
    "q_bias": 15, "k_bias": 16, "v_bias": 17, "q_norm": 18, "k_norm": 19,
    "post_attention_layernorm": 20, "gate_proj": 21, "up_proj": 22, "down_proj": 23,
}


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    qk_norm: bool
    qkv_bias: bool

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            ff=c["intermediate_size"], vocab=c["vocab_size"], eps=c["rms_norm_eps"],
            theta=float(c["rope_theta"]), qk_norm=c["model_type"] == "qwen3",
            qkv_bias=c["model_type"] == "qwen2",
        )


def layer_leaves(m: Dims) -> dict[str, tuple[tuple[int, ...], float, float]]:
    """name -> (shape, std, mean) of every per-layer leaf."""
    d, h, kv, hd, ff = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    out = {
        "input_layernorm": ((d,), 0.1, 1.0),
        "q_proj": ((d, h, hd), d ** -0.5, 0.0),
        "k_proj": ((d, kv, hd), d ** -0.5, 0.0),
        "v_proj": ((d, kv, hd), d ** -0.5, 0.0),
        "o_proj": ((h, hd, d), (h * hd) ** -0.5, 0.0),
        "post_attention_layernorm": ((d,), 0.1, 1.0),
        "gate_proj": ((d, ff), d ** -0.5, 0.0),
        "up_proj": ((d, ff), d ** -0.5, 0.0),
        "down_proj": ((ff, d), ff ** -0.5, 0.0),
    }
    if m.qkv_bias:
        out.update({
            "q_bias": ((h, hd), 0.1, 0.0),
            "k_bias": ((kv, hd), 0.1, 0.0),
            "v_bias": ((kv, hd), 0.1, 0.0),
        })
    if m.qk_norm:
        out.update({"q_norm": ((hd,), 0.1, 1.0), "k_norm": ((hd,), 0.1, 1.0)})
    return out


def global_leaves(m: Dims) -> dict[str, tuple[tuple[int, ...], float, float]]:
    return {
        "embed_tokens": ((m.vocab, m.d), 1.0, 0.0),
        "norm": ((m.d,), 0.1, 1.0),
        "lm_head": ((m.d, m.vocab), m.d ** -0.5, 0.0),
    }


def base_key(seed: int) -> jax.Array:
    """A key from any whole-number seed, also one past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def draw(base: jax.Array, name: str, layer, shape, std: float, mean: float) -> jax.Array:
    """One leaf (of one layer) in bf16."""
    k = jax.random.fold_in(jax.random.fold_in(base, LEAF_IDS[name]), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std + mean).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("m",))
def layer(base: jax.Array, m: Dims, i) -> dict[str, jax.Array]:
    """Layer ``i``'s leaves in float32 (the bf16 values, widened)."""
    return {
        n: draw(base, n, i, s, std, mu).astype(jnp.float32)
        for n, (s, std, mu) in layer_leaves(m).items()
    }


@functools.partial(jax.jit, static_argnames=("m",))
def globals_f32(base: jax.Array, m: Dims) -> dict[str, jax.Array]:
    return {
        n: draw(base, n, 0, s, std, mu).astype(jnp.float32)
        for n, (s, std, mu) in global_leaves(m).items()
    }


def stacked(base: jax.Array, m: Dims) -> dict[str, jax.Array]:
    """Every leaf in bf16, per-layer leaves stacked on a leading layer axis."""
    ids = jnp.arange(m.layers)
    out = {
        n: jax.vmap(lambda i, n=n, s=s, std=std, mu=mu: draw(base, n, i, s, std, mu))(ids)
        for n, (s, std, mu) in layer_leaves(m).items()
    }
    out.update({n: draw(base, n, 0, s, std, mu) for n, (s, std, mu) in global_leaves(m).items()})
    return out


def to_program(g: dict[str, jax.Array], m: Dims, padded_vocab: int) -> dict:
    """The published layout as the program's parameter tree.

    The program multiplies embeddings by sqrt(hidden) (``models/lm.py``);
    Qwen does not, so the table is stored divided by that factor, as a
    checkpoint converter would. The vocabulary pads with zero rows."""
    scale = jnp.asarray(m.d ** 0.5, jnp.bfloat16).astype(jnp.float32)
    pad = padded_vocab - m.vocab
    tok = (g["embed_tokens"].astype(jnp.float32) / scale).astype(jnp.bfloat16)
    attn = {"wq": g["q_proj"], "wk": g["k_proj"], "wv": g["v_proj"], "wo": g["o_proj"]}
    if m.qkv_bias:
        attn.update({"bq": g["q_bias"], "bk": g["k_bias"], "bv": g["v_bias"]})
    if m.qk_norm:
        attn.update({"q_norm": {"scale": g["q_norm"]}, "k_norm": {"scale": g["k_norm"]}})
    block = {
        "ln1": {"scale": g["input_layernorm"]},
        "attn": attn,
        "ln2": {"scale": g["post_attention_layernorm"]},
        "mlp": {"gate": g["gate_proj"], "up": g["up_proj"], "down": g["down_proj"]},
    }
    return {
        "embed": {
            "tok": jnp.pad(tok, ((0, pad), (0, 0))),
            "unembed": jnp.pad(g["lm_head"], ((0, 0), (0, pad))),
        },
        "stack": {"groups": {"g0": block}},
        "final_norm": {"scale": g["norm"]},
    }


def program_params(seed: int, m: Dims, cfg, sctx):
    """The program's weights on the device, made in one jitted call at their
    mesh placement. Shapes are checked against the program's own schema."""
    from repro.models import lm
    from repro.models.schema import is_spec, sharding_tree

    schema = lm.model_schema(cfg)
    fn = lambda key: to_program(stacked(key, m), m, cfg.padded_vocab)
    path = jax.tree_util.keystr
    want = {path(p): s.shape for p, s in jax.tree.flatten_with_path(schema, is_leaf=is_spec)[0]}
    got = {path(p): a.shape for p, a in jax.tree.flatten_with_path(
        jax.eval_shape(fn, base_key(seed)))[0]}
    if want != got:
        raise ValueError(f"weights do not match the program's schema:\n{want}\n{got}")
    shardings = sharding_tree(schema, sctx)
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(fn, **kw)(base_key(seed))
