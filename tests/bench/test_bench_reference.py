"""The plain float32 references against the program's logits, at smoke
widths on the CPU, on weights the benchmark draws from a seed: Qwen3
(per-head q/k norm) and Qwen2 (q/k/v bias)."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from chipbench import check, serving, spec  # noqa: E402
from chipbench import weights as W  # noqa: E402

SEED = 2**31 + 3


def _config(name):
    c = json.loads((ROOT / "tests" / "bench" / "data" / name).read_text())
    return dict(c, mesh=[1, 1])


@pytest.mark.parametrize("name", ["qwen3-smoke.json", "qwen2-smoke-tp4.json"])
def test_reference_matches_program_logits(name):
    from repro.models import lm
    from repro.sharding.rules import ShardingCtx

    c = _config(name)
    m = W.Dims.from_config(c)
    cfg = serving.model_config(c, "smoke")
    sctx = ShardingCtx.null()
    params = W.program_params(SEED, m, cfg, sctx)
    ref = spec.reference_module(c["reference"])
    rng = np.random.default_rng(0)
    lens = (5, 23, 40)
    seqs = [rng.integers(0, m.vocab, size=n).astype(np.int32) for n in lens]
    tokens, pos, seg, rows, _ = check.pack([(s[:-1], [int(s[-1]), 0]) for s in seqs], cap=128)
    base = W.base_key(SEED)
    g = W.globals_f32(base, m)
    x = ref.embed(g, jnp.asarray(tokens))
    for i in range(m.layers):
        x = ref.layer(W.layer(base, m, i), x, jnp.asarray(pos), jnp.asarray(seg), eps=m.eps, theta=m.theta)
    want = np.asarray(ref.head(g, x, jnp.asarray(rows), eps=m.eps))
    # The packed row holds each sequence in full; its last position's
    # logits are the prefill's.
    last = [k for k in range(len(rows)) if k + 1 == len(rows) or seg[rows[k + 1]] != seg[rows[k]]]
    for s, k in zip(seqs, last):
        got, _ = lm.prefill(params, cfg, {"tokens": jnp.asarray(s)[None]}, sctx)
        got = np.asarray(got[0, -1, : m.vocab])
        err = np.max(np.abs(got - want[k])) / np.max(np.abs(want[k]))
        assert err < 1e-4, (name, len(s), err)
        assert np.argmax(got) == np.argmax(want[k])


def test_layer_draw_matches_the_stacked_program_weights():
    c = _config("qwen3-smoke.json")
    m = W.Dims.from_config(c)
    stacked = jax.jit(lambda k: W.stacked(k, m))(W.base_key(SEED))
    for i in range(m.layers):
        one = W.layer(W.base_key(SEED), m, i)
        for n, a in one.items():
            assert np.array_equal(np.asarray(a), np.asarray(stacked[n][i], np.float32)), n


def test_float8_control_moves_the_logits():
    c = _config("qwen3-smoke.json")
    m = W.Dims.from_config(c)
    w = W.layer(W.base_key(SEED), m, 0)
    q = check.fp8_matrices(w)
    for n in check.MATRICES:
        rel = float(jnp.max(jnp.abs(q[n] - w[n])) / jnp.max(jnp.abs(w[n])))
        assert 1e-3 < rel < 0.1, (n, rel)
    assert np.array_equal(np.asarray(q["input_layernorm"]), np.asarray(w["input_layernorm"]))
