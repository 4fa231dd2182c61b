"""Runs with the timed path broken underneath come out as not correct.

Each test skips the harness's look for a chip and drives the rest of a
run at smoke widths on the CPU, with one fault planted in the program the
window drives: a token altered where it is produced, a decode step that
returns its state unchanged, a decode step that leaves half of its rows
out, and (on four virtual devices, tensor parallel) the all-reduce
between chips left out."""
import json
import os
import subprocess
import sys
import time

import pytest

import bench_smoke
from chipbench import cli, serving

SEED = 2**31 + 202


def _run_with(monkeypatch, plant):
    build = serving.build_scheduler

    def broken(*a, **kw):
        sched = build(*a, **kw)
        plant(sched)
        return sched

    monkeypatch.setattr(serving, "build_scheduler", broken)
    return cli.run_cell(bench_smoke.cell(), SEED, 2.0, False, t0=time.perf_counter(),
                        device=bench_smoke.CPU)


def test_token_altered_where_produced(monkeypatch):
    def plant(sched):
        sample, vocab = sched.programs.sample, sched.cfg.vocab_size
        sched.programs.sample = lambda *a: (sample(*a) + 1) % vocab

    out = _run_with(monkeypatch, plant)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > out["check"]["max_logit_gap"]["limit"]


def test_decode_returns_its_state_unchanged(monkeypatch):
    def plant(sched):
        decode = sched.programs.decode
        sched.programs.decode = lambda params, states, *a: (decode(params, states, *a)[0], states)

    out = _run_with(monkeypatch, plant)
    assert out["correct"] is False


def test_half_of_the_batch_left_out(monkeypatch):
    def plant(sched):
        decode = sched.programs.decode

        def half(params, states, token, active):
            # Every other slot: the smoke mix seldom fills the upper half.
            return decode(params, states, token, active.at[1::2].set(False))

        sched.programs.decode = half

    out = _run_with(monkeypatch, plant)
    assert out["correct"] is False


TP_RUN = """
import json, sys, time
sys.path[:0] = {paths!r}
import bench_smoke
from chipbench import cli
if sys.argv[1] == "fault":
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.models import blocks, layers

    def mlp_without_allreduce(p, cfg, x, sctx):
        dt = layers.cdt(cfg)
        g = jnp.einsum("...d,df->...f", x, p["gate"].astype(dt))
        u = jnp.einsum("...d,df->...f", x, p["up"].astype(dt))
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(dt)
        # Each chip keeps its own partial sum of the row-parallel product.
        return jax.shard_map(
            lambda h, w: jnp.einsum("...f,fd->...d", h, w),
            mesh=sctx.mesh, in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(), check_vma=False,
        )(h, p["down"].astype(dt))

    blocks.mlp = mlp_without_allreduce
out = cli.run_cell(bench_smoke.cell("qwen2-smoke-tp4.json"), {seed}, 2.0, False,
                   t0=time.perf_counter(), device=dict(bench_smoke.CPU, count=4))
print(json.dumps(out))
"""


@pytest.mark.parametrize("mode,correct", [("sound", True), ("fault", False)])
def test_allreduce_left_out(mode, correct):
    here = os.path.dirname(os.path.abspath(__file__))
    code = TP_RUN.format(paths=[here, str(bench_smoke.ROOT / "benchmarks" / "chip")], seed=SEED)
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(bench_smoke.ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
    )
    p = subprocess.run([sys.executable, "-c", code, mode], capture_output=True, text=True,
                       env=env, timeout=900, cwd=bench_smoke.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct, out["check"]
