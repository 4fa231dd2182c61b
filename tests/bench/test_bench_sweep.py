"""The evaluation-sweep cell `qwen2.5-14b-tp4.sweep`: its files load with
the shapes and metrics it promises, its all-reduce reader counts what it
says on a hand-made trace, and a closed-loop window at Qwen2.5-14B's head
ratio (10 query and 2 KV heads a shard over four virtual devices, the
Pallas paged kernels interpreted) serves the reference's tokens."""
import json
import os
import re
import subprocess
import sys

import pytest

import bench_smoke
import test_bench_reference
from chipbench import counts, serving, spec, trace
from chipbench.device import PEAKS
from chipbench.readers import DECODE, Run
from chipbench.weights import Dims

CELL = "qwen2.5-14b-tp4.sweep"
PER_LAYER = {
    "allreduce_share.sweep", "paged_decode_roofline.sweep", "paged_chunk_roofline.sweep",
    "idle_in_step_share.sweep", "mfu.sweep",
}
SEED = 2**31 + 303


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(bench_smoke.ROOT, CELL)


def test_sweep_cell_loads(cell):
    assert cell.chips == 4
    assert {e["name"] for e in cell.end_to_end} == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    assert {p["name"] for p in cell.per_layer} == PER_LAYER
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 128
    m = Dims.from_config(cell.config)
    shards = serving.mesh_shape(cell.config)[1]
    assert (m.heads // shards, m.kv_heads // shards) == (10, 2)
    assert m.qkv_bias and not m.qk_norm and m.layers == 48
    pages = int(re.match(r"(\d+) pages", cell.config["assumed"]["kv_pool_bytes_per_chip"])[1])
    assert serving.n_pages(cell.config, cell.traffic["scheduler"]) == pages


def test_sweep_counts(cell):
    # Per layer: q 5120*40*128 + k, v 2*5120*8*128 + o 40*128*5120 + MLP
    # 3*5120*13824: 14.77B parameters with the embedding and the head.
    m = Dims.from_config(cell.config)
    assert counts.matmul_params(m) == 26_214_400 + 10_485_760 + 26_214_400 + 212_336_640
    total = m.layers * counts.matmul_params(m) + 2 * m.vocab * m.d
    assert 14.7e9 < total < 14.8e9
    # A chip's share of one layer's paged decode: K and V of 1,000 live
    # tokens for 2 KV heads, q and o of one row of 10 heads; memory bound.
    nbytes = 2 * 2 * 128 * 2 * 1000 + 2 * 10 * 128 * 2
    assert counts.paged_decode_least_s(m, [1000], 4, PEAKS["TPU v5 lite"]) == pytest.approx(
        nbytes / 819e9)


AR = "%all-reduce.{i} = bf16[64,1,5120]{{2,0,1}} all-reduce(bf16[64,1,5120]{{2,0,1}} %fusion.{i})"
FUSION = "%fusion.9 = bf16[64,1,5120]{2,0,1} fusion(bf16[64,1,5120]{2,0,1} %all-reduce.1)"


def _synthetic_run(with_trace=True):
    # Two chips; times in ns. Chip 0: two decode calls of 100 each holding
    # all-reduces of 10 + 5 and 20, and an all-reduce in another program
    # that must not count. Chip 1: one decode call of 200 with 50 of them.
    dev0 = trace.Device(
        modules=[("jit__decode_fn(1)", 0, 100), ("jit__decode_fn(1)", 200, 300),
                 ("jit__chunk_fn(2)", 400, 500)],
        ops=[(AR.format(i=1), 10, 20, DECODE), (FUSION, 20, 60, DECODE),
             (AR.format(i=2), 60, 65, DECODE), (AR.format(i=3), 210, 230, DECODE),
             (AR.format(i=4), 410, 490, "_chunk_fn")],
    )
    dev1 = trace.Device(
        modules=[("jit__decode_fn(1)", 0, 200)],
        ops=[(AR.format(i=1), 0, 50, DECODE), (FUSION, 50, 150, DECODE)],
    )
    tr = trace.Trace(steps=[(0, 300)], spans=[], devices=[dev0, dev1]) if with_trace else None
    m = Dims.from_config(json.loads((bench_smoke.DATA / "qwen2-smoke-tp4-g5.json").read_text()))
    return Run(steps=[], traced=[], trace=tr, dims=m, shards=4, chips=2,
               peaks=PEAKS["TPU v5 lite"])


def test_allreduce_share_reader():
    read = spec.metric_reader("allreduce_share.sweep")
    # Chip 0: 35 of 200; chip 1: 50 of 200; the mean of 17.5% and 25%.
    assert read(_synthetic_run()) == pytest.approx(21.25)
    assert read(_synthetic_run(with_trace=False)) is None


SWEEP_RUN = """
import dataclasses, json, sys, time
sys.path[:0] = {paths!r}
import bench_smoke
from chipbench import cli, serving

MIX = {{
    "loop": "closed", "clients": 96, "ramp_s": 0.5, "schedule_seed": 7, "temperature": 0.0,
    "prompt_len": {{"median": 40, "sigma": 0.8, "min": 8, "max": 100}},
    "output_len": {{"median": 12, "sigma": 0.5, "min": 8, "max": 24}},
    "scheduler": {{"n_slots": 4, "cache_len": 128, "page_size": 16, "chunk_budget": 32,
                  "min_chunk": 8, "preemption": "off"}},
}}
windows = []
run = serving.Window.run
serving.Window.run = lambda self, *a, **kw: (windows.append(self), run(self, *a, **kw))[1]
cell = dataclasses.replace(bench_smoke.cell("qwen2-smoke-tp4-g5.json"), traffic=MIX, chips=4)
out = cli.run_cell(cell, {seed}, 2.0, False, t0=time.perf_counter(),
                   device=dict(bench_smoke.CPU, count=4))
from repro.serve.plan import chunk_bucket
s = MIX["scheduler"]
out["chunk_buckets"] = sorted({{
    chunk_bucket(st.chunk[1], chunk_budget=s["chunk_budget"], min_chunk=s["min_chunk"])[0]
    for st in windows[0].window_steps() if st.chunk is not None
}})
print(json.dumps(out))
"""


def test_closed_loop_at_the_14b_head_ratio_is_correct():
    here = os.path.dirname(os.path.abspath(__file__))
    code = SWEEP_RUN.format(paths=[here, str(bench_smoke.ROOT / "benchmarks" / "chip")], seed=SEED)
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=os.pathsep.join([str(bench_smoke.ROOT / "src"), os.environ.get("PYTHONPATH", "")]),
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=900, cwd=bench_smoke.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["check"]["max_logit_gap"]["value"] is not None
    assert len(out["chunk_buckets"]) >= 2, out["chunk_buckets"]


def test_reference_matches_program_logits_at_the_14b_head_ratio():
    test_bench_reference.test_reference_matches_program_logits("qwen2-smoke-tp4-g5.json")
