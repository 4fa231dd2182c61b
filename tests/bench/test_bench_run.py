"""A benchmark run rehearsed on the CPU at smoke widths: the harness's
window loop gives a last line of the contract's shape, the device check
refuses the CPU, and the float8 control comes out as not correct."""
import json
import os
import subprocess
import sys
import time

import numpy as np

import bench_smoke
from chipbench import check, cli, serving, spec, traffic
from chipbench.weights import Dims

SEED = 2**31 + 101


def test_run_line_has_the_contract_shape():
    t0 = time.perf_counter()
    out = cli.run_cell(bench_smoke.cell(), SEED, 2.0, False, t0=t0, device=bench_smoke.CPU)
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    for v in line["metrics"].values():
        assert v["value"] > 0 and v["unit"]
    assert line["device"]["platform"] == "cpu" and "memory_peak_bytes" in line["device"]
    gap = line["check"]["max_logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"] == bench_smoke.LIMIT


def test_run_py_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(bench_smoke.ROOT / "benchmarks" / "chip" / "run.py"),
         "--workload", "qwen3-8b-h18.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=bench_smoke.ROOT,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_float8_control_is_not_correct():
    cell = bench_smoke.cell()
    m = Dims.from_config(cell.config)
    sched, _, _ = serving.build(cell, SEED)
    serving.warm_up(sched, cell.traffic, m.vocab, SEED)
    win = serving.Window(sched, cell.traffic, traffic.build(cell.traffic, SEED, 2.0, m.vocab), 2.0)
    win.run()
    finished = [(tr.req.prompt, list(tr.rs.tokens)) for tr in win.finished()]
    picked = check.sample(finished, SEED)
    ref = spec.reference_module(cell.config["reference"])
    gaps, gaps_c = check.logit_gaps(
        ref, m, SEED, *check.pack([finished[i] for i in picked]), control=True
    )
    assert np.max(gaps) <= bench_smoke.LIMIT < np.max(gaps_c)
