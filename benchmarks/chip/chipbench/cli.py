"""One run of one cell: set up, measure, check, print one JSON line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` a few seconds mid-window are traced and the metrics
are the cell's per-layer metrics. The last lines on stderr, and the
``check`` key that ends the JSON line, give each number compared for
``correct`` beside its limit.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from chipbench import check, serving, spec, traffic
from chipbench import trace as tracing
from chipbench.device import memory_peak_bytes, peaks_for, require_tpu
from chipbench.readers import Run
from chipbench.weights import Dims


def end_to_end(name: str, win, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "ttft_p90_s":
        return float(np.percentile(win.ttft_s(), 90))
    if name == "itl_p95_ms":
        return float(np.percentile(win.itl_s(), 95)) * 1e3
    if name == "out_tok_s":
        return win.tokens_in_window() / win.seconds
    raise KeyError(f"no end-to-end metric {name!r}")


def _log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


def run_cell(
    cell: spec.Cell, seed: int, seconds: float, trace: bool, *, t0: float,
    device: dict, cache_root: Path | None = None,
) -> dict:
    """Everything after the chip check; returns the result line's object."""
    import jax

    from chipbench.cache import CompileStats, enable_compile_cache

    if cache_root is not None:
        _log(compile_cache=enable_compile_cache(cache_root))
    stats = CompileStats()
    m = Dims.from_config(cell.config)
    sched, cfg, sctx = serving.build(cell, seed)
    warm = serving.warm_up(sched, cell.traffic, m.vocab, seed)
    reqs = traffic.build(cell.traffic, seed, seconds, m.vocab)
    setup_s = time.perf_counter() - t0
    _log(setup_s=setup_s, warm_lengths=warm, requests=len(reqs), **stats.snapshot())

    before = stats.snapshot()
    win = serving.Window(sched, cell.traffic, reqs, seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    win.run(trace_dir=trace_dir)
    devices = list(sctx.mesh.devices.flat) if sctx.mesh is not None else jax.devices()[:1]
    peak = memory_peak_bytes(devices)
    lat = win.lateness_s()
    gc_long = max(win.gc_pauses, key=lambda p: p[1], default=(win.t_open, 0.0, 0))
    _log(
        window_steps=len(win.window_steps()), tokens=win.tokens_in_window(),
        attempted=win.attempted(), finished=len(win.finished()),
        compiles_in_window=stats.snapshot()["compiles"] - before["compiles"],
        generator_late_max_s=max(lat) if lat else 0.0,
        generator_late_at=win.tracks[int(np.argmax(lat))].due - win.t_open if lat else 0.0,
        generator_late_mean_s=float(np.mean(lat)) if lat else 0.0,
        decode_traces=sched.decode_traces, chunk_traces=sched.chunk_traces,
        slowest_steps=[
            {"s": st.t1 - st.t0, "at": st.t0 - win.t_open, "rows": len(st.decode_ctx),
             "chunk": st.chunk}
            for st in sorted(win.steps, key=lambda st: st.t0 - st.t1)[:3]
        ],
        longest_loop_gap_s=max(
            (b.t0 - a.t1 for a, b in zip(win.steps, win.steps[1:])), default=0.0),
        gc_pauses=len(win.gc_pauses), gc_total_s=sum(p[1] for p in win.gc_pauses),
        gc_longest={"s": gc_long[1], "at": gc_long[0] - win.t_open, "generation": gc_long[2]},
    )
    metrics = {}
    if not trace:
        for e in cell.end_to_end:
            metrics[e["name"]] = {"value": end_to_end(e["name"], win, setup_s), "unit": e["unit"]}

    # The program's state goes before the reference runs.
    finished = [(tr.req.prompt, list(tr.rs.tokens)) for tr in win.finished()]
    attempted = win.attempted()
    shards = (serving.mesh_shape(cell.config) or (1, 1))[1]
    run = Run(
        steps=win.window_steps(), traced=[s for s in win.steps if s.traced], trace=None,
        dims=m, shards=shards, chips=len(devices), peaks=peaks_for(device["kind"])
        if device["platform"] == "tpu" else None,
    )
    del sched, win
    gc.collect()

    picked = check.sample(finished, seed)
    gap = None
    if picked:
        packed = check.pack([finished[i] for i in picked])
        ref = spec.reference_module(cell.config["reference"])
        gaps, _ = check.logit_gaps(ref, m, seed, *packed)
        gap = float(np.max(gaps))
        _log(compared_requests=len(picked), compared_tokens=int(len(gaps)),
             tokens_off_best=int(np.sum(gaps > 0)), mean_gap=float(np.mean(gaps)))
    limit = float(cell.check["max_logit_gap"])
    correct = gap is not None and gap <= limit

    out = {
        "correct": correct,
        "attempted": attempted,
        "failed": 0,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=peak),
    }
    if trace:
        run.trace = tracing.read(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for p in cell.per_layer:
            v = spec.metric_reader(p["name"])(run)
            if v is not None:
                metrics[p["name"]] = {"value": v, "unit": p["unit"]}
        lo, hi = run.trace.window
        out["device"]["busy_s"] = float(np.mean(
            [run.trace.busy_ns(d, lo, hi) for d in range(len(devices))])) * 1e-9
        out["device"]["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = tracing.breakdown(run.trace)
        _log(traced_steps=len(run.traced), trace_steps=len(run.trace.steps))
    out["check"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    print(f"check max_logit_gap {gap!r} limit {limit!r} correct {correct}", file=sys.stderr)
    return out


def main(argv: list[str], *, t0: float, root: Path) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = spec.load_cell(root, a.workload)
    device = require_tpu(cell.chips)
    out = run_cell(cell, a.seed, a.seconds, bool(a.trace), t0=t0, device=device, cache_root=root)
    print(json.dumps(out), flush=True)
    return 0
