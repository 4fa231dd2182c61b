"""Readings that set a cell's rate and its correctness limit, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --knee 1.0,1.5,2.0 --seconds 30
    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 --control 1,2,3 --seconds 45

``--knee`` serves the cell's open-loop mix at each rate for one window and
reports the queue at the window's start and end: the knee is the highest
rate whose queue did not grow. ``--seeds`` serves one window per seed, with
that seed's weights and traffic, and reads the widest logit gap of the
served tokens against the reference, as a run does; seeds also named in
``--control`` read the float8 control's gap on the same tokens. Every
reading is one JSON line on stdout. Set-up is paid once per process.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, serving, spec, traffic  # noqa: E402
from chipbench.cache import enable_compile_cache  # noqa: E402
from chipbench.device import require_tpu  # noqa: E402
from chipbench.weights import Dims, program_params  # noqa: E402


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def _queue(sched) -> int:
    return sched.pending + sum(1 for rs in sched._active.values() if rs.status.value == "prefilling")


def knee(cell, rates, seconds: float, seed: int) -> None:
    m = Dims.from_config(cell.config)
    sched, _, _ = serving.build(cell, seed)
    serving.warm_up(sched, cell.traffic, m.vocab, seed)
    for rate in rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        win = serving.Window(sched, mix, traffic.build(mix, seed, seconds, m.vocab), seconds)
        q = {}
        orig = win._step

        def step(inflight, win=win, orig=orig):
            if "open" not in q and time.perf_counter() >= win.t_open:
                q["open"] = _queue(win.sched)
            return orig(inflight)

        win._step = step
        win.run()
        q_close = _queue(sched)
        ttft, itl = win.ttft_s(), win.itl_s()
        _emit(rate=rate, queue_open=q.get("open"), queue_close=q_close,
              attempted=win.attempted(), tokens=win.tokens_in_window(),
              ttft_p50_s=float(np.percentile(ttft, 50)), ttft_p90_s=float(np.percentile(ttft, 90)),
              itl_p50_ms=1e3 * float(np.percentile(itl, 50)),
              itl_p95_ms=1e3 * float(np.percentile(itl, 95)),
              steps=len(win.window_steps()),
              chunk_steps=sum(1 for s in win.window_steps() if s.chunk is not None))
        t = time.perf_counter()
        sched.run()  # drain before the next rate
        _emit(rate=rate, drained_s=time.perf_counter() - t)


def seeds(cell, seed_list, control, seconds: float) -> None:
    m = Dims.from_config(cell.config)
    ref = spec.reference_module(cell.config["reference"])
    sched, cfg, sctx = serving.build(cell, seed_list[0])
    serving.warm_up(sched, cell.traffic, m.vocab, seed_list[0])
    programs = sched.programs
    for seed in seed_list:
        if sched is None:
            sched = serving.build_scheduler(cell, cfg, sctx, program_params(seed, m, cfg, sctx), seed)
            sched.programs = programs  # same compiled programs, fresh state
        win = serving.Window(sched, cell.traffic, traffic.build(cell.traffic, seed, seconds, m.vocab), seconds)
        win.run()
        finished = [(tr.req.prompt, list(tr.rs.tokens)) for tr in win.finished()]
        del win
        sched = None
        gc.collect()
        picked = check.sample(finished, seed)
        gaps, gaps_c = check.logit_gaps(
            ref, m, seed, *check.pack([finished[i] for i in picked]), control=seed in control
        )
        _emit(seed=seed, requests=len(picked), tokens=int(len(gaps)),
              longest=max(len(finished[i][0]) + len(finished[i][1]) for i in picked),
              max_gap=float(np.max(gaps)), tokens_off_best=int(np.sum(gaps > 0)),
              control_max_gap=None if gaps_c is None else float(np.max(gaps_c)),
              control_off_best=None if gaps_c is None else int(np.sum(gaps_c > 0)))
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--knee", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    a = ap.parse_args()
    cell = spec.load_cell(ROOT, a.workload)
    require_tpu(cell.chips)
    enable_compile_cache(ROOT)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    if a.knee:
        knee(cell, [float(x) for x in a.knee.split(",")], a.seconds, 1)
    if a.seeds:
        seeds(cell, ints(a.seeds), set(ints(a.control)), a.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
