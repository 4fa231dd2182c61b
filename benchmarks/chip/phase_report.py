"""Where a step's host time and a request's wait go: one traced window of a
BENCHMARK.json cell on the chip, read with the serving program's own spans.

    python3 benchmarks/chip/phase_report.py --workload <cell> --seed <n> --seconds <s>

Sets up and sends the cell's traffic as ``run.py`` does, traces the same
seconds mid-window that ``run.py`` traces, and prints one JSON line: the mean host time of a step
(``serve.step`` less its waits on the device), the p90s of admit wait and
prefill, the split of the time to first token, the device's idle ms per
step by the program phase the host was in, the trace breakdown with its
gaps so named, the names of the kernel ops, step times with the profiler
on and off, what an unrecorded span costs, and the slowest steps with
their phase times. It checks, and reports on
stderr, that the harness's first-token stamp follows the program's by at
most the wall time of the step that emitted the token. It runs no
correctness check (``run.py`` does). Needs the chip, as ``run.py`` does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import phases, serving, spec, traffic  # noqa: E402
from chipbench import trace as tracing  # noqa: E402
from chipbench.cache import enable_compile_cache  # noqa: E402
from chipbench.device import require_tpu  # noqa: E402
from chipbench.weights import Dims  # noqa: E402


def _mean(xs, scale: float = 1.0) -> float | None:
    xs = list(xs)
    return scale * float(np.mean(xs)) if xs else None


def step_times(win: phases.PhaseWindow) -> dict:
    """Mean step wall time and host time, in ms, of the window's steps with
    the profiler on and off, apart for steps with and without a chunk."""
    out = {}
    rows = win.in_window(list(zip(win.steps, win.host)))
    for traced in (True, False):
        for chunk in (True, False):
            sel = [(s, h) for s, h in rows if s.traced == traced and (s.chunk is not None) == chunk]
            out[f"{'on' if traced else 'off'}.{'chunk' if chunk else 'decode'}"] = {
                "n": len(sel),
                "wall_ms": _mean((s.t1 - s.t0 for s, _ in sel), 1e3),
                "host_ms": _mean((h for _, h in sel if h is not None), 1e3),
            }
    return out


def stamp_check(win: phases.PhaseWindow) -> dict:
    """The harness's first-token stamp less the program's, for every
    request whose first token came in the window, beside the wall time of
    the step that emitted it."""
    by_end = {s.t1: s for s in win.steps}
    rows = []
    for tr in win.tracks:
        if tr.stamps and win.t_open <= tr.stamps[0] <= win.t_close and tr.rs.t_first_token > 0:
            s = by_end[tr.stamps[0]]
            rows.append((tr.stamps[0] - tr.rs.t_first_token, s.t1 - s.t0))
    return {
        "n": len(rows),
        "outside": sum(not 0 <= d <= w for d, w in rows),
        "delay_mean_s": _mean(d for d, _ in rows),
        "delay_max_s": max((d for d, _ in rows), default=None),
        "step_wall_mean_s": _mean(w for _, w in rows),
    }


def span_cost_us(sched, n: int = 20000) -> float | None:
    """Host cost of one of the scheduler's spans with no trace running, in
    microseconds. The scheduler's ``phase_time_s`` is left as it was."""
    phase = getattr(sched, "_phase", None)
    if phase is None:
        return None
    kept = dict(sched.phase_time_s)
    try:
        t = time.perf_counter()
        for _ in range(n):
            with phase("serve.cost"):
                pass
        return 1e6 * (time.perf_counter() - t) / n
    finally:
        sched.phase_time_s = kept


def kernel_ops(tr) -> dict[str, list[str]]:
    """The distinct names of the kernel ops of each program on chip 0."""
    out: dict[str, set] = {}
    for name, _, _, mod in tr.devices[0].ops:
        if tracing.KERNEL in name:
            out.setdefault(mod, set()).add(name.partition(" = ")[0])
    return {m: sorted(v)[:5] for m, v in out.items()}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, a.workload)
    require_tpu(cell.chips)
    enable_compile_cache(ROOT)
    m = Dims.from_config(cell.config)
    sched, _, _ = serving.build(cell, a.seed)
    serving.warm_up(sched, cell.traffic, m.vocab, a.seed)
    reqs = traffic.build(cell.traffic, a.seed, a.seconds, m.vocab)
    setup_s = time.perf_counter() - T0

    win = phases.PhaseWindow(sched, cell.traffic, reqs, a.seconds)
    tmp = tempfile.mkdtemp(prefix="chipbench-phases-")
    win.run(trace_dir=tmp)
    tr = tracing.read(tmp, 1)
    spans = phases.read_spans(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    full = phases.with_spans(tr, spans)
    lo, hi = win.t_open, win.t_close
    stamps = win.stamps()
    n_steps = len(tr.steps)
    idle = phases.idle_by_span(full)
    stamp = stamp_check(win)
    print(json.dumps({"first_token_stamp_check": stamp}), file=sys.stderr, flush=True)
    out = {
        "workload": a.workload, "seed": a.seed, "setup_s": setup_s,
        "host_us_per_step": phases.host_us_per_step(win.in_window(win.host)),
        "admit_wait_p90_s": phases.admit_wait_p90_s(stamps, lo, hi),
        "prefill_p90_s": phases.prefill_p90_s(stamps, lo, hi),
        "ttft_p90_s": float(np.percentile(win.ttft_s(), 90)) if win.ttft_s() else None,
        "ttft_split": phases.ttft_split(stamps, lo, hi),
        "trace_steps": n_steps, "traced_steps": sum(s.traced for s in win.steps),
        "spans_per_step": sum(n.startswith(phases.PREFIX) for n, _, _ in spans) / max(n_steps, 1),
        "idle_ms_per_step": {k: 1e3 * v / max(n_steps, 1)
                             for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "breakdown": tracing.breakdown(full),
        "kernel_ops": kernel_ops(tr),
        "step_times": step_times(win),
        "span_cost_us": span_cost_us(sched),
        "slowest_steps": [
            {"s": st.t1 - st.t0, "at": st.t0 - lo, "chunk": st.chunk, "phases": p, "host_s": h}
            for st, p, h in sorted(zip(win.steps, win.phases, win.host),
                                   key=lambda sp: sp[0].t0 - sp[0].t1)[:3]
        ],
        "first_token_stamp_check": stamp,
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
