"""The program's step spans and request stamps as the harness reads them:
on a small chip trace with the spans (``data/trace_spans``, recorded by
``benchmarks/chip/record_spans.py``), on a window rehearsed on the CPU at
smoke widths, and on synthetic records, where each reader returns None
when there is nothing to read."""
import json
import time
from pathlib import Path

import numpy as np
import pytest

import bench_smoke
import phase_report
from chipbench import phases, serving, trace, traffic
from chipbench.phases import Stamps
from chipbench.weights import Dims

DATA = Path(__file__).resolve().parent / "data" / "trace_spans"
SEED = 2**31 + 7


# -- the recorded chip trace --------------------------------------------------
@pytest.fixture(scope="module")
def chip():
    tr = trace.read(str(DATA), 1)
    return tr, phases.read_spans(str(DATA)), json.loads((DATA / "steps.json").read_text())


def test_read_keeps_steps_one_for_one(chip):
    tr, spans, steps = chip
    assert len(tr.steps) == len(steps) > 0
    assert {n for n, _, _ in tr.spans} <= {trace.STEP, trace.GENERATE}
    assert sum(n == phases.STEP for n, _, _ in spans) == len(tr.steps)


def test_spans_nest_in_the_steps(chip):
    tr, spans, _ = chip
    assert {"serve.step", "serve.admit", "serve.decode", "serve.wait.decode",
            "serve.emit"} <= {n for n, _, _ in spans}
    for _, s, e in spans:
        assert any(s0 <= s <= e <= e0 for s0, e0 in tr.steps)


def test_breakdown_names_gaps_by_program_spans(chip):
    tr, spans, _ = chip
    full = phases.with_spans(tr, spans)
    gaps = trace.breakdown(full)["idle_gaps"]
    assert gaps and all(n.startswith(phases.PREFIX) for n, _ in gaps)
    idle = phases.idle_by_span(full)
    in_steps = sum(e - s for a, b in tr.steps for s, e in tr.idle_gaps(0, a, b)) * 1e-9
    assert sum(idle.values()) == pytest.approx(in_steps)
    # only the harness's own bookkeeping around ``Scheduler.step`` is left
    # to ``bench.step``
    assert set(idle) <= {n for n, _, _ in spans} | {trace.STEP}
    assert idle.get(trace.STEP, 0.0) < 0.01 * sum(idle.values())


# -- a window on the CPU ------------------------------------------------------
@pytest.fixture(scope="module")
def window(tmp_path_factory):
    cell = bench_smoke.cell()
    m = Dims.from_config(cell.config)
    sched, _, _ = serving.build(cell, SEED)
    serving.warm_up(sched, cell.traffic, m.vocab, SEED)
    win = phases.PhaseWindow(
        sched, cell.traffic, traffic.build(cell.traffic, SEED, 2.0, m.vocab), 2.0)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    win.run(trace_dir=log_dir, trace_s=0.5)
    return win, trace.read(log_dir, 1), phases.read_spans(log_dir)


def test_window_keeps_each_steps_phases(window):
    win, _, _ = window
    assert len(win.phases) == len(win.host) == len(win.steps) > 0
    for s, p, h in zip(win.steps, win.phases, win.host):
        assert 0 < p[phases.STEP] <= s.t1 - s.t0
        assert 0 <= h <= p[phases.STEP]
    assert phases.host_us_per_step(win.in_window(win.host)) > 0


def test_window_stamps_and_their_readers(window):
    win, _, _ = window
    lo, hi = win.t_open, win.t_close
    stamps = win.stamps()
    for r in stamps:
        if r.first:
            assert r.due <= r.submit <= r.admit <= r.first <= r.seen
    assert phases.admit_wait_p90_s(stamps, lo, hi) >= 0
    assert phases.prefill_p90_s(stamps, lo, hi) > 0
    split = phases.ttft_split(stamps, lo, hi)
    assert split["all"]["n"] >= split["tail"]["n"] > 0
    assert sum(split["all"][k] for k in ("late_s", "admit_wait_s", "prefill_s",
                                         "stamp_delay_s")) == pytest.approx(split["all"]["ttft_s"])
    check = phase_report.stamp_check(win)
    assert check["n"] > 0 and check["outside"] == 0


def test_window_trace_names_gaps_by_program_spans(window):
    win, tr, spans = window
    assert len(tr.steps) == sum(s.traced for s in win.steps) > 0
    assert sum(n == phases.STEP for n, _, _ in spans) == len(tr.steps)
    gaps = trace.breakdown(phases.with_spans(tr, spans))["idle_gaps"]
    assert gaps and all(n.startswith(phases.PREFIX) for n, _ in gaps)
    times = phase_report.step_times(win)
    assert sum(v["n"] for v in times.values()) == len(win.window_steps())
    kept = dict(win.sched.phase_time_s)
    assert phase_report.span_cost_us(win.sched, n=100) > 0
    assert win.sched.phase_time_s == kept


# -- synthetic records ----------------------------------------------------------
def test_host_us_per_step():
    assert phases.host_us_per_step([0.003, 0.002]) == pytest.approx(2500.0)
    assert phases.host_us_per_step([]) is None
    assert phases.host_us_per_step([None, None]) is None  # a program without the counter


def test_request_readers():
    t = time.perf_counter()
    lo, hi = t, t + 10
    reqs = [
        Stamps(due=t + 1, submit=t + 1.1, admit=t + 1.5, first=t + 2.5, seen=t + 2.6),
        Stamps(due=t + 2, submit=t + 2.0, admit=t + 4.0, first=t + 5.0, seen=t + 5.1),
        Stamps(due=t + 3, submit=t + 3.0, admit=t + 9.5, first=0.0, seen=0.0),  # no token
        Stamps(due=t + 4, submit=t + 4.0, admit=0.0, first=0.0, seen=0.0),  # queued
        Stamps(due=t - 1, submit=t - 1, admit=t - 0.5, first=t, seen=t + 0.1),  # ramp
    ]
    assert phases.admit_wait_p90_s(reqs, lo, hi) == pytest.approx(
        float(np.percentile([0.4, 2.0, 6.5], 90)))
    assert phases.prefill_p90_s(reqs, lo, hi) == pytest.approx(
        float(np.percentile([1.0, 1.0], 90)))
    split = phases.ttft_split(reqs, lo, hi)
    assert split["all"]["n"] == 2 and split["all"]["ttft_s"] == pytest.approx(2.35)
    assert split["tail"]["n"] == 1 and split["tail"]["admit_wait_s"] == pytest.approx(2.0)


@pytest.mark.parametrize("reqs", [
    [],
    [Stamps(due=5.0, submit=5.0, admit=0.0, first=0.0, seen=0.0)],  # nothing admitted
    [Stamps(due=-5.0, submit=-5.0, admit=-4.0, first=-3.0, seen=-3.0)],  # due before the window
], ids=["empty", "unadmitted", "outside"])
def test_request_readers_with_nothing_to_read(reqs):
    assert phases.admit_wait_p90_s(reqs, 0.0, 10.0) is None
    assert phases.prefill_p90_s(reqs, 0.0, 10.0) is None
    assert phases.ttft_split(reqs, 0.0, 10.0) is None
