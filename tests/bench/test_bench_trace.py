"""The trace reduction on a small trace recorded on a TPU v5e chip
(``data/trace``: the smoke-width cell with head_dim 128, two layers, bf16,
four clients in a closed loop, a tenth of a second of trace kept to the
lines the reduction reads; ``benchmarks/chip/record_trace.py`` records it)."""
import json
from pathlib import Path

import pytest

import bench_smoke
from chipbench import spec, trace
from chipbench.device import PEAKS
from chipbench.readers import CHUNK, DECODE, Run, roofline
from chipbench.serving import StepRec
from chipbench.weights import Dims

DATA = Path(__file__).resolve().parent / "data" / "trace"


@pytest.fixture(scope="module")
def run():
    tr = trace.read(str(DATA), 1)
    steps = [StepRec(**{**s, "decode_ctx": tuple(s["decode_ctx"]),
                        "chunk": None if s["chunk"] is None else tuple(s["chunk"])})
             for s in json.loads((DATA / "steps.json").read_text())]
    dims = Dims.from_config(bench_smoke.cell(**bench_smoke.TRACE_CONFIG).config)
    return Run(steps=steps, traced=steps, trace=tr, dims=dims, shards=1, chips=1,
               peaks=PEAKS["TPU v5 lite"])


def test_host_spans_line_up_with_the_steps(run):
    assert run.aligned
    assert all(s < e for s, e in run.trace.steps)
    assert run.trace.window[1] > run.trace.window[0]


def test_device_time(run):
    lo, hi = run.trace.window
    busy = run.trace.busy_ns(0, lo, hi)
    assert 0 < busy <= hi - lo
    gaps = run.trace.idle_gaps(0, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)


def test_programs_and_kernels(run):
    decode = run.trace.calls(0, DECODE)
    assert decode and all(e > s for _, s, e in decode)
    kern = run.trace.kernel_ns_by_step(0, DECODE)
    assert kern and all(0 < ns for ns in kern.values())
    share = roofline(run, DECODE)
    assert 0 < share <= 100


def test_every_reader_reads_the_trace(run):
    for name in ("decode_ms.chat", "idle_in_step_share.chat", "plan_us_per_step.chat",
                 "kv_reserved_over_used.chat", "mfu.chat", "paged_decode_roofline.chat"):
        v = spec.metric_reader(name)(run)
        assert v is not None and v > 0, name
    chunk = roofline(run, CHUNK)
    assert chunk is None or 0 < chunk <= 100


def test_breakdown(run):
    b = trace.breakdown(run.trace)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"] + b["idle_gaps"])
    assert {n for n, _ in b["idle_gaps"]} <= {trace.STEP, trace.GENERATE, "host.other"}
