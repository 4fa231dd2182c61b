"""The arithmetic behind the per-layer metrics, shared by the one-line
readers in ``metrics/``. Each takes a :class:`Run` and returns a number,
or None where the run holds nothing to read (no step of that kind, no
trace); a share of a roofline or a peak is never reported as 0 for lack
of data."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import counts
from chipbench.device import Peaks
from chipbench.serving import StepRec
from chipbench.trace import Trace
from chipbench.weights import Dims

DECODE, CHUNK = "_decode_fn", "_chunk_fn"


@dataclass
class Run:
    steps: list[StepRec]  # the measured window's steps
    traced: list[StepRec]  # steps inside the trace, in order
    trace: Trace | None
    dims: Dims
    shards: int  # chips the model axis splits heads over
    chips: int
    peaks: Peaks

    @property
    def aligned(self) -> bool:
        """The trace's step spans are the traced steps, one for one."""
        return self.trace is not None and len(self.trace.steps) == len(self.traced) > 0


def plan_us_per_step(run: Run) -> float | None:
    if not run.steps:
        return None
    return 1e6 * sum(s.plan_s for s in run.steps) / len(run.steps)


def kv_reserved_over_used(run: Run) -> float | None:
    r = [s.pages_reserved / s.pages_in_use for s in run.steps if s.pages_in_use]
    return float(np.mean(r)) if r else None


def _step_flops(run: Run, s: StepRec) -> float:
    f = counts.decode_flops(run.dims, s.decode_ctx)
    if s.chunk is not None:
        f += counts.chunk_flops(run.dims, *s.chunk)
    return f


def mfu(run: Run, chunk_only: bool = False) -> float | None:
    steps = [s for s in run.steps if s.chunk is not None or not chunk_only]
    wall = sum(s.t1 - s.t0 for s in steps)
    if not steps or wall <= 0:
        return None
    return 100.0 * sum(_step_flops(run, s) for s in steps) / (wall * run.chips * run.peaks.bf16_flops)


def program_ms(run: Run, program: str) -> float | None:
    if run.trace is None:
        return None
    calls = run.trace.calls(0, program)
    return 1e-6 * float(np.mean([e - s for _, s, e in calls])) if calls else None


def roofline(run: Run, program: str) -> float | None:
    """Sum of the kernel's least times over the traced calls, over the sum
    of its device time in them, on chip 0."""
    if not run.aligned:
        return None
    kern = run.trace.kernel_ns_by_step(0, program)
    least = spent = 0.0
    for j, ns in kern.items():
        s = run.traced[j]
        if program == DECODE and s.decode_ctx:
            least += run.dims.layers * counts.paged_decode_least_s(
                run.dims, s.decode_ctx, run.shards, run.peaks)
        elif program == CHUNK and s.chunk is not None:
            least += run.dims.layers * counts.paged_chunk_least_s(
                run.dims, *s.chunk, run.shards, run.peaks)
        else:
            continue
        spent += ns * 1e-9
    return 100.0 * least / spent if spent > 0 else None


def idle_in_step_share(run: Run) -> float | None:
    if run.trace is None or not run.trace.steps:
        return None
    tr = run.trace
    span = sum(e - s for s, e in tr.steps)
    busy = [sum(tr.busy_ns(d, s, e) for s, e in tr.steps) for d in range(len(tr.devices))]
    return 100.0 * (1.0 - float(np.mean(busy)) / span) if span > 0 else None

