"""The serving program's own step spans and request stamps, read beside the
harness's records.

``Scheduler.step`` opens a ``serve.*`` profiler span for each host phase of
a step (``serve.admit``, ``serve.chunk``, ``serve.wait.first_token``,
``serve.pages``, ``serve.decode``, ``serve.wait.decode``, ``serve.emit``,
all inside ``serve.step``), adds each span's wall time to
``Scheduler.phase_time_s``, and counts the step less its blocks on the
device in ``Scheduler.host_time_s``; a request carries ``t_submit``,
``t_admit`` and ``t_first_token``. This module turns them into per-step
phase and host times, the split of the time to first token, and idle gaps
named by the phase the host was in. A program without the spans gives
empty phases, no host times and no spans; ``host_us_per_step`` then
returns None.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from chipbench import serving
from chipbench.trace import Trace

PREFIX = "serve."
STEP = "serve.step"


def read_spans(log_dir: str) -> list[tuple[str, float, float]]:
    """Every host event of the trace in ``log_dir`` whose name, cut at the
    first ``#`` (where the profiler may append metadata), starts with
    ``serve.``: (name, start, end) in ns, in order of start."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#")[0]
                    if name.startswith(PREFIX):
                        spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda x: x[1])


def with_spans(tr: Trace, spans: list[tuple[str, float, float]]) -> Trace:
    """``tr`` with the program's spans among its host spans and its steps
    unchanged, so that ``Trace.host_label`` (the innermost span holding a
    time) and ``trace.breakdown`` name a gap by the program's phase."""
    return Trace(steps=tr.steps, spans=sorted(tr.spans + spans, key=lambda x: x[1]),
                 devices=tr.devices)


def idle_by_span(tr: Trace, dev: int = 0) -> dict[str, float]:
    """Idle seconds of chip ``dev`` inside the harness's step spans, each
    stretch of a gap given to the innermost host span it lies in."""
    out: dict[str, float] = {}
    for lo, hi in tr.steps:
        for a, b in tr.idle_gaps(dev, lo, hi):
            cuts = sorted({a, b} | {t for _, s, e in tr.spans for t in (s, e) if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                lab = tr.host_label((x + y) / 2)
                out[lab] = out.get(lab, 0.0) + (y - x) * 1e-9
    return out


@dataclass(frozen=True)
class Stamps:
    """One request's times on the host clock (0.0 where not yet reached):
    when it was due, the program's submit, admit and first-token stamps,
    and the harness's first-token stamp (when the step that emitted the
    token returned)."""

    due: float
    submit: float
    admit: float
    first: float
    seen: float


class PhaseWindow(serving.Window):
    """The harness's window, keeping each step's share of the program's
    ``phase_time_s`` and ``host_time_s`` beside its record (``phases[i]``
    and ``host[i]`` for ``steps[i]``; ``host[i]`` is None for a program
    without the counter)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.phases: list[dict[str, float]] = []
        self.host: list[float | None] = []

    def _step(self, inflight):
        before = dict(getattr(self.sched, "phase_time_s", {}))
        host0 = getattr(self.sched, "host_time_s", None)
        done = super()._step(inflight)
        after = getattr(self.sched, "phase_time_s", {})
        self.phases.append({k: v - before.get(k, 0.0) for k, v in after.items()
                            if v != before.get(k, 0.0)})
        self.host.append(None if host0 is None else self.sched.host_time_s - host0)
        return done

    def in_window(self, xs: list) -> list:
        """The entries of ``xs`` (one per step) of the steps inside the window."""
        return [x for s, x in zip(self.steps, xs)
                if s.t0 >= self.t_open and s.t1 <= self.t_close]

    def stamps(self) -> list[Stamps]:
        return [
            Stamps(tr.due, tr.rs.t_submit, tr.rs.t_admit, tr.rs.t_first_token,
                   tr.stamps[0] if tr.stamps else 0.0)
            for tr in self.tracks
        ]


def host_us_per_step(host: list[float | None]) -> float | None:
    """Mean of the steps' ``host_time_s`` deltas, in microseconds."""
    h = [x for x in host if x is not None]
    return 1e6 * float(np.mean(h)) if h else None


def _due(reqs: list[Stamps], lo: float, hi: float) -> list[Stamps]:
    return [r for r in reqs if lo <= r.due < hi]


def admit_wait_p90_s(reqs: list[Stamps], lo: float, hi: float) -> float | None:
    """p90 of admit − submit over the requests due in [lo, hi) and
    admitted by ``hi``."""
    w = [r.admit - r.submit for r in _due(reqs, lo, hi) if 0 < r.admit <= hi]
    return float(np.percentile(w, 90)) if w else None


def prefill_p90_s(reqs: list[Stamps], lo: float, hi: float) -> float | None:
    """p90 of first token − admit over the requests due in [lo, hi) with a
    first token by ``hi``."""
    w = [r.first - r.admit for r in _due(reqs, lo, hi) if 0 < r.first <= hi]
    return float(np.percentile(w, 90)) if w else None


def ttft_split(reqs: list[Stamps], lo: float, hi: float) -> dict[str, dict[str, float]] | None:
    """The time to first token of the requests due in [lo, hi) that saw a
    first token by ``hi``, cut into generator lateness (due → submit),
    admit wait (submit → admit), prefill (admit → the program's first-token
    stamp) and stamp delay (→ the harness's stamp): each part's mean over
    all of them and over the tail (those at or above the p90 of the whole)."""
    rs = [r for r in _due(reqs, lo, hi) if 0 < r.first and 0 < r.seen <= hi]
    if not rs:
        return None
    parts = np.array([[r.submit - r.due, r.admit - r.submit, r.first - r.admit,
                       r.seen - r.first] for r in rs])
    ttft = parts.sum(axis=1)
    tail = ttft >= np.percentile(ttft, 90)
    names = ("late_s", "admit_wait_s", "prefill_s", "stamp_delay_s")
    return {
        "all": dict(zip(names, parts.mean(axis=0).tolist()), ttft_s=float(ttft.mean()), n=len(rs)),
        "tail": dict(zip(names, parts[tail].mean(axis=0).tolist()),
                     ttft_s=float(ttft[tail].mean()), n=int(tail.sum())),
    }
