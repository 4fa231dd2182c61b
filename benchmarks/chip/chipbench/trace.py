"""The profiler trace of a traced run, reduced to what the readers need.

A few seconds in the middle of the window are traced. The trace holds the
harness's own host spans (``bench.step`` around each ``Scheduler.step``,
``bench.generate`` around the generator's work) and, per TPU, the
``XLA Modules`` line (one event per program call, named ``jit_<fn>(...)``)
and the ``XLA Ops`` line (one event per op, nested inside loop ops). Host
and device events share one clock. A Pallas kernel is the op whose name
carries ``custom_call_target="tpu_custom_call"``; which kernel it is
follows from the program it runs in (the decode program holds only the
paged decode kernel, the chunk program only the paged chunk kernel).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

STEP = "bench.step"
GENERATE = "bench.generate"
KERNEL = 'custom_call_target="tpu_custom_call"'


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the host spans are the harness's own
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


@dataclass
class Device:
    modules: list[tuple[str, float, float]] = field(default_factory=list)  # name, start, end (ns)
    ops: list[tuple[str, float, float, str]] = field(default_factory=list)  # name, start, end, module


@dataclass
class Trace:
    steps: list[tuple[float, float]]  # host bench.step spans, in order
    spans: list[tuple[str, float, float]]  # every harness span
    devices: list[Device]

    # -- time sets -----------------------------------------------------------
    @property
    def window(self) -> tuple[float, float]:
        return self.steps[0][0], self.steps[-1][1]

    def busy_ns(self, dev: int, lo: float, hi: float) -> float:
        return sum(e - s for s, e in _union(self.devices[dev].ops, lo, hi))

    def idle_gaps(self, dev: int, lo: float, hi: float) -> list[tuple[float, float]]:
        gaps, t = [], lo
        for s, e in _union(self.devices[dev].ops, lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_label(self, t: float) -> str:
        inside = [n for n, s, e in self.spans if s <= t <= e]
        return inside[-1] if inside else "host.other"

    # -- programs and kernels ------------------------------------------------
    def calls(self, dev: int, program: str) -> list[tuple[str, float, float]]:
        return [m for m in self.devices[dev].modules if _fn(m[0]) == program]

    def step_of(self, t_start: float, t_end: float) -> int | None:
        """The host step that ran a device event: the span holding its end
        (a step blocks on its decode), else the last span begun before it."""
        best = None
        for j, (s, e) in enumerate(self.steps):
            if s <= t_end <= e:
                return j
            if s <= t_start:
                best = j
        return best

    def kernel_ns_by_step(self, dev: int, program: str) -> dict[int, float]:
        """Kernel device time inside each call of ``program``, by host step."""
        out: dict[int, float] = {}
        for name, s, e, mod in self.devices[dev].ops:
            if mod == program and KERNEL in name:
                j = self.step_of(s, e)
                if j is not None:
                    out[j] = out.get(j, 0.0) + (e - s)
        return out


def _fn(module_name: str) -> str:
    """``jit__decode_fn(123)`` -> ``_decode_fn``."""
    base = module_name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def _union(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    iv = sorted((max(o[1], lo), min(o[2], hi)) for o in ops if o[2] > lo and o[1] < hi)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _leaf_ops(ops) -> list[tuple[str, float, float, str]]:
    """Ops that hold no other op (a loop op spans its body's ops)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    leaves = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= o[2]:
            leaves.append(o)
    return leaves


def read(log_dir: str, n_devices: int) -> Trace:
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    spans: list[tuple[str, float, float]] = []
    devices = [Device() for _ in range(n_devices)]
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (STEP, GENERATE):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            i = int(plane.name.rsplit(":", 1)[1])
            if i >= n_devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[i].modules = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                    ]
                elif line.name == "XLA Ops":
                    ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
                    devices[i].ops = ops
    for dev in devices:
        mods = sorted(dev.modules, key=lambda m: m[1])
        tagged, k = [], 0
        for name, s, e in sorted(dev.ops, key=lambda o: o[1]):
            while k < len(mods) and mods[k][2] < s:
                k += 1
            mod = _fn(mods[k][0]) if k < len(mods) and mods[k][1] <= s else ""
            tagged.append((name, s, e, mod))
        dev.ops = tagged
    spans.sort(key=lambda x: x[1])
    steps = [(s, e) for n, s, e in spans if n == STEP]
    return Trace(steps=steps, spans=spans, devices=devices)


def op_label(name: str, module: str) -> str:
    """``%fusion.3 = bf16[32,128]{...} fusion(...)`` -> ``_decode_fn %fusion.3 bf16[32,128] fusion``."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0]
    kind = rest.split("(")[0].split(" ")[-1] if "(" in rest else ""
    if KERNEL in name:
        kind = "pallas"
    return f"{module} {head} {shape} {kind}".strip()


def breakdown(tr: Trace, dev: int = 0, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps, each
    gap named by the harness span the host was in."""
    lo, hi = tr.window
    tot: dict[str, float] = {}
    for name, s, e, mod in _leaf_ops(tr.devices[dev].ops):
        if e <= lo or s >= hi:
            continue
        lab = op_label(name, mod)
        tot[lab] = tot.get(lab, 0.0) + (min(e, hi) - max(s, lo))
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(tr.idle_gaps(dev, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[k, v * 1e-9] for k, v in ops],
        "idle_gaps": [[tr.host_label((s + e) / 2), (e - s) * 1e-9] for s, e in gaps],
    }
