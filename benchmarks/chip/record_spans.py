"""Record the small chip trace with the program's step spans that tests/bench
checks the span reading on.

    python3 benchmarks/chip/record_spans.py <out_dir>

As ``record_trace.py`` (the smoke-width cell on one TPU chip, four clients
in a closed loop, a tenth of a second of trace), but the trace also keeps
the program's ``serve.*`` host spans. Writes ``trace.xplane.pb`` and the
harness's records of the traced steps (``steps.json``) to ``out_dir``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src"), str(ROOT / "tests" / "bench")]

import bench_smoke  # noqa: E402
from chipbench import phases, serving, traffic  # noqa: E402
from chipbench import trace as tracing  # noqa: E402
from chipbench.device import require_tpu  # noqa: E402
from chipbench.weights import Dims  # noqa: E402
from record_trace import _xplane_pb2  # noqa: E402


def _kept(name: str, host: bool) -> bool:
    return not host or name in (tracing.STEP, tracing.GENERATE) or name.split("#")[0].startswith(
        phases.PREFIX)


def shrink(path: Path) -> None:
    """Rewrite the trace at ``path`` with only the harness's and the
    program's host spans and each TPU's ``XLA Modules`` and ``XLA Ops``
    lines, and no stats."""
    pb = _xplane_pb2()
    space = pb.XSpace.FromString(path.read_bytes())
    out = pb.XSpace()
    for plane in space.planes:
        host = plane.name.startswith("/host:")
        if not host and not plane.name.startswith("/device:TPU:"):
            continue
        keep = out.planes.add(id=plane.id, name=plane.name)
        names = {k: v.name for k, v in plane.event_metadata.items()}
        used = set()
        for line in plane.lines:
            if not host and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = [e for e in line.events if _kept(names.get(e.metadata_id, ""), host)]
            if not events:
                continue
            new = keep.lines.add(id=line.id, display_id=line.display_id, name=line.name,
                                 timestamp_ns=line.timestamp_ns, duration_ps=line.duration_ps)
            for e in events:
                new.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                               duration_ps=e.duration_ps)
                used.add(e.metadata_id)
        for k in used:
            md = plane.event_metadata[k]
            keep.event_metadata[k].CopyFrom(pb.XEventMetadata(id=md.id, name=md.name))
    path.write_bytes(out.SerializeToString())


def main(out: Path) -> int:
    require_tpu(1)
    cell = bench_smoke.cell(**bench_smoke.TRACE_CONFIG)
    m = Dims.from_config(cell.config)
    sched, _, _ = serving.build(cell, 1)
    serving.warm_up(sched, cell.traffic, m.vocab, 1)
    mix = dict(cell.traffic, loop="closed", clients=4)
    win = serving.Window(sched, mix, traffic.build(mix, 1, 2.0, m.vocab), 2.0)
    tmp = tempfile.mkdtemp()
    win.run(trace_dir=tmp, trace_s=0.1)
    out.mkdir(parents=True, exist_ok=True)
    (src,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    shutil.copy(src, out / "trace.xplane.pb")
    shutil.rmtree(tmp)
    shrink(out / "trace.xplane.pb")
    steps = [dataclasses.asdict(s) for s in win.steps if s.traced]
    (out / "steps.json").write_text(json.dumps(steps))
    print(json.dumps({"traced_steps": len(steps), "bytes": (out / "trace.xplane.pb").stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
