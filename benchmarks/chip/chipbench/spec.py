"""What a cell is: BENCHMARK.json plus the data files it names.

Every configuration, traffic mix, per-layer metric and correctness limit
lives in a file of its own, found by name:

    configs/<config>.json    the model as run (published keys, plus the cut)
    traffic/<mix>.json       the generator's parameters and the scheduler
    metrics/<metric>.py      one reader, ``read(run) -> float | None``
    checks/<cell>.json       the limits that decide ``correct``
    reference/<family>.py    the plain float32 forward pass

so a later change adds a cell, a mix or a metric by adding files and
entries, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    check: dict[str, Any]
    end_to_end: tuple[dict[str, Any], ...]
    per_layer: tuple[dict[str, Any], ...]


def _json(path: Path) -> dict[str, Any]:
    return json.loads(path.read_text())


def load_cell(root: Path, name: str, *, here: Path = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its data files."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    (conf,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    e2e = tuple(m for m in bench["end_to_end"] if name in m.get("workloads", [name]))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    )
    return Cell(
        name=name,
        config_name=w["config"],
        chips=int(w["chips"]),
        config=_json(root / conf["file"]),
        traffic=_json(here / "traffic" / f"{w['traffic']}.json"),
        check=_json(here / "checks" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def metric_reader(name: str, *, here: Path = HERE) -> Callable[[Any], float | None]:
    """``read`` of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(family: str, *, here: Path = HERE):
    """The plain float32 forward pass ``reference/<family>.py``."""
    path = here / "reference" / f"{family}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_reference_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
