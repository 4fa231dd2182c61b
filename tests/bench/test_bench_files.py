"""The chip benchmark's data files: every configuration, traffic mix,
metric reader, correctness limit and reference loads by name, and
BENCHMARK.json keeps to its schema. Nothing here needs a chip."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(HERE))

from chipbench import serving, spec  # noqa: E402
from chipbench.weights import Dims  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _stems(sub, suffix):
    return sorted(p.name[: -len(suffix)] for p in (HERE / sub).glob(f"*{suffix}"))


@pytest.mark.parametrize("name", _stems("configs", ".json"))
def test_config_loads(name):
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    m = Dims.from_config(c)
    cfg = serving.model_config(c, name)
    assert cfg.resolved_head_dim == m.head_dim and cfg.n_layers == m.layers
    assert serving.n_pages(c, {"page_size": 16, "cache_len": 2560, "n_slots": 64}) > 0
    spec.reference_module(c["reference"])
    assert name in {x["name"] for x in BENCH["configs"]}


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_traffic_loads(name):
    from chipbench import traffic

    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    reqs = traffic.build(mix, 2**31 + 11, 5.0, 1000)
    assert reqs and all(
        mix["prompt_len"]["min"] <= len(r.prompt) <= mix["prompt_len"]["max"] for r in reqs
    )
    assert name in {w["traffic"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("name", _stems("metrics", ".py"))
def test_metric_reader_loads(name):
    assert callable(spec.metric_reader(name))
    assert name in {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads(cell):
    c = spec.load_cell(ROOT, cell)
    assert c.check["max_logit_gap"] > 0
    names = {e["name"] for e in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for p in c.per_layer:
        assert p["moves"] in names


def test_benchmark_json_schema():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"])) == len(
        BENCH["end_to_end"] + BENCH["per_layer"]
    )
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert set(c["reduced"]) == set(json.loads((ROOT / c["file"]).read_text())["reduced"])
    cells = BENCH["workloads"]
    assert {w["config"] for w in cells} == set(configs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in layer for layer in layers)
