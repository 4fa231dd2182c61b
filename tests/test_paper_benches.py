"""benchmarks/run.py: each benchmark of Memento's host-side claims runs at
its smoke size and prints the rows it should, with the facts in ``derived``
that are not timings. No speedup is asserted: timings vary with the host."""
import importlib
import re
import tempfile
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture
def bench(monkeypatch, tmp_path):
    # benchmarks.run must be importable by name: B12's spawned workers
    # import their target from it.
    monkeypatch.syspath_prepend(str(ROOT))
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    mod = importlib.import_module("benchmarks.run")
    monkeypatch.setattr(mod, "_FAILED", [])
    return mod


def _rows(out: str) -> dict[str, str]:
    """name -> derived, for the CSV rows a benchmark printed."""
    return {
        name: derived
        for name, _, derived in (
            ln.split(",", 2) for ln in out.splitlines() if re.match(r"B\d+_", ln)
        )
    }


def _b1(b, d):
    b.bench_matrix_expansion(smoke=True)
    return {
        "B1_matrix_expand_216_tasks": "tasks/s incl hashing",
        "B1_matrix_algebra_48_tasks": "-> 48/64 tasks",
    }


def _b2(b, d):
    b.bench_parallel_speedup(smoke=True)
    return {"B2_sequential_4x20ms": "", "B2_parallel_4workers": "speedup="}


def _b3(b, d):
    b.bench_cache_speedup(tmpdir=str(d / "cache"), smoke=True)
    return {"B3_cold_run_4x20ms": "", "B3_cached_rerun": "speedup="}


def _b4(b, d):
    b.bench_checkpoint_overhead(tmpdir=str(d / "ckpt"), smoke=True)
    return {
        "B4_ckpt_save_0.0MB_sync": "",
        "B4_ckpt_save_0.0MB_async_enqueue": "hidden=",
    }


def _b5(b, d):
    b.bench_failure_isolation()
    return {"B5_half_failing_run": "ok=4 failed=4 isolated=True"}


def _b12(b, d):
    b.bench_distributed(smoke=True)
    return {
        "B12_distributed_1proc_8tasks": "speedup=1.00x",
        "B12_distributed_2proc_8tasks": "tasks/s",
        "B12_distributed_killrecovery_8tasks": "complete=True",
    }


@pytest.mark.parametrize(
    "run", [_b1, _b2, _b3, _b4, _b5, _b12],
    ids=["B1", "B2", "B3", "B4", "B5", "B12"],
)
def test_paper_bench_smoke(run, bench, tmp_path, capsys):
    want = run(bench, tmp_path)
    rows = _rows(capsys.readouterr().out)
    assert list(rows) == list(want)
    for name, fact in want.items():
        assert fact in rows[name], (name, rows[name])
    assert bench._FAILED == []
