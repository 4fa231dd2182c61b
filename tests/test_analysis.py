"""repro.analysis: metric extraction, comparison tables, the event-fed
dashboard, and the CLI."""
import json
import os
import subprocess
import sys
import urllib.request
from pathlib import Path

import pytest

from repro.analysis import (
    AnalysisNotificationProvider,
    Dashboard,
    Examiner,
    MetricFrame,
    MetricRecord,
    MetricSpec,
    compare,
    compare_frames,
)
from repro.core import ConfigMatrix, FileQueue, Memento, RunnerConfig
from repro.core.notifications import Event

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([SRC, env.get("PYTHONPATH", "")])
    return env


def _cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=_env(), cwd=cwd,
    )


def _sweep(ctx):
    return {
        "tokens_per_s": 100.0 * ctx["n"] + ctx["seed"],
        "wall_s": 0.5,
        "itl_p50_s": 0.004,
    }


def _run_sweep():
    return Memento(
        _sweep,
        runner_config=RunnerConfig(max_workers=2, enable_speculation=False),
    ).run({"parameters": {"n": [1, 2], "seed": [0, 10]}})


class TestMetrics:
    def test_examine_results_params_and_host_ride_along(self):
        res = _run_sweep()
        frame = Examiner(["tokens_per_s", "wall_s"]).examine_results(res)
        assert len(frame) == 8  # 4 tasks x 2 metrics
        assert set(frame.metrics()) == {"tokens_per_s", "wall_s"}
        r = frame.where(metric="tokens_per_s", n=2, seed=10).records[0]
        assert r.value == 210.0
        assert r.host and r.source == "result"

    def test_spec_extract_and_failed_tasks_skipped(self):
        def sometimes(ctx):
            if ctx["i"] == 1:
                raise RuntimeError("boom")
            return {"itl_p50_s": 0.004 * (ctx["i"] + 1)}

        res = Memento(
            sometimes,
            runner_config=RunnerConfig(max_workers=2, retries=0,
                                       enable_speculation=False),
        ).run({"parameters": {"i": [0, 1, 2]}})
        ex = Examiner([
            MetricSpec("itl_p50_ms", extract=lambda v: v["itl_p50_s"] * 1e3,
                       unit="ms"),
        ])
        frame = ex.examine_results(res)
        assert sorted(frame.values()) == [4.0, 12.0]
        assert all(r.unit == "ms" for r in frame)

    def test_examine_text_regex_num_placeholder(self):
        ex = Examiner({"tok_s": r"({num}) tok/s", "p95_ms": r"p95=({num})ms"})
        frame = ex.examine_text("run A: 42.5 tok/s p95=17ms\nrun B: 99 tok/s")
        assert frame.where(metric="tok_s").values() == [42.5, 99.0]
        assert frame.where(metric="p95_ms").values() == [17.0]

    def test_examine_done_dir(self, tmp_path):
        def f(ctx):
            return ctx["i"]

        eng = Memento(f, workdir=tmp_path / "w")
        eng.run_distributed({"parameters": {"i": [0, 1]}},
                            queue_dir=tmp_path / "q", owner="hostA")
        frame = Examiner(["wall_s", "attempts"]).examine_done_dir(tmp_path / "q")
        assert "failed" in frame.metrics()  # synthetic 0/1 failure metric
        assert set(frame.values("failed")) == {0.0}
        assert all(r.host == "hostA" for r in frame.where(metric="failed"))

    def test_frame_roundtrip_results_csv(self, tmp_path):
        res = _run_sweep()
        path = tmp_path / "r.csv"
        res.to_csv(path)
        frame = MetricFrame.from_results_csv(path)
        assert set(frame.metrics()) == {"wall_s", "tokens_per_s", "itl_p50_s"}
        assert frame.where(metric="tokens_per_s", n=2.0, seed=10.0).values() == [210.0]
        assert frame.param_values("n") == [1.0, 2.0]

    def test_frame_csv_failed_rows_keep_wall_only(self, tmp_path):
        def sometimes(ctx):
            if ctx["i"]:
                raise RuntimeError("boom")
            return {"m": 1.0}

        res = Memento(
            sometimes,
            runner_config=RunnerConfig(retries=0, enable_speculation=False),
        ).run({"parameters": {"i": [0, 1]}})
        path = tmp_path / "r.csv"
        res.to_csv(path)
        frame = MetricFrame.from_results_csv(path)
        assert len(frame.where(metric="m")) == 1
        assert len(frame.where(metric="wall_s")) == 2

    def test_group_and_where_pred(self):
        frame = MetricFrame([
            MetricRecord("m", 1.0, params={"a": 1}, host="h1"),
            MetricRecord("m", 3.0, params={"a": 1}, host="h2"),
            MetricRecord("m", 5.0, params={"a": 2}, host="h1"),
        ])
        assert frame.group(["a"], metric="m") == {(1,): [1.0, 3.0], (2,): [5.0]}
        assert frame.group(["host"]) == {("h1",): [1.0, 5.0], ("h2",): [3.0]}
        assert frame.where(pred=lambda r: r.value > 2).values() == [3.0, 5.0]


class TestTables:
    def _frame(self):
        recs = []
        for a in ("x", "y"):
            for b in (1, 2):
                for rep in range(2):
                    recs.append(MetricRecord(
                        "tok_s", {"x": 10.0, "y": 20.0}[a] * b + rep,
                        params={"arch": a, "slots": b},
                    ))
        return MetricFrame(recs)

    def test_compare_grouped_agg(self):
        t = compare(self._frame(), rows="arch", cols="slots", agg="mean")
        assert t.row_labels == [("x",), ("y",)]
        assert t.col_labels == [1, 2]
        assert t.cells == [[10.5, 20.5], [20.5, 40.5]]

    def test_compare_agg_variants(self):
        t = compare(self._frame(), rows="arch", cols="slots", agg="max")
        assert t.cells[0] == [11.0, 21.0]
        t = compare(self._frame(), rows="arch", cols="slots", agg="count")
        assert t.cells == [[2, 2], [2, 2]]
        t = compare(self._frame(), rows="arch", cols="slots", agg="p95")
        assert t.cells[1][1] == pytest.approx(40.95)

    def test_compare_metrics_as_columns(self):
        frame = MetricFrame([
            MetricRecord("tok_s", 10.0, params={"a": 1}),
            MetricRecord("wall_s", 0.5, params={"a": 1}),
        ])
        t = compare(frame, rows="a")
        assert t.col_labels == ["tok_s", "wall_s"]
        assert t.cells == [[10.0, 0.5]]

    def test_compare_multiple_metrics_with_cols_requires_pick(self):
        frame = MetricFrame([
            MetricRecord("m1", 1.0, params={"a": 1, "b": 1}),
            MetricRecord("m2", 2.0, params={"a": 1, "b": 1}),
        ])
        with pytest.raises(ValueError, match="pass metric="):
            compare(frame, rows="a", cols="b")

    def test_baseline_annotations_in_every_renderer(self):
        t = compare(self._frame(), rows="arch", cols="slots", agg="mean",
                    baseline=1)
        md, csv, txt = t.to_markdown(), t.to_csv(), str(t)
        for out in (md, csv, txt):
            assert "2 (vs 1)" in out
            assert "(1.95x, +95.2%)" in out  # x-row: 10.5 -> 20.5
        assert md.splitlines()[1].startswith("| ---")

    def test_baseline_must_be_a_column(self):
        t = compare(self._frame(), rows="arch", cols="slots")
        t.baseline = 99
        with pytest.raises(ValueError, match="not a column"):
            t.to_markdown()

    def _two_runs(self):
        old = MetricFrame([
            MetricRecord("tok_s", 100.0, params={"benchmark": "B9"}),
            MetricRecord("tok_s", 8.0, params={"benchmark": "B10"}),
        ])
        new = MetricFrame([
            MetricRecord("tok_s", 50.0, params={"benchmark": "B9"}),
        ])
        return old, new

    def test_compare_frames_cross_run_diff(self):
        old, new = self._two_runs()
        t = compare_frames([("base", old), ("cand", new)], rows="benchmark")
        assert t.col_labels == ["base", "cand"]
        assert t.baseline == "base"
        assert t.cells == [[100.0, 50.0], [8.0, None]]
        md = t.to_markdown()
        assert "cand (vs base)" in md
        assert "(0.50x, -50.0%)" in md  # B9 halved
        # B10 is missing from the candidate run: renders as "-", not dropped
        assert "| B10 | 8 | - |" in md

    def test_compare_frames_empty_run_keeps_column(self):
        old, _ = self._two_runs()
        t = compare_frames([("base", old), ("cand", MetricFrame())],
                           rows="benchmark", metric="tok_s")
        assert t.col_labels == ["base", "cand"]
        assert all(row[1] is None for row in t.cells)

    def test_compare_frames_baseline_override_and_agg(self):
        old, new = self._two_runs()
        t = compare_frames({"base": old, "cand": new}, rows="benchmark",
                           baseline="cand", agg="max")
        assert "base (vs cand)" in t.to_markdown()
        assert "(2.00x, +100.0%)" in t.to_markdown()

    def test_compare_frames_validates_inputs(self):
        old, new = self._two_runs()
        with pytest.raises(ValueError, match="at least two"):
            compare_frames([("only", old)], rows="benchmark")
        with pytest.raises(ValueError, match="distinct"):
            compare_frames([("a", old), ("a", new)], rows="benchmark")

    def test_compare_frames_multiple_metrics_requires_pick(self):
        a = MetricFrame([MetricRecord("m1", 1.0, params={"b": 1}),
                         MetricRecord("m2", 2.0, params={"b": 1})])
        b = MetricFrame([MetricRecord("m1", 3.0, params={"b": 1})])
        with pytest.raises(ValueError, match="pass metric="):
            compare_frames([("a", a), ("b", b)], rows="b")
        t = compare_frames([("a", a), ("b", b)], rows="b", metric="m1")
        assert t.cells == [[1.0, 3.0]]


def _event(kind, t=1000.0, **payload):
    return Event(kind=kind, message="", unix_time=t, payload=payload)


class TestDashboardProvider:
    def _feed(self, prov):
        prov.notify(_event("run_started", t=1000.0, total=4, workers=2))
        prov.notify(_event(
            "task_finished", t=1001.0, key="k1", status="ok",
            params={"i": 0}, host="h1", wall_s=1.0, attempts=1, cached=False,
            metrics={"tokens_per_s": 50.0, "generated_tokens": 64.0,
                     "accept_rate": 0.9},
        ))
        prov.notify(_event(
            "task_failed", t=1002.0, key="k2", status="failed",
            params={"i": 1}, host="h2", wall_s=0.5, attempts=2, cached=False,
            error="RuntimeError: boom", traceback="Traceback ... boom",
        ))
        prov.notify(_event(
            "queue_progress", t=1002.5, total=4, done=2, failed=1,
            claimed_by={"h1": 1}, done_by={"h1": 1, "h2": 1},
            owner="h1", elapsed_s=2.5, eta_s=2.5,
        ))

    def test_aggregates_and_failure_drilldown(self):
        prov = AnalysisNotificationProvider()
        self._feed(prov)
        s = prov.state()
        assert s["total"] == 4 and s["done"] == 2 and s["failed"] == 1
        assert s["queue"]["claimed_by"] == {"h1": 1}
        assert set(s["hosts"]) == {"h1", "h2"}
        assert s["hosts"]["h1"]["tokens_per_s"] == 64.0  # 64 tokens / 1.0s
        assert s["hosts"]["h1"]["metrics"]["accept_rate"] == 0.9
        assert s["serve"]["accept_rate"] == 0.9
        [fail] = s["failures"]
        assert fail["error"] == "RuntimeError: boom"
        assert "boom" in fail["traceback"]
        assert fail["host"] == "h2" and fail["params"] == {"i": 1}
        assert s["eta_s"] is not None and s["eta_s"] >= 0

    def test_journal_write_and_replay(self, tmp_path):
        journal = tmp_path / "events.jsonl"
        prov = AnalysisNotificationProvider(journal_path=journal)
        self._feed(prov)
        lines = journal.read_text().strip().splitlines()
        assert len(lines) == 4
        assert json.loads(lines[0])["kind"] == "run_started"

        fresh = AnalysisNotificationProvider()
        offset = fresh.replay_journal(journal)
        assert offset == len(journal.read_bytes())
        assert fresh.state()["done"] == prov.state()["done"]
        assert fresh.state()["failures"] == prov.state()["failures"]
        # replay does not re-append to a journal
        prov2 = AnalysisNotificationProvider(journal_path=journal)
        prov2.replay_journal(journal)
        assert len(journal.read_text().strip().splitlines()) == 4

    def test_events_since_cursor(self):
        prov = AnalysisNotificationProvider()
        self._feed(prov)
        cursor, events = prov.events_since(0)
        assert cursor == 4 and len(events) == 4
        cursor2, tail = prov.events_since(cursor)
        assert cursor2 == 4 and tail == []

    def test_track_and_notify_double_report_deduped(self):
        prov = AnalysisNotificationProvider()
        eng = Memento(
            lambda ctx: {"tokens_per_s": 1.0},
            notification_provider=prov,
            runner_config=RunnerConfig(max_workers=2, enable_speculation=False),
        )
        results = list(prov.track(eng.stream(
            ConfigMatrix.from_dict({"parameters": {"i": [0, 1, 2]}})
        )))
        assert len(results) == 3
        assert prov.state()["done"] == 3  # not 6

    def test_http_endpoints(self):
        prov = AnalysisNotificationProvider()
        self._feed(prov)
        dash = Dashboard(prov)  # port=0: ephemeral
        url = dash.start()
        try:
            with urllib.request.urlopen(f"{url}/api/state", timeout=5) as r:
                state = json.loads(r.read())
            assert state["done"] == 2 and "h1" in state["hosts"]
            with urllib.request.urlopen(f"{url}/api/events?since=0",
                                        timeout=5) as r:
                ev = json.loads(r.read())
            assert ev["next"] == 4 and len(ev["events"]) == 4
            with urllib.request.urlopen(url, timeout=5) as r:
                page = r.read().decode()
            assert "memento fleet" in page and "/api/state" in page
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"{url}/api/nope", timeout=5)
        finally:
            dash.stop()


class TestCLI:
    def test_table_cli_identical_to_api(self, tmp_path):
        res = _run_sweep()
        csv_path = tmp_path / "r.csv"
        res.to_csv(csv_path)
        frame = MetricFrame.from_results_csv(csv_path)
        api = compare(frame, rows="n", cols="seed", metric="tokens_per_s",
                      agg="mean", baseline=0).to_markdown()
        out = _cli("table", "--csv", str(csv_path), "--rows", "n",
                   "--cols", "seed", "--metric", "tokens_per_s",
                   "--agg", "mean", "--baseline", "0")
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == api

    def test_table_cli_diff_errors(self, tmp_path):
        a = tmp_path / "a.csv"
        _run_sweep().to_csv(a)
        out = _cli("table", "--diff", str(a), str(tmp_path / "gone.csv"),
                   "--rows", "n", "--metric", "tokens_per_s")
        assert out.returncode != 0
        assert "no results CSV" in out.stderr and "gone.csv" in out.stderr
        out = _cli("table", "--diff", str(a), "--rows", "n",
                   "--metric", "tokens_per_s")
        assert out.returncode != 0
        assert "at least two" in out.stderr

    def test_table_cli_diff_csv_runs(self, tmp_path):
        res = _run_sweep()
        a, b = tmp_path / "a.csv", tmp_path / "b.csv"
        res.to_csv(a)
        res.to_csv(b)
        # CSV runs need --rows (no benchmark param to default to)
        out = _cli("table", "--diff", str(a), str(b))
        assert out.returncode != 0 and "--rows" in out.stderr
        out = _cli("table", "--diff", str(a), str(b), "--rows", "n",
                   "--metric", "tokens_per_s")
        assert out.returncode == 0, out.stderr
        # identical inputs: every diff column is exactly 1.00x
        assert "(1.00x, +0.0%)" in out.stdout
        assert "(vs " in out.stdout

    def test_filequeue_stats_json(self, tmp_path):
        q = FileQueue(tmp_path / "q", owner="me")
        specs = ConfigMatrix.from_dict(
            {"parameters": {"i": [0, 1, 2]}}
        ).task_list()
        q.publish(specs)
        assert q.try_claim(specs[0].key)
        q.mark_done(specs[1].key, "ok", {"wall_s": 0.1})
        out = subprocess.run(
            [sys.executable, "-m", "repro.core.filequeue", "stats",
             str(tmp_path / "q"), "--json"],
            capture_output=True, text=True, env=_env(),
        )
        assert out.returncode == 0, out.stderr
        data = json.loads(out.stdout)
        assert data["total"] == 3 and data["claimed"] == 1
        assert data["done"] == 1 and data["available"] == 1
        assert data["done_by"] == {"me": 1}
        # the human format still works
        out = subprocess.run(
            [sys.executable, "-m", "repro.core.filequeue", "stats",
             str(tmp_path / "q")],
            capture_output=True, text=True, env=_env(),
        )
        assert "total=3 claimed=1 done=1 available=1" in out.stdout
