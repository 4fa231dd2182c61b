"""Distributed sweep runtime: file-queue race tests + multi-process
contention/crash-recovery suite.

The four deterministic regression tests interleave the historical races by
monkeypatching one host's ``_read_claim`` to let a rival act between the
read and the mutation — each fails on the pre-tombstone protocol and passes
on the rename-based one. The multi-process tests drain one queue directory
with real worker processes (including an induced mid-task crash) and assert
exactly-once-observable completion plus key-for-key equality with a
single-host ``run()``.

Kept free of jax imports: worker processes are spawned and re-import this
module; they only need ``repro.core``.
"""
import json
import multiprocessing
import os
import threading
import time
import types
import uuid
from collections import Counter
from pathlib import Path

import pytest

from repro.core import (
    ConfigMatrix,
    FileQueue,
    Memento,
    ProgressNotificationProvider,
    RecordingProvider,
    RunnerConfig,
    drain,
)

_MP = multiprocessing.get_context("spawn")


def _matrix(n=6):
    return ConfigMatrix.from_dict({"parameters": {"i": list(range(n))}})


def _claim_owner(tmp_path, key):
    path = Path(tmp_path) / "claims" / f"{key}.claim"
    return json.loads(path.read_text())["owner"] if path.exists() else None


class TestClaimRaces:
    """Deterministic interleavings of the lease-break and release races."""

    def test_try_claim_lease_break_race(self, tmp_path):
        """Two hosts observe the same expired lease; the slower one must NOT
        destroy the winner's fresh claim (the old unlink-based break did)."""
        qa = FileQueue(tmp_path, lease_s=60, owner="host-a")
        qb = FileQueue(tmp_path, lease_s=60, owner="host-b")
        qdead = FileQueue(tmp_path, lease_s=0.05, owner="dead-host")
        specs = _matrix(1).task_list()
        qa.publish(specs)
        key = specs[0].key
        assert qdead.try_claim(key)
        time.sleep(0.1)  # dead-host's lease expires

        real_read = FileQueue._read_claim
        fired = []

        def interleaved(self, k):
            claim = real_read(self, k)
            if not fired and claim is not None and claim.get("owner") == "dead-host":
                fired.append(1)
                # B races in *after* A observed the expired lease but before
                # A breaks it: B breaks the dead lease and claims.
                assert qb.try_claim(k)
            return claim  # A still holds the stale "expired" observation

        qa._read_claim = types.MethodType(interleaved, qa)
        got = qa.try_claim(key)
        assert fired, "interleave point never hit"
        assert not got, "slower host won a claim it should have lost"
        assert _claim_owner(tmp_path, key) == "host-b"

    def test_release_does_not_destroy_reclaimed_lease(self, tmp_path):
        """release() after our lease expired and was legitimately broken +
        re-claimed by a peer must leave the peer's live claim intact (the
        old read-then-unlink deleted it)."""
        qa = FileQueue(tmp_path, lease_s=0.05, owner="host-a")
        qb = FileQueue(tmp_path, lease_s=60, owner="host-b")
        specs = _matrix(1).task_list()
        qa.publish(specs)
        key = specs[0].key
        assert qa.try_claim(key)
        time.sleep(0.1)  # A's lease expires while its task is still running

        real_read = FileQueue._read_claim
        fired = []

        def interleaved(self, k):
            claim = real_read(self, k)
            if not fired and claim is not None and claim.get("owner") == "host-a":
                fired.append(1)
                # B breaks A's expired lease and re-claims between A's
                # ownership check and A's removal of the claim file.
                assert qb.try_claim(k)
            return claim

        qa._read_claim = types.MethodType(interleaved, qa)
        qa.release(key)
        assert fired, "interleave point never hit"
        assert _claim_owner(tmp_path, key) == "host-b"
        qb.renew(key)  # B's lease is alive and renewable

    def test_renew_does_not_clobber_reclaimed_lease(self, tmp_path):
        """renew() after our lease expired and was broken + re-claimed by a
        peer must raise and leave the peer's claim intact — a blind replace
        would overwrite it and resurrect the double-ownership state."""
        from repro.core import QueueError

        qa = FileQueue(tmp_path, lease_s=0.05, owner="host-a")
        qb = FileQueue(tmp_path, lease_s=60, owner="host-b")
        specs = _matrix(1).task_list()
        qa.publish(specs)
        key = specs[0].key
        assert qa.try_claim(key)
        time.sleep(0.1)  # A's lease expires (stalled renewer)

        real_read = FileQueue._read_claim
        fired = []

        def interleaved(self, k):
            claim = real_read(self, k)
            if not fired and claim is not None and claim.get("owner") == "host-a":
                fired.append(1)
                assert qb.try_claim(k)  # peer breaks + re-claims first
            return claim

        qa._read_claim = types.MethodType(interleaved, qa)
        with pytest.raises(QueueError):
            qa.renew(key)
        assert fired, "interleave point never hit"
        assert _claim_owner(tmp_path, key) == "host-b"
        qb.renew(key)  # B's claim is alive and renewable

    def test_release_of_own_live_claim(self, tmp_path):
        q = FileQueue(tmp_path, lease_s=60, owner="h")
        specs = _matrix(1).task_list()
        q.publish(specs)
        key = specs[0].key
        assert q.try_claim(key)
        q.release(key)
        assert _claim_owner(tmp_path, key) is None
        assert q.try_claim(key)  # claimable again

    def test_no_stray_tombstones(self, tmp_path):
        q1 = FileQueue(tmp_path, lease_s=0.05, owner="h1")
        q2 = FileQueue(tmp_path, lease_s=60, owner="h2")
        specs = _matrix(1).task_list()
        q1.publish(specs)
        key = specs[0].key
        assert q1.try_claim(key)
        time.sleep(0.1)
        assert q2.try_claim(key)  # breaks via tombstone
        q2.release(key)
        left = [p.name for p in (Path(tmp_path) / "claims").iterdir()]
        assert left == [], f"leftover claim-dir entries: {left}"


def _backdate(path, age_s):
    t = time.time() - age_s
    os.utime(path, (t, t))


class TestGC:
    """``FileQueue.gc``: stale attempt records and orphaned lease debris."""

    def test_fail_records_purged_for_done_and_aged_tasks(self, tmp_path):
        q = FileQueue(tmp_path, lease_s=60, owner="h")
        specs = _matrix(3).task_list()
        q.publish(specs)
        done_k, aged_k, live_k = (s.key for s in specs)
        q.record_failure(done_k, "boom")
        q.record_failure(done_k, "boom again")
        q.mark_done(done_k, "failed")
        q.record_failure(aged_k, "old boom")
        for p in (tmp_path / "fails").glob(f"{aged_k}.*.json"):
            _backdate(p, 10 * 86400)
        q.record_failure(live_k, "fresh boom")

        out = q.gc(max_age_s=7 * 86400)
        assert out["fails_purged"] == 3
        # the done task's budget can never be consulted again; the aged
        # record crossed max_age_s; the fresh one still counts
        assert q.failure_records(done_k) == []
        assert q.failure_records(aged_k) == []
        assert len(q.failure_records(live_k)) == 1

    def test_orphan_tombstones_audited(self, tmp_path):
        q = FileQueue(tmp_path, lease_s=60, owner="h")
        specs = _matrix(2).task_list()
        q.publish(specs)
        k_dead, k_live = specs[0].key, specs[1].key
        claims = tmp_path / "claims"

        # Expired-claim tombstone from a host that died mid-break: retired.
        dead = claims / f".{k_dead}.deadbeef.tomb"
        dead.write_text(json.dumps({"owner": "x", "expires_unix": time.time() - 5}))
        _backdate(dead, 300)
        # Live-claim tombstone whose restore never ran (host died between
        # rename and link) and whose claim file is gone: restored, not lost.
        live = claims / f".{k_live}.cafef00d.tomb"
        live.write_text(
            json.dumps({"owner": "h2", "expires_unix": time.time() + 3600})
        )
        _backdate(live, 300)
        # A young tombstone is someone's in-flight steal: untouchable.
        young = claims / f".{k_dead}.0badcafe.tomb"
        young.write_text(json.dumps({"owner": "y", "expires_unix": 0}))

        out = q.gc()
        assert out["tombs_retired"] == 1
        assert out["tombs_restored"] == 1
        assert not dead.exists()
        assert not live.exists()
        assert young.exists()
        assert _claim_owner(tmp_path, k_live) == "h2"
        # restored claim is live again: not claimable until it expires
        assert not FileQueue(tmp_path, owner="h3").try_claim(k_live)

    def test_scratch_purged_and_dry_run(self, tmp_path):
        q = FileQueue(tmp_path, lease_s=60, owner="h")
        q.publish(_matrix(1).task_list())
        old_tmp = tmp_path / "tasks" / ".x.h.tmp"
        old_tmp.write_text("{}")
        _backdate(old_tmp, 300)
        old_renew = tmp_path / "claims" / "k.renew"
        old_renew.write_text("{}")
        _backdate(old_renew, 300)
        fresh_tmp = tmp_path / "done" / ".y.h.tmp"
        fresh_tmp.write_text("{}")

        dry = q.gc(dry_run=True)
        assert dry["scratch_purged"] == 2
        assert old_tmp.exists() and old_renew.exists()
        out = q.gc()
        assert out["scratch_purged"] == 2
        assert not old_tmp.exists() and not old_renew.exists()
        assert fresh_tmp.exists()
        # task/claim/done records themselves were never candidates
        assert q.pending_keys()

    def test_cli_entrypoint(self, tmp_path):
        import subprocess
        import sys

        q = FileQueue(tmp_path, lease_s=60, owner="h")
        specs = _matrix(2).task_list()
        q.publish(specs)
        q.record_failure(specs[0].key, "boom")
        q.mark_done(specs[0].key, "ok")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parent.parent / "src"),
             env.get("PYTHONPATH", "")]
        )
        r = subprocess.run(
            [sys.executable, "-m", "repro.core.filequeue", "gc", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        assert "fails_purged=1" in r.stdout
        assert q.failure_records(specs[0].key) == []
        r = subprocess.run(
            [sys.executable, "-m", "repro.core.filequeue", "stats", str(tmp_path)],
            capture_output=True, text=True, env=env, timeout=60,
        )
        assert r.returncode == 0, r.stderr
        assert "total=2" in r.stdout and "done=1" in r.stdout
        r = subprocess.run(
            [sys.executable, "-m", "repro.core.filequeue", "gc",
             str(tmp_path / "nonexistent")],
            capture_output=True, text=True, env=env, timeout=60,
        )
        assert r.returncode != 0


class TestDrain:
    def test_drain_ignores_foreign_matrix_keys(self, tmp_path):
        """Keys published by a matrix version this worker doesn't know must
        not count toward termination — the old code livelocked forever."""
        specs = _matrix(3).task_list()
        foreign = ConfigMatrix.from_dict({"parameters": {"j": [10, 11]}}).task_list()
        pub = FileQueue(tmp_path, owner="pub")
        pub.publish(specs)
        pub.publish(foreign)
        by_key = {s.key: s for s in specs}
        out = {}

        def worker():
            q = FileQueue(tmp_path, lease_s=60, owner="w")
            out.update(
                drain(q, by_key, lambda s, beat: s.params["i"],
                      idle_rounds=2, idle_sleep_s=0.02)
            )

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        t.join(timeout=15)
        assert not t.is_alive(), "drain() livelocked on foreign-published keys"
        assert set(out) == set(by_key)
        # the foreign keys are untouched, not claimed/failed
        assert all(not pub.is_done(s.key) for s in foreign)

    def test_drain_failure_records_and_cross_host_budget(self, tmp_path):
        specs = _matrix(1).task_list()
        key = specs[0].key
        q1 = FileQueue(tmp_path, lease_s=60, owner="h1")
        q2 = FileQueue(tmp_path, lease_s=60, owner="h2")
        q1.publish(specs)
        by_key = {s.key: s for s in specs}

        # Host 1 fails the task once mid-drain: attempt recorded, claim
        # released, nothing terminal yet (this is exactly what drain() does
        # on a non-terminal failure).
        assert q1.try_claim(key)
        assert q1.record_failure(key, "ValueError: original kaboom",
                                 "Traceback ... ValueError: original kaboom") == 1
        q1.release(key)
        assert not q1.is_done(key)

        def boom(spec, beat):
            raise RuntimeError("later failure on h2")

        # Host 2 exhausts the cross-host budget: terminal, with the
        # *original* error + traceback + attempt count in the done record.
        res2 = drain(q2, by_key, boom, idle_rounds=1, idle_sleep_s=0.01,
                     max_attempts=2)
        assert res2 == {key: "failed"}
        rec = q2.read_done(key)
        assert rec["status"] == "failed"
        assert rec["error"] == "ValueError: original kaboom"
        assert "ValueError" in rec["traceback"]
        assert rec["attempts"] == 2
        assert rec["last_error"] == "RuntimeError: later failure on h2"
        assert rec["owner"] == "h2"

    def test_stats_key_scoping(self, tmp_path):
        specs = _matrix(2).task_list()
        foreign = ConfigMatrix.from_dict({"parameters": {"j": [1]}}).task_list()
        q = FileQueue(tmp_path, owner="h")
        q.publish(specs)
        q.publish(foreign)
        known = {s.key for s in specs}
        assert q.stats().total == 3
        assert q.stats(keys=known).total == 2
        assert q.try_claim(foreign[0].key)
        assert q.stats().claimed == 1
        assert q.stats(keys=known).claimed == 0


def exec_and_value(ctx):
    """Experiment function for the multi-process suite: records every
    execution as a unique file (exactly-once observability), then returns a
    pure function of the params."""
    d = Path(ctx.settings["execdir"])
    (d / f"{ctx.key}.{uuid.uuid4().hex}").touch()
    marker = ctx.settings.get("crash_marker")
    if marker and ctx["i"] == ctx.settings["crash_i"] and not Path(marker).exists():
        Path(marker).touch()
        os._exit(23)  # simulated host death: leases left behind must expire
    time.sleep(ctx.settings.get("delay", 0.01))
    return ctx["i"] * 7


def _worker_main(root, matrix, owner, lease_s):
    eng = Memento(
        exec_and_value,
        workdir=os.path.join(root, "w"),
        runner_config=RunnerConfig(max_workers=2, enable_speculation=False, retries=0),
    )
    eng.run_distributed(
        matrix, queue_dir=os.path.join(root, "q"), lease_s=lease_s, owner=owner
    )


def _mk_matrix(root, n, crash=False):
    settings = {"execdir": os.path.join(root, "exec"), "delay": 0.01}
    if crash:
        settings.update(crash_marker=os.path.join(root, "crashed"), crash_i=2)
    return {"parameters": {"i": list(range(n))}, "settings": settings}


def _exec_counts(root):
    return Counter(p.name.split(".")[0] for p in (Path(root) / "exec").iterdir())


class TestMultiProcess:
    def _run_workers(self, root, matrix, n_procs, lease_s, timeout=120):
        procs = [
            _MP.Process(target=_worker_main, args=(root, matrix, f"w{i}", lease_s))
            for i in range(n_procs)
        ]
        for p in procs:
            p.start()
        for p in procs:
            p.join(timeout=timeout)
        codes = [p.exitcode for p in procs]
        assert all(c is not None for c in codes), f"worker hung: {codes}"
        return codes

    def test_contention_exactly_once_and_matches_single_host(self, tmp_path):
        n = 12
        root = str(tmp_path)
        (tmp_path / "exec").mkdir()
        matrix = _mk_matrix(root, n)
        codes = self._run_workers(root, matrix, n_procs=3, lease_s=60)
        assert codes == [0, 0, 0]
        # Exactly-once observable: each of the n tasks executed exactly once
        # across all three processes (claims were exclusive, no lease broke).
        counts = _exec_counts(root)
        assert len(counts) == n
        assert set(counts.values()) == {1}, f"double-executed: {counts}"
        # Any host (here: the parent, which ran nothing) assembles the full
        # ResultSet from the shared cache/queue...
        eng = Memento(exec_and_value, workdir=tmp_path / "w")
        assembled = eng.run_distributed(
            matrix, queue_dir=tmp_path / "q", publish=False
        )
        assert sorted(r.value for r in assembled) == [i * 7 for i in range(n)]
        assert all(r.ok for r in assembled)
        # ...and it equals a single-host run() key-for-key.
        single = Memento(
            exec_and_value,
            workdir=tmp_path / "w-single",
            runner_config=RunnerConfig(max_workers=4, enable_speculation=False),
        ).run(matrix)
        assert {r.spec.key: r.value for r in single} == {
            r.spec.key: r.value for r in assembled
        }

    def test_killed_worker_recovered_via_lease_break(self, tmp_path):
        n = 8
        root = str(tmp_path)
        (tmp_path / "exec").mkdir()
        matrix = _mk_matrix(root, n, crash=True)
        codes = self._run_workers(root, matrix, n_procs=3, lease_s=1.0)
        # exactly one worker died mid-task; the others (or a lease break by
        # whoever was still draining) completed the whole matrix anyway
        assert sorted(codes) == [0, 0, 23], codes
        eng = Memento(exec_and_value, workdir=tmp_path / "w")
        assembled = eng.run_distributed(
            matrix, queue_dir=tmp_path / "q", publish=False, lease_s=1.0
        )
        assert sorted(r.value for r in assembled) == [i * 7 for i in range(n)]
        counts = _exec_counts(root)
        assert len(counts) == n
        # the crashed task (and any task the dead worker had in flight) was
        # re-executed after its lease expired; nothing ran more than twice
        assert all(1 <= c <= 2 for c in counts.values()), counts


class TestDistributedRuntime:
    """Single-process (thread-level) behaviours of the Runner-backed drain."""

    def test_peer_failure_surfaces_real_error(self, tmp_path):
        """A task that failed on a peer host must surface that host's real
        error + traceback, never a generic 'failed on a peer host'."""

        def boom(ctx):
            if ctx["i"] == 1:
                raise ValueError("actual root cause 42")
            return ctx["i"]

        matrix = {"parameters": {"i": [0, 1]}}
        eng_a = Memento(
            boom, workdir=tmp_path / "w",
            runner_config=RunnerConfig(max_workers=2, enable_speculation=False,
                                       retries=0),
        )
        res_a = eng_a.run_distributed(
            matrix, queue_dir=tmp_path / "q", max_attempts=1, owner="host-a"
        )
        # ...as seen by the executing host itself,
        failed_a = [r for r in res_a if not r.ok]
        assert len(failed_a) == 1
        assert "actual root cause 42" in failed_a[0].error
        assert "peer host" not in failed_a[0].error
        # ...and by a peer that only observes the done record.
        eng_b = Memento(boom, workdir=tmp_path / "w")
        res_b = eng_b.run_distributed(
            matrix, queue_dir=tmp_path / "q", max_attempts=1, owner="host-b"
        )
        failed_b = [r for r in res_b if not r.ok]
        assert len(failed_b) == 1
        assert "actual root cause 42" in failed_b[0].error
        assert "peer host" not in failed_b[0].error
        assert failed_b[0].host == "host-a"
        assert "ValueError" in failed_b[0].traceback_str
        assert [r.value for r in res_b if r.ok] == [0]

    def test_cross_host_retry_until_budget_then_success(self, tmp_path):
        execs = tmp_path / "execs"
        execs.mkdir()

        def flaky(ctx):
            n_before = len(list(execs.iterdir()))
            (execs / f"e{n_before}").touch()
            if n_before < 2:
                raise RuntimeError(f"transient {n_before}")
            return "recovered"

        eng = Memento(
            flaky, workdir=tmp_path / "w",
            runner_config=RunnerConfig(max_workers=1, enable_speculation=False,
                                       retries=0),
        )
        res = eng.run_distributed(
            {"parameters": {"i": [0]}}, queue_dir=tmp_path / "q", max_attempts=3
        )
        assert res[0].ok and res[0].value == "recovered"
        assert len(list(execs.iterdir())) == 3  # two queue retries, then ok
        q = FileQueue(tmp_path / "q")
        assert len(q.failure_records(res[0].spec.key)) == 2

    def test_lease_renewal_thread_covers_heartbeat_free_tasks(self, tmp_path):
        """A long task that never calls ctx.heartbeat() must keep its lease:
        a rival host polling the queue the whole time never steals the task,
        so it executes exactly once."""
        execs = tmp_path / "execs"
        execs.mkdir()

        def slow(ctx):
            (execs / uuid.uuid4().hex).touch()
            time.sleep(1.0)  # >> lease_s, no heartbeat calls
            return "done"

        matrix = {"parameters": {"i": [0]}}
        results = {}

        def host(name):
            eng = Memento(
                slow, workdir=tmp_path / "w",
                runner_config=RunnerConfig(max_workers=1,
                                           enable_speculation=False, retries=0),
            )
            results[name] = eng.run_distributed(
                matrix, queue_dir=tmp_path / "q", lease_s=0.3, owner=name
            )

        t1 = threading.Thread(target=host, args=("h1",), daemon=True)
        t2 = threading.Thread(target=host, args=("h2",), daemon=True)
        t1.start(); t2.start()
        t1.join(timeout=30); t2.join(timeout=30)
        assert not t1.is_alive() and not t2.is_alive()
        assert len(list(execs.iterdir())) == 1, "lease expired mid-task"
        for name in ("h1", "h2"):
            assert [r.ok for r in results[name]] == [True]

    def test_stream_yields_cache_hits_first_then_live(self, tmp_path):
        def f(ctx):
            return ctx["i"] * 2

        eng = Memento(f, workdir=tmp_path / "w",
                      runner_config=RunnerConfig(max_workers=2,
                                                 enable_speculation=False))
        eng.run({"parameters": {"i": [0]}})  # warm one cell of the cache
        seen = [
            r.status
            for r in eng.stream_distributed(
                {"parameters": {"i": [0, 1, 2]}}, queue_dir=tmp_path / "q"
            )
        ]
        assert seen[0] == "cached"
        assert sorted(seen[1:]) == ["ok", "ok"]

    def test_queue_progress_events_and_provider_rendering(self, tmp_path):
        import io

        rec = RecordingProvider()

        def f(ctx):
            return ctx["i"]

        eng = Memento(
            f, rec, workdir=tmp_path / "w",
            runner_config=RunnerConfig(max_workers=2, enable_speculation=False),
        )
        from repro.core import DistributedConfig

        res = eng.run_distributed(
            {"parameters": {"i": [0, 1, 2]}}, queue_dir=tmp_path / "q",
            owner="me", distributed_config=DistributedConfig(progress_every_s=0.0),
        )
        assert all(r.ok for r in res)
        prog = [e for e in rec.events if e.kind == "queue_progress"]
        assert prog
        assert prog[-1].payload["total"] == 3
        assert "claimed_by" in prog[-1].payload and "done_by" in prog[-1].payload
        # structured-event schema: every queue_progress snapshot carries the
        # fleet fields the dashboard consumes
        for e in prog:
            p = e.payload
            assert set(p) >= {"total", "done", "failed", "claimed_by",
                              "done_by", "owner", "elapsed_s", "eta_s"}
            assert p["owner"] == "me"
            assert p["elapsed_s"] >= 0.0
            assert p["eta_s"] is None or p["eta_s"] >= 0.0
        # run_started announces the matrix size for ETA math downstream
        started = [e for e in rec.events if e.kind == "run_started"]
        assert started and started[0].payload["total"] == 3
        assert started[0].payload["workers"] == 2
        # task_finished events carry host/wall_s/params/metrics
        fin = [e for e in rec.events if e.kind == "task_finished"]
        assert fin
        for e in fin:
            p = e.payload
            assert set(p) >= {"key", "status", "params", "host", "wall_s",
                              "attempts", "cached", "metrics"}
            assert p["host"]
            assert "i" in p["params"]
        rec_dict = fin[0].to_record()
        assert rec_dict["kind"] == "task_finished" and rec_dict["key"]
        # ProgressNotificationProvider renders the per-host queue line
        buf = io.StringIO()
        prov = ProgressNotificationProvider(total=3, stream=buf)
        prov.notify(prog[-1])
        line = buf.getvalue()
        assert "queue" in line and "/3 done" in line
        assert prov.queue_state["total"] == 3

    def test_queue_state_converges_for_warm_caches(self, tmp_path):
        def f(ctx):
            return ctx["i"]

        eng = Memento(f, workdir=tmp_path / "w")
        eng.run({"parameters": {"i": [0, 1]}})
        eng.run_distributed({"parameters": {"i": [0, 1]}}, queue_dir=tmp_path / "q")
        q = FileQueue(tmp_path / "q")
        # cache-hit tasks were marked done so the queue itself drains
        assert q.stats().done == 2
        assert q.pending_keys() == []
