"""Runner, cache, file-queue, task checkpoints, notifications: fault-injection."""
import os
import sys
import threading
import time

import pytest

from repro.core import (
    ConfigMatrix,
    Context,
    FileQueue,
    FsCache,
    Memento,
    MemoryCache,
    RecordingProvider,
    Runner,
    RunnerConfig,
    TaskCheckpointStore,
    drain,
)


def _matrix(n=6):
    return ConfigMatrix.from_dict({"parameters": {"i": list(range(n))}})


def square(ctx: Context):
    return ctx["i"] ** 2


_fail_registry: dict[str, int] = {}


def flaky(ctx: Context):
    """Fails on first attempt for odd i, then succeeds."""
    key = ctx.key
    _fail_registry[key] = _fail_registry.get(key, 0) + 1
    if ctx["i"] % 2 == 1 and _fail_registry[key] == 1:
        raise RuntimeError(f"transient failure i={ctx['i']}")
    return ctx["i"]


def always_fails(ctx: Context):
    raise ValueError(f"broken task i={ctx['i']}")


def slow_then_value(ctx: Context):
    time.sleep(2.0 if ctx["i"] == 0 else 0.01)
    return ctx["i"]


def worker_state(ctx: Context):
    """Where the task ran, and whether that process had JAX loaded."""
    return os.getpid(), "jax" in sys.modules


class TestRunner:
    def test_parallel_ok(self):
        r = Runner(square, config=RunnerConfig(max_workers=4, enable_speculation=False))
        results = r.run(_matrix().task_list())
        assert [res.value for res in results] == [i * i for i in range(6)]
        assert all(res.ok for res in results)

    def test_process_mode_spawns_fresh_workers(self):
        """Process workers start from a fresh import: a fork would carry
        this process's loaded JAX (and its device) into every worker."""
        import jax.numpy as jnp

        jnp.zeros(1).block_until_ready()  # this process has started JAX
        r = Runner(worker_state, config=RunnerConfig(max_workers=2, mode="process"))
        results = r.run(_matrix(2).task_list())
        assert all(res.ok for res in results)
        for res in results:
            pid, has_jax = res.value
            assert pid != os.getpid()
            assert not has_jax

    def test_failure_isolation_and_traceback(self):
        def mixed(ctx):
            if ctx["i"] == 3:
                raise ValueError("boom")
            return ctx["i"]

        mixed.__module__ = TestRunner.__module__
        r = Runner(square, config=RunnerConfig(max_workers=2, retries=0, enable_speculation=False))
        r.func = mixed
        results = r.run(_matrix().task_list())
        failed = [x for x in results if not x.ok]
        assert len(failed) == 1
        assert failed[0].spec.params["i"] == 3
        assert "boom" in failed[0].error
        assert "ValueError" in failed[0].traceback_str
        assert sum(1 for x in results if x.ok) == 5

    def test_retry_recovers_transient(self):
        _fail_registry.clear()
        prov = RecordingProvider()
        r = Runner(flaky, provider=prov, config=RunnerConfig(max_workers=2, retries=2, enable_speculation=False))
        results = r.run(_matrix(4).task_list())
        assert all(res.ok for res in results)
        assert "task_retry" in prov.kinds()

    def test_retries_exhausted(self):
        r = Runner(always_fails, config=RunnerConfig(max_workers=2, retries=1, enable_speculation=False))
        results = r.run(_matrix(2).task_list())
        assert all(not res.ok for res in results)
        assert all(res.attempts == 2 for res in results)

    def test_hard_timeout(self):
        def hang(ctx):
            if ctx["i"] == 0:
                time.sleep(30)
            return ctx["i"]

        r = Runner(
            hang,
            config=RunnerConfig(
                max_workers=3, retries=0, task_timeout_s=0.5, enable_speculation=False
            ),
        )
        t0 = time.time()
        results = r.run(_matrix(3).task_list())
        assert time.time() - t0 < 10
        by_i = {res.spec.params["i"]: res for res in results}
        assert by_i[0].status == "timeout"
        assert by_i[1].ok and by_i[2].ok

    def test_straggler_speculation(self):
        r = Runner(
            slow_then_value,
            config=RunnerConfig(
                max_workers=4,
                retries=0,
                enable_speculation=True,
                straggler_min_s=0.3,
                straggler_factor=2.0,
            ),
        )
        prov = RecordingProvider()
        r.provider = prov
        results = r.run(_matrix(6).task_list())
        assert all(res.ok for res in results)
        assert "straggler_respawned" in prov.kinds()

    def test_cache_hits_skip_execution(self, tmp_path):
        calls = []

        def counting(ctx):
            calls.append(ctx["i"])
            return ctx["i"]

        counting.__module__ = TestRunner.__module__
        cache = FsCache(tmp_path / "cache")
        cfg = RunnerConfig(max_workers=2, enable_speculation=False)
        Runner(counting, cache=cache, config=cfg).run(_matrix(4).task_list())
        assert sorted(calls) == [0, 1, 2, 3]
        calls.clear()
        results = Runner(counting, cache=cache, config=cfg).run(_matrix(4).task_list())
        assert calls == []
        assert all(res.status == "cached" for res in results)

    def test_force_ignores_cache(self, tmp_path):
        cache = FsCache(tmp_path / "cache")
        r = Runner(square, cache=cache, config=RunnerConfig(max_workers=2, enable_speculation=False))
        r.run(_matrix(2).task_list())
        results = r.run(_matrix(2).task_list(), force=True)
        assert all(res.status == "ok" for res in results)


class TestFsCache:
    def test_roundtrip_and_manifest(self, tmp_path):
        c = FsCache(tmp_path)
        c.put("k1", {"x": [1, 2, 3]}, manifest={"note": "hi"})
        e = c.get("k1")
        assert e.value == {"x": [1, 2, 3]}
        assert e.manifest["note"] == "hi"
        assert e.manifest["payload_sha256"]

    def test_corruption_quarantined(self, tmp_path):
        c = FsCache(tmp_path)
        c.put("k1", [1, 2, 3])
        payload = tmp_path / "k1" / "result.pkl"
        payload.write_bytes(b"garbage")
        assert c.get("k1") is None  # quarantined, not returned
        assert not (tmp_path / "k1").exists()
        assert list((tmp_path / "_quarantine").iterdir())

    def test_overwrite_idempotent(self, tmp_path):
        c = FsCache(tmp_path)
        c.put("k", 1)
        c.put("k", 2)
        assert c.get("k").value == 2
        assert len(c) == 1


class TestTaskCheckpoints:
    def test_versioned_roundtrip(self, tmp_path):
        s = TaskCheckpointStore(tmp_path, "task1")
        assert not s.exists()
        assert s.save({"step": 1}) == 1
        assert s.save({"step": 2}) == 2
        assert s.restore() == {"step": 2}
        # keeps only two most recent
        s.save({"step": 3})
        files = sorted(p.name for p in (tmp_path / "task1").glob("ckpt-*.pkl"))
        assert files == ["ckpt-2.pkl", "ckpt-3.pkl"]

    def test_context_checkpoint_api(self, tmp_path):
        from repro.core.matrix import TaskSpec

        spec = TaskSpec(index=0, params={"i": 1}, settings={}, key="deadbeef")
        ctx = Context(spec=spec, checkpoints=TaskCheckpointStore(tmp_path, spec.key))
        assert not ctx.checkpoint_exists()
        assert ctx.restore(default={"fresh": True}) == {"fresh": True}
        ctx.checkpoint({"progress": 5})
        assert ctx.checkpoint_exists()
        assert ctx.restore()["progress"] == 5


def queue_work(ctx: Context):
    return ctx["i"] * 10


class TestFileQueue:
    def test_claim_exclusivity(self, tmp_path):
        q1 = FileQueue(tmp_path, lease_s=60, owner="host1")
        q2 = FileQueue(tmp_path, lease_s=60, owner="host2")
        specs = _matrix(1).task_list()
        q1.publish(specs)
        key = specs[0].key
        assert q1.try_claim(key)
        assert not q2.try_claim(key)
        q1.release(key)
        assert q2.try_claim(key)

    def test_expired_lease_reclaimed(self, tmp_path):
        q1 = FileQueue(tmp_path, lease_s=0.1, owner="dead-host")
        q2 = FileQueue(tmp_path, lease_s=60, owner="live-host")
        specs = _matrix(1).task_list()
        q1.publish(specs)
        key = specs[0].key
        assert q1.try_claim(key)
        time.sleep(0.2)
        assert q2.try_claim(key)  # broke the dead lease

    def test_two_hosts_drain_disjointly(self, tmp_path):
        specs = _matrix(12).task_list()
        by_key = {s.key: s for s in specs}
        q = FileQueue(tmp_path, lease_s=60, owner="seed")
        q.publish(specs)
        done: dict[str, list[str]] = {"h1": [], "h2": []}

        def host(name):
            qh = FileQueue(tmp_path, lease_s=60, owner=name)
            res = drain(
                qh, by_key, lambda spec, beat: spec.params["i"], idle_rounds=2, idle_sleep_s=0.05
            )
            done[name] = list(res)

        t1 = threading.Thread(target=host, args=("h1",))
        t2 = threading.Thread(target=host, args=("h2",))
        t1.start(); t2.start(); t1.join(); t2.join()
        assert set(done["h1"]) | set(done["h2"]) == set(by_key)
        assert not (set(done["h1"]) & set(done["h2"]))

    def test_memento_run_distributed(self, tmp_path):
        eng = Memento(queue_work, workdir=tmp_path / "w")
        res = eng.run_distributed(
            {"parameters": {"i": [1, 2, 3]}}, queue_dir=tmp_path / "q"
        )
        assert sorted(r.value for r in res if r.ok) == [10, 20, 30]


class TestMementoFacade:
    def test_paper_snippet_shape(self, tmp_path):
        import repro.core as memento

        notif = memento.RecordingProvider()
        results = memento.Memento(square, notif, workdir=tmp_path).run(
            {"parameters": {"i": [1, 2]}, "settings": {}, "exclude": []}
        )
        assert results.values == [1, 4]
        assert "run_finished" in notif.kinds()

    def test_dry_run_executes_nothing(self):
        hits = []

        def f(ctx):
            hits.append(1)

        res = Memento(f).run({"parameters": {"i": [1, 2, 3]}}, dry_run=True)
        assert hits == []
        assert len(res) == 3
        assert all(r.status == "skipped" for r in res)

    def test_value_by_params(self):
        res = Memento(square).run({"parameters": {"i": [1, 2, 3]}})
        assert res.value_by_params(i=3) == 9
