"""Benchmarks of Memento's host-side claims, one function per claim.

The Memento paper's claims (demo paper, no numeric tables) map to:
  B1  configuration-matrix expansion scales to large experiment sets
      (including composed matrices: products, filters, derived params)
  B2  parallel execution beats sequential for embarrassingly-parallel tasks
  B3  result caching makes re-runs ~free
  B4  in-task checkpointing bounds lost work on interruption
  B5  failure isolation: one broken task does not poison a run
  B12 distributed drain: the same matrix drained through the file-queue by
      1/2/4 single-threaded worker processes on one shared tmpdir —
      tasks/s, speedup, and scaling efficiency; plus a kill-one-worker row
      showing lease recovery completing the matrix anyway

The host is these claims' real platform. Serving speed is measured on the
TPU by the chip benchmark (``BENCHMARK.json``, ``benchmarks/chip/``); its
numbers and their history are in ``PERF.md`` and ``PERF_LEDGER.jsonl``.

Prints ``name,us_per_call,derived`` CSV rows and exits nonzero if any row
failed. ``--smoke`` runs B1–B5 at their smoke sizes (seconds);
``--distributed-smoke`` runs B12 small.
"""
from __future__ import annotations

import argparse
import statistics
import sys
import time


def _t(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


# Names of the rows that reported ok=False; any turns into a nonzero exit.
_FAILED: list[str] = []


def _row(name: str, us: float, derived: str = "", ok: bool = True) -> None:
    print(f"{name},{us:.1f},{derived}")
    if not ok:
        _FAILED.append(name)


def bench_matrix_expansion(smoke: bool = False) -> None:
    from repro.core import ConfigMatrix

    shapes = ((3, 6),) if smoke else ((4, 10), (5, 12))
    for n_axes, width in shapes:
        m = ConfigMatrix.from_dict(
            {"parameters": {f"p{i}": list(range(width)) for i in range(n_axes)}}
        )
        total = width ** n_axes
        us = _t(lambda: m.task_list(), n=2)
        _row(
            f"B1_matrix_expand_{total}_tasks", us,
            f"{total/ (us/1e6):.0f} tasks/s incl hashing",
        )

    # Composed expansion: product of two matrices, a callable exclude, and a
    # derived parameter — the v2 algebra on the same hot path.
    w = 4 if smoke else 8
    m1 = ConfigMatrix.from_dict({"parameters": {"a": list(range(w)), "b": list(range(w))}})
    m2 = ConfigMatrix.from_dict({"parameters": {"c": list(range(w))}})
    comp = (m1 * m2).where(lambda p: p["a"] != p["c"]).derive("ab", lambda p: p["a"] * p["b"])
    n_tasks = len(comp.task_list())
    us = _t(lambda: comp.task_list(), n=2)
    _row(
        f"B1_matrix_algebra_{n_tasks}_tasks", us,
        f"(m1*m2).where(a!=c).derive(ab) -> {n_tasks}/{w**3} tasks",
    )


def bench_parallel_speedup(smoke: bool = False) -> None:
    from repro.core import Memento, RunnerConfig

    delay = 0.02 if smoke else 0.05
    n_tasks = 4 if smoke else 8

    def sleepy(ctx):
        time.sleep(ctx.settings["delay"])
        return ctx["i"]

    matrix = {"parameters": {"i": list(range(n_tasks))}, "settings": {"delay": delay}}
    seq = Memento(sleepy, runner_config=RunnerConfig(max_workers=1, enable_speculation=False))
    par = Memento(sleepy, runner_config=RunnerConfig(max_workers=n_tasks, enable_speculation=False))
    t_seq = _t(lambda: seq.run(matrix, cache=False), n=2, warmup=0)
    t_par = _t(lambda: par.run(matrix, cache=False), n=2, warmup=0)
    _row(f"B2_sequential_{n_tasks}x{delay*1e3:.0f}ms", t_seq)
    _row(f"B2_parallel_{n_tasks}workers", t_par, f"speedup={t_seq/t_par:.2f}x")


def bench_cache_speedup(tmpdir="/tmp/repro_bench_cache", smoke: bool = False) -> None:
    import shutil

    from repro.core import Memento

    shutil.rmtree(tmpdir, ignore_errors=True)
    delay = 0.02 if smoke else 0.05
    n_tasks = 4 if smoke else 6

    def work(ctx):
        time.sleep(ctx.settings["delay"])
        return ctx["i"] ** 2

    eng = Memento(work, workdir=tmpdir)
    matrix = {"parameters": {"i": list(range(n_tasks))}, "settings": {"delay": delay}}
    t_cold = _t(lambda: eng.run(matrix), n=1, warmup=0)
    t_warm = _t(lambda: eng.run(matrix), n=3, warmup=0)
    _row(f"B3_cold_run_{n_tasks}x{delay*1e3:.0f}ms", t_cold)
    _row("B3_cached_rerun", t_warm, f"speedup={t_cold/max(t_warm,1e-9):.1f}x")


def bench_checkpoint_overhead(tmpdir="/tmp/repro_bench_ckpt", smoke: bool = False) -> None:
    import shutil

    import jax.numpy as jnp

    from repro.ckpt.store import CheckpointStore

    shutil.rmtree(tmpdir, ignore_errors=True)
    dim = 64 if smoke else 512
    state = {"w": jnp.ones((dim, dim)), "m": jnp.ones((dim, dim)), "step": jnp.ones(())}
    store = CheckpointStore(tmpdir)
    us_sync = _t(lambda: store.save(1, state, blocking=True), n=3)
    def async_save():
        store.save(2, state, blocking=False)
    us_async = _t(async_save, n=3)
    store.wait()
    mb = state["w"].nbytes * 2 / 1e6
    _row(f"B4_ckpt_save_{mb:.1f}MB_sync", us_sync)
    _row(f"B4_ckpt_save_{mb:.1f}MB_async_enqueue", us_async, f"hidden={us_sync/max(us_async,1):.1f}x")


def bench_failure_isolation() -> None:
    from repro.core import Memento, RunnerConfig

    def half_broken(ctx):
        if ctx["i"] % 2:
            raise RuntimeError("boom")
        return ctx["i"]

    eng = Memento(
        half_broken,
        runner_config=RunnerConfig(max_workers=4, retries=0, enable_speculation=False),
    )
    us = _t(lambda: eng.run({"parameters": {"i": list(range(8))}}, cache=False), n=2, warmup=0)
    res = eng.run({"parameters": {"i": list(range(8))}}, cache=False)
    _row("B5_half_failing_run", us, f"ok={len(res.ok)} failed={len(res.failed)} isolated=True",
         ok=(len(res.ok), len(res.failed)) == (4, 4))


def _b12_task(ctx):
    time.sleep(ctx.settings["delay"])
    return ctx["i"]


def _b12_worker(root: str, n: int, delay: float, owner: str, lease_s: float,
                die_after: float = 0.0) -> None:
    import os

    from repro.core import (
        CallbackNotificationProvider,
        DistributedConfig,
        Memento,
        RunnerConfig,
    )

    if die_after:
        # Simulated host death: hard-kill this worker mid-drain, claims and
        # all. The survivors must finish the matrix via lease expiry.
        import threading

        threading.Timer(die_after, lambda: os._exit(29)).start()
    matrix = {"parameters": {"i": list(range(n))}, "settings": {"delay": delay}}
    eng = Memento(
        _b12_task,
        notification_provider=CallbackNotificationProvider(lambda e: None),
        workdir=os.path.join(root, "w"),
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    eng.run_distributed(
        matrix,
        queue_dir=os.path.join(root, "q"),
        lease_s=lease_s,
        owner=owner,
        # local disk, not NFS: poll tightly so completion latency, not the
        # poll cadence, dominates the tail
        distributed_config=DistributedConfig(
            poll_s=0.05, claim_ahead=1, progress_every_s=60.0
        ),
    )


def _b12_assemble(root: str):
    """A quiet parent-side engine that only assembles results (runs nothing
    itself by the time it is called — everything is cached/done)."""
    from repro.core import CallbackNotificationProvider, Memento

    return Memento(
        _b12_task,
        notification_provider=CallbackNotificationProvider(lambda e: None),
        workdir=f"{root}/w",
    )


def bench_distributed(smoke: bool = False) -> None:
    """B12: multi-host drain scaling.

    Each worker is a real OS process running ``Memento.run_distributed``
    with a single-threaded Runner (so the scaling measured is across the
    file-queue protocol, not across one process's thread pool), all draining
    one matrix on one shared tmpdir. A fresh queue+cache per point keeps the
    points independent; the parent verifies every point produced the full,
    identical ResultSet.
    """
    import multiprocessing
    import shutil
    import tempfile

    from repro.core import Memento

    mp = multiprocessing.get_context("spawn")
    n_tasks = 8 if smoke else 32
    delay = 0.02 if smoke else 0.15
    points = (1, 2) if smoke else (1, 2, 4)
    lease_s = 30.0
    base_rate = None
    expected = list(range(n_tasks))
    for n_procs in points:
        root = tempfile.mkdtemp(prefix="repro_b12_")
        try:
            procs = [
                mp.Process(
                    target=_b12_worker,
                    args=(root, n_tasks, delay, f"w{i}", lease_s),
                )
                for i in range(n_procs)
            ]
            t0 = time.perf_counter()
            for p in procs:
                p.start()
            for p in procs:
                p.join(timeout=300)
            wall = time.perf_counter() - t0
            assert all(p.exitcode == 0 for p in procs), [p.exitcode for p in procs]
            matrix = {"parameters": {"i": expected}, "settings": {"delay": delay}}
            res = _b12_assemble(root).run_distributed(
                matrix, queue_dir=f"{root}/q", publish=False
            )
            assert sorted(r.value for r in res) == expected, "ResultSet mismatch"
            rate = n_tasks / wall
            if base_rate is None:
                base_rate = rate
            speedup = rate / base_rate
            _row(
                f"B12_distributed_{n_procs}proc_{n_tasks}tasks",
                wall * 1e6,
                f"{rate:.1f} tasks/s speedup={speedup:.2f}x "
                f"efficiency={speedup / n_procs:.2f}",
            )
        finally:
            shutil.rmtree(root, ignore_errors=True)

    # Kill-one-worker: 2 workers + one that dies mid-drain; lease recovery
    # still completes the full matrix.
    root = tempfile.mkdtemp(prefix="repro_b12k_")
    try:
        kill_lease = 1.0
        die_after = 0.05 if smoke else 0.15  # must land mid-drain
        procs = [
            mp.Process(target=_b12_worker,
                       args=(root, n_tasks, delay, "victim", kill_lease, die_after)),
            mp.Process(target=_b12_worker, args=(root, n_tasks, delay, "s1", kill_lease)),
            mp.Process(target=_b12_worker, args=(root, n_tasks, delay, "s2", kill_lease)),
        ]
        t0 = time.perf_counter()
        for p in procs:
            p.start()
        for p in procs:
            p.join(timeout=300)
        wall = time.perf_counter() - t0
        codes = sorted(p.exitcode for p in procs)
        matrix = {"parameters": {"i": expected}, "settings": {"delay": delay}}
        res = _b12_assemble(root).run_distributed(
            matrix, queue_dir=f"{root}/q", publish=False, lease_s=kill_lease
        )
        complete = sorted(r.value for r in res) == expected
        _row(
            f"B12_distributed_killrecovery_{n_tasks}tasks",
            wall * 1e6,
            f"exitcodes={codes} complete={complete} (lease recovery)",
            ok=complete,
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main(smoke: bool = False) -> None:
    print("name,us_per_call,derived")
    bench_matrix_expansion(smoke)
    if not smoke:
        bench_distributed()
    bench_parallel_speedup(smoke)
    bench_cache_speedup(smoke=smoke)
    bench_checkpoint_overhead(smoke=smoke)
    bench_failure_isolation()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="B1-B5 only, at their smoke sizes",
    )
    ap.add_argument(
        "--distributed-smoke", action="store_true",
        help="tiny B12 only: 1/2-process file-queue drain + kill-recovery row",
    )
    args = ap.parse_args()
    if args.distributed_smoke:
        print("name,us_per_call,derived")
        bench_distributed(smoke=True)
    else:
        main(smoke=args.smoke)
    if _FAILED:
        print(f"FAILED ROWS: {','.join(_FAILED)}", file=sys.stderr)
        sys.exit(1)
