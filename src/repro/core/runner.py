"""Parallel task runner: streaming results, retries, hard timeouts,
straggler speculation.

Thread mode is the default — the heavy tasks in this framework (XLA
lower/compile, filesystem IO, JAX dispatch) all release the GIL, so threads
give real parallelism while sharing the in-process device state. Process mode
exists for python-bound workloads (requires the experiment function and task
parameters to be picklable / module-level).

``stream()`` is the primary entry: a generator that yields each task's final
``TaskResult`` the moment it is known — cache hits first, then live results
in completion order. ``run()`` is a thin collector over it that restores
matrix order.

Fault model (beyond the paper, needed at cluster scale):
  * a task raising       -> captured traceback, retried up to the budget
  * a task hanging       -> hard timeout, the attempt is abandoned (the thread
                            is left to die with the process), retried/marked
  * a straggler          -> speculative duplicate attempt once the runtime
                            exceeds ``straggler_factor`` x median of completed
                            peers; first finisher wins, tasks must be
                            idempotent (they are: pure functions + atomic
                            caches + versioned checkpoints)
  * the whole host dying -> handled one level up by the file-queue runner
                            (lease expiry) and by task checkpoints

Attempt accounting is per *task*, not per submission: every submission
(primary, retry, or speculative duplicate) is one attempt, and a task is
finalised as failed once ``retries + 1`` attempts have failed — a failed
primary whose speculative twin also fails consumes two entries of the
budget, not one. All finalisation decisions happen under one lock.
"""
from __future__ import annotations

import concurrent.futures as cf
import multiprocessing
import os
import statistics
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Sequence

from .cache import BaseCache, NullCache, param_repr
from .matrix import TaskSpec
from .notifications import Event, NotificationProvider
from .task import Context, TaskCheckpointStore, TaskResult


@dataclass
class RunnerConfig:
    max_workers: int | None = None  # None -> os.cpu_count()
    mode: str = "thread"  # "thread" | "process"
    retries: int = 1  # extra attempts after the first failure
    retry_backoff_s: float = 0.25
    task_timeout_s: float | None = None  # hard per-attempt timeout
    straggler_factor: float = 3.0
    straggler_min_s: float = 30.0
    enable_speculation: bool = True
    max_speculative: int = 4  # concurrent duplicate attempts across the run
    fail_fast: bool = False
    poll_interval_s: float = 0.05

    def resolved_workers(self) -> int:
        if self.max_workers is not None:
            return max(1, self.max_workers)
        return max(1, os.cpu_count() or 1)


@dataclass
class _Attempt:
    spec: TaskSpec
    number: int  # 1-based attempt number (per task, across twins/retries)
    future: cf.Future
    started: float
    speculative: bool = False
    last_beat: float = field(default_factory=time.time)
    abandoned: bool = False
    seen: bool = False  # harvested by the supervision loop
    finished: float = 0.0  # stamped by a done-callback, not at harvest time

    @property
    def wall_s(self) -> float:
        # Harvest may lag completion when a stream consumer is slow; the
        # done-callback stamp keeps task timings honest regardless.
        return (self.finished or time.time()) - self.started


def _run_task(
    func: Callable[[Context], Any],
    spec: TaskSpec,
    ckpt_root: str | None,
    attempt: int,
    beat: Callable[[], None] | None,
    progress_cb: Callable[[str], None] | None,
) -> Any:
    ckpts = TaskCheckpointStore(ckpt_root, spec.key) if ckpt_root else None
    ctx = Context(
        spec=spec,
        checkpoints=ckpts,
        attempt=attempt,
        progress_cb=progress_cb,
        _heartbeat=beat,
    )
    return func(ctx)


class Runner:
    """Executes a list of TaskSpecs under a RunnerConfig."""

    def __init__(
        self,
        func: Callable[[Context], Any],
        cache: BaseCache | None = None,
        provider: NotificationProvider | None = None,
        config: RunnerConfig | None = None,
        checkpoint_root: str | None = None,
        manifest_extra: dict[str, Any] | None = None,
    ):
        self.func = func
        # NOT `cache or NullCache()`: an empty FsCache is len()==0 == falsy.
        self.cache = cache if cache is not None else NullCache()
        self.provider = provider
        self.config = config or RunnerConfig()
        self.checkpoint_root = checkpoint_root
        # Folded into every cache manifest (e.g. the Memento namespace, so
        # per-axis invalidation can respect namespace partitions).
        self.manifest_extra = dict(manifest_extra or {})
        self.stats: dict[str, Any] = {}

    def _manifest(self, spec: TaskSpec, **extra: Any) -> dict[str, Any]:
        return {
            "params": {k: param_repr(v) for k, v in spec.params.items()},
            **self.manifest_extra,
            **extra,
        }

    # -- notifications ------------------------------------------------------
    def _notify(self, kind: str, message: str, **payload: Any) -> None:
        if self.provider is None:
            return
        try:
            self.provider.notify(Event(kind=kind, message=message, payload=payload))
        except Exception:
            pass  # providers must never take the run down

    def _notify_finished(self, res: TaskResult) -> None:
        if self.provider is None:
            return
        try:
            self.provider.task_finished(res)
        except Exception:
            pass

    # -- main entries ---------------------------------------------------------
    def run(self, specs: Sequence[TaskSpec], force: bool = False) -> list[TaskResult]:
        """Blocking collector over :meth:`stream`, restoring spec order."""
        results = {r.spec.key: r for r in self.stream(specs, force=force)}
        ordered: list[TaskResult] = []
        seen: set[str] = set()
        for s in specs:
            if s.key in results and s.key not in seen:
                seen.add(s.key)
                ordered.append(results[s.key])
        return ordered

    def stream(
        self, specs: Sequence[TaskSpec], force: bool = False
    ) -> Iterator[TaskResult]:
        """Yield each task's final TaskResult as soon as it is known.

        Cache hits are yielded immediately (before any execution starts);
        live results follow in completion order. Duplicate keys in ``specs``
        are collapsed to the first occurrence.

        The supervision loop (timeouts, retries, speculation) runs between
        yields, so it is paced by the consumer: task *timings* stay honest
        (completion is stamped by a done-callback), but a consumer that
        blocks for a long time between results delays timeout/retry
        enforcement — do heavy per-result work elsewhere, or collect with
        :meth:`run`.
        """
        cfg = self.config
        t_run0 = time.time()
        self.stats = {}
        self._notify(
            "run_started",
            f"{len(specs)} tasks, {cfg.resolved_workers()} workers",
            total=len(specs),
            workers=cfg.resolved_workers(),
            mode=cfg.mode,
        )

        n_ok = n_failed = n_cached = 0
        to_run: list[TaskSpec] = []
        seen_keys: set[str] = set()
        for spec in specs:
            if spec.key in seen_keys:
                continue
            seen_keys.add(spec.key)
            entry = None if force else self.cache.get(spec.key)
            if entry is not None:
                n_ok += 1
                n_cached += 1
                yield TaskResult(
                    spec=spec, status="cached", value=entry.value, wall_s=0.0
                )
            else:
                to_run.append(spec)

        live = (
            self._stream_processes(to_run)
            if cfg.mode == "process"
            else self._stream_threads(to_run)
        )
        try:
            for res in live:
                if res.ok:
                    n_ok += 1
                else:
                    n_failed += 1
                yield res
        finally:
            live.close()
            wall = time.time() - t_run0
            self.stats = {
                "tasks": len(seen_keys),
                "ok": n_ok,
                "failed": n_failed,
                "cached": n_cached,
                "wall_s": wall,
                "speculative_launched": self.stats.get("speculative_launched", 0),
            }
            self._notify(
                "run_finished",
                f"{n_ok} ok / {n_failed} failed in {wall:.1f}s",
                **{k: v for k, v in self.stats.items() if k != "tasks"},
            )

    # -- thread mode (full feature set) ---------------------------------------
    def _stream_threads(self, specs: Sequence[TaskSpec]) -> Iterator[TaskResult]:
        if not specs:
            return
        yield from self.stream_source(iter(specs))

    def stream_source(
        self, source: "Iterator[TaskSpec | None]"
    ) -> Iterator[TaskResult]:
        """Thread-mode streaming over an *incremental* spec source.

        ``source`` is pulled between supervision rounds: each ``TaskSpec`` it
        yields is submitted to the pool immediately; ``None`` means "nothing
        available right now, ask again next round" (the pull resumes on the
        following round); exhaustion (``StopIteration``) means no further
        specs will ever arrive. The stream terminates once the source is
        exhausted and every submitted task is finalised.

        This is what lets an external work feed — the distributed file-queue
        claim loop — drive the full local machinery (thread pool, retries,
        hard timeouts, straggler speculation) instead of a one-task-at-a-time
        loop. A plain ``iter(list_of_specs)`` reproduces :meth:`run` exactly.

        Re-yielding a key that was already finalised resets that task's
        attempt state and runs it afresh — the distributed driver uses this
        for queue-level (cross-host) retry rounds. Only re-feed a key after
        consuming its previous final result.
        """
        cfg = self.config
        n_spec_launched = 0
        attempts_failed: dict[str, int] = {}  # failed attempts per task
        submitted: dict[str, TaskSpec] = {}
        source_exhausted = False
        retry_at: list[tuple[float, TaskSpec]] = []
        attempts: dict[str, list[_Attempt]] = {}
        done_keys: set[str] = set()
        completed_durations: list[float] = []
        fresh: list[TaskResult] = []  # finalised since the last yield round
        lock = threading.Lock()

        def make_beat(holder: _Attempt) -> Callable[[], None]:
            def beat() -> None:
                holder.last_beat = time.time()

            return beat

        pool = cf.ThreadPoolExecutor(max_workers=cfg.resolved_workers())
        try:

            def submit(spec: TaskSpec, speculative: bool = False) -> None:
                submitted[spec.key] = spec
                attempts_failed.setdefault(spec.key, 0)
                number = len(attempts.get(spec.key, [])) + 1
                holder = _Attempt(
                    spec=spec,
                    number=number,
                    future=None,  # type: ignore[arg-type]
                    started=time.time(),
                    speculative=speculative,
                )
                holder.future = pool.submit(
                    _run_task,
                    self.func,
                    spec,
                    self.checkpoint_root,
                    number,
                    make_beat(holder),
                    None,
                )
                holder.future.add_done_callback(
                    lambda _f, h=holder: setattr(h, "finished", time.time())
                )
                attempts.setdefault(spec.key, []).append(holder)
                self._notify(
                    "task_started",
                    spec.describe() + (" [speculative]" if speculative else ""),
                    key=spec.key,
                    attempt=number,
                )

            def admit(spec: TaskSpec) -> None:
                with lock:
                    if spec.key in done_keys:
                        # Re-fed after finalisation (queue-level retry):
                        # forget the previous round's attempt state.
                        done_keys.discard(spec.key)
                        attempts_failed[spec.key] = 0
                        attempts.pop(spec.key, None)
                submit(spec)

            def record_success(att: _Attempt, value: Any) -> None:
                with lock:
                    if att.spec.key in done_keys:
                        return
                    done_keys.add(att.spec.key)
                wall = att.wall_s
                completed_durations.append(wall)
                res = TaskResult(
                    spec=att.spec,
                    status="ok",
                    value=value,
                    attempts=len(attempts.get(att.spec.key, [])) or att.number,
                    started_unix=att.started,
                    wall_s=wall,
                    speculative=att.speculative,
                )
                fresh.append(res)
                try:
                    self.cache.put(
                        att.spec.key,
                        value,
                        manifest=self._manifest(
                            att.spec, wall_s=wall, attempts=att.number
                        ),
                    )
                except Exception as e:
                    self._notify("cache_error", f"{att.spec.key[:12]}: {e}")
                self._notify_finished(res)

            def record_failure(att: _Attempt, exc: BaseException | None, status: str) -> None:
                """Handle a failed/timed-out attempt: retry or finalise.

                The whole decision — duplicate-completion check, per-task
                attempt accounting, retry-vs-finalise — happens under the
                lock so concurrent completions can neither double-finalise
                nor under-count failed attempts.
                """
                key = att.spec.key
                with lock:
                    if key in done_keys:
                        return
                    attempts_failed[key] += 1
                    live_twins = [
                        a
                        for a in attempts.get(key, [])
                        if a is not att and not a.future.done() and not a.abandoned
                    ]
                    if live_twins:
                        # A duplicate attempt is still running and may yet
                        # succeed; its completion drives the next decision.
                        # This attempt's failure stays counted above.
                        return
                    if attempts_failed[key] <= cfg.retries:
                        self._notify(
                            "task_retry",
                            f"{att.spec.describe()} attempt {att.number} {status}; retrying",
                            key=key,
                            attempt=att.number + 1,
                        )
                        retry_at.append((time.time() + cfg.retry_backoff_s, att.spec))
                        return
                    done_keys.add(key)
                total_attempts = len(attempts.get(key, [])) or att.number
                if exc is not None:
                    res = TaskResult.from_exception(att.spec, exc, total_attempts, att.started)
                else:
                    res = TaskResult(
                        spec=att.spec,
                        status=status,
                        error=f"attempt exceeded {cfg.task_timeout_s}s",
                        attempts=total_attempts,
                        started_unix=att.started,
                        wall_s=att.wall_s,
                    )
                fresh.append(res)
                self._notify_finished(res)

            # -- supervision loop ---------------------------------------------
            failed_seen = False
            while True:
                # Pull newly available work. A list source is drained whole on
                # the first round (the classic submit-everything-upfront); an
                # incremental source hands over what it has and yields None.
                if not source_exhausted:
                    while True:
                        try:
                            item = next(source)
                        except StopIteration:
                            source_exhausted = True
                            break
                        if item is None:
                            break  # nothing available this round
                        admit(item)
                with lock:
                    n_done = len(done_keys)
                if source_exhausted and n_done == len(submitted):
                    break
                if cfg.fail_fast and failed_seen:
                    break

                now = time.time()
                # due retries
                due = [r for r in retry_at if r[0] <= now]
                for item in due:
                    retry_at.remove(item)
                    _, spec = item
                    if spec.key not in done_keys:
                        submit(spec)

                live: list[_Attempt] = [
                    a
                    for atts in attempts.values()
                    for a in atts
                    if not a.future.done() and not a.abandoned
                ]

                # hard timeouts
                if cfg.task_timeout_s is not None:
                    for att in live:
                        if now - att.started > cfg.task_timeout_s:
                            att.abandoned = True
                            att.future.cancel()
                            self._notify(
                                "task_timeout",
                                f"{att.spec.describe()} abandoned after "
                                f"{cfg.task_timeout_s:.1f}s",
                                key=att.spec.key,
                            )
                            record_failure(att, None, "timeout")

                # harvest finished futures BEFORE deciding to speculate, so a
                # just-failed twin is accounted for and not treated as "this
                # task has no duplicate yet".
                finished = [
                    a
                    for atts in attempts.values()
                    for a in atts
                    if a.future.done() and not a.abandoned and not a.seen
                ]
                for att in finished:
                    att.seen = True
                    if att.future.cancelled():
                        continue
                    exc = att.future.exception()
                    if exc is None:
                        record_success(att, att.future.result())
                    else:
                        self._notify(
                            "task_attempt_failed",
                            f"{att.spec.describe()} attempt {att.number}: {exc}",
                            key=att.spec.key,
                        )
                        record_failure(att, exc, "failed")

                # straggler speculation
                if (
                    cfg.enable_speculation
                    and len(completed_durations) >= 3
                    and n_spec_launched < cfg.max_speculative
                ):
                    median = statistics.median(completed_durations)
                    threshold = max(cfg.straggler_min_s, cfg.straggler_factor * median)
                    for att in live:
                        if att.speculative or att.spec.key in done_keys:
                            continue
                        if attempts_failed[att.spec.key] > 0:
                            # Speculation is for stragglers, not flaky tasks:
                            # once an attempt has *failed*, further duplicates
                            # would just burn the retry budget.
                            continue
                        twins = attempts.get(att.spec.key, [])
                        if sum(1 for a in twins if not a.future.done()) > 1:
                            continue  # already speculated
                        if now - att.started > threshold:
                            n_spec_launched += 1
                            self.stats["speculative_launched"] = n_spec_launched
                            self._notify(
                                "straggler_respawned",
                                f"{att.spec.describe()} running {now - att.started:.1f}s "
                                f"(median {median:.1f}s); launching duplicate",
                                key=att.spec.key,
                            )
                            submit(att.spec, speculative=True)
                            if n_spec_launched >= cfg.max_speculative:
                                break

                # stream out everything finalised this round
                if fresh:
                    for res in fresh:
                        if not res.ok:
                            failed_seen = True
                        yield res
                    fresh.clear()
                elif not finished and not due:
                    time.sleep(cfg.poll_interval_s)

            for res in fresh:
                yield res
            fresh.clear()

            # drop any still-running abandoned attempts on the floor: cancel
            # what never started and do NOT wait for hung threads (they are
            # joined at interpreter exit; the fleet answer is process kill).
            for atts in attempts.values():
                for a in atts:
                    if not a.future.done():
                        a.future.cancel()
        finally:
            pool.shutdown(wait=False, cancel_futures=True)

    # -- process mode (no speculation/heartbeat; picklable funcs only) --------
    def _stream_processes(self, specs: Sequence[TaskSpec]) -> Iterator[TaskResult]:
        if not specs:
            return
        cfg = self.config
        # Workers are spawned, never forked: a forked child of a process that
        # has started JAX inherits its threads and its hold on the device.
        with cf.ProcessPoolExecutor(
            max_workers=cfg.resolved_workers(),
            mp_context=multiprocessing.get_context("spawn"),
        ) as pool:
            fut_to_spec: dict[cf.Future, tuple[TaskSpec, float, int]] = {}
            for spec in specs:
                fut = pool.submit(_run_task, self.func, spec, self.checkpoint_root, 1, None, None)
                fut_to_spec[fut] = (spec, time.time(), 1)
            failures_left = {s.key: cfg.retries for s in specs}
            while fut_to_spec:
                done, _ = cf.wait(
                    list(fut_to_spec), timeout=1.0, return_when=cf.FIRST_COMPLETED
                )
                for fut in done:
                    spec, started, number = fut_to_spec.pop(fut)
                    exc = fut.exception()
                    res: TaskResult | None = None
                    if exc is None:
                        value = fut.result()
                        res = TaskResult(
                            spec=spec,
                            status="ok",
                            value=value,
                            attempts=number,
                            started_unix=started,
                            wall_s=time.time() - started,
                        )
                        try:
                            self.cache.put(
                                spec.key, value,
                                manifest=self._manifest(spec, wall_s=res.wall_s),
                            )
                        except Exception:
                            pass
                    elif failures_left[spec.key] > 0:
                        failures_left[spec.key] -= 1
                        nf = pool.submit(
                            _run_task, self.func, spec, self.checkpoint_root, number + 1, None, None
                        )
                        fut_to_spec[nf] = (spec, time.time(), number + 1)
                    else:
                        res = TaskResult.from_exception(spec, exc, number, started)
                    if res is not None:
                        self._notify_finished(res)
                        yield res
