"""Benchmark harness — one function per paper claim + roofline summaries.

The Memento paper's claims (demo paper, no numeric tables) map to:
  B1  configuration-matrix expansion scales to large experiment sets
      (including composed matrices: products, filters, derived params)
  B2  parallel execution beats sequential for embarrassingly-parallel tasks
  B3  result caching makes re-runs ~free
  B4  in-task checkpointing bounds lost work on interruption
  B5  failure isolation: one broken task does not poison a run
plus framework-level benchmarks, which since the Experiment API v2 run
*through* Memento via the ``repro.experiments`` adapters (so they exercise
caching/streaming/retries end-to-end, not hand-rolled loops):
  B6  per-kernel interpret-mode microbenches (us_per_call vs jnp oracle)
  B7  train-sweep cell wall time for a tiny model (CPU, smoke scale)
  B8  dry-run roofline summary (from the cached sweep, if present)
  B9  continuous-batching serve throughput under Poisson arrivals
  B10 paged-KV serving: mixed prompt sizes multiplexed over a fixed page
      pool vs the contiguous per-slot baseline (tokens/s, p50/p95 latency,
      peak cache bytes) — one matrix, ``paged`` as an axis
  B11 chunked prefill: mixed 32–4096-token prompts with the unified
      token-budget step on vs off — p50/p95 *inter-token* latency for
      in-flight decodes at equal throughput, ``chunk_budget`` as an axis
  B12 distributed drain: the same matrix drained through the file-queue by
      1/2/4 single-threaded worker processes on one shared tmpdir —
      tasks/s, speedup, and scaling efficiency; plus a kill-one-worker row
      showing lease recovery completing the matrix anyway
  B13 prompt-prefix sharing: warm vs cold TTFT + peak page bytes on a
      shared-system-prompt workload, ``prefix_sharing`` as an axis
  B14 speculative decoding: drafted multi-token steps with batched verify
      on the mixed-length Poisson workload — decode tokens per model step
      and inter-token latency, ``speculative`` as an axis, token identity
      asserted against the non-speculative row
  B16 layered serving core: 1x1 vs (data)x1 step times with slot ranges
      and pool slices partitioned across the data axis, plus the pure-host
      plan layer's us/step (``plan_us_per_step``, gated by policy.json)

Prints ``name,us_per_call,derived`` CSV rows, and **persists** every run
as a versioned record ``benchmarks/records/BENCH_<n>.json`` (rows + git
commit + timestamp + mode) — the repo's queryable perf trajectory (see
``repro.analysis.trajectory``). After writing, the run is auto-diffed
against the latest same-mode record on the current commit's *lineage*
and ``WARN,...`` lines flag >30% tok/s regressions.
Identity rows (B11/B13/B14 token mismatches) make the process exit
nonzero so CI cannot silently pass on corrupted outputs.

``--smoke`` runs B1–B5 at tiny sizes (seconds, no model compiles) plus
tiny B9/B10/B11/B13/B14 serve rows (one smoke-scale model compile) — the
CI end-to-end exercise of the experiment *and* serving layers.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time
from datetime import datetime, timezone


def _t(fn, n=3, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


# Every _row() call lands here; write_records() persists the run. Identity
# rows report ok=False on mismatch, which turns into a nonzero exit.
_RECORDS: list[dict] = []
_FAILED: list[str] = []
_RECORDS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "records")


def _row(name: str, us: float, derived: str = "", ok: bool = True,
         metrics: dict | None = None) -> None:
    print(f"{name},{us:.1f},{derived}")
    rec: dict = {
        "name": name,
        "value": round(us, 1),
        "unit": "us_per_call",
        "derived": derived,
        "ok": bool(ok),
    }
    # Examiner-style metric extraction: the throughput figure embedded in
    # the derived text becomes a first-class record field the perf diff
    # can compare across runs; ``metrics`` adds fields with no textual form
    # (anything named in benchmarks/policy.json must land here).
    m = re.search(r"([0-9][0-9.]*) tok/s", derived)
    if m:
        rec["tok_s"] = float(m.group(1))
    if metrics:
        rec.update({k: v for k, v in metrics.items() if v is not None})
    _RECORDS.append(rec)
    if not ok:
        _FAILED.append(name)


def _git_commit() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        )
        if out.returncode == 0:
            return out.stdout.strip()
    except OSError:
        pass
    return "unknown"


def write_records(mode: str, records_dir: str | None = None) -> str | None:
    """Persist this run's rows as the next ``BENCH_<n>.json`` record."""
    if not _RECORDS:
        return None
    d = records_dir or _RECORDS_DIR
    os.makedirs(d, exist_ok=True)
    ns = [
        int(m.group(1))
        for f in os.listdir(d)
        if (m := re.fullmatch(r"BENCH_(\d+)\.json", f))
    ]
    n = max(ns, default=0) + 1
    path = os.path.join(d, f"BENCH_{n}.json")
    payload = {
        "schema": 1,
        "record": n,
        "mode": mode,
        "git_commit": _git_commit(),
        "timestamp": datetime.now(timezone.utc).isoformat(timespec="seconds"),
        "rows": _RECORDS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"RECORD,{path},{len(_RECORDS)} rows")
    return path


def diff_records(new_path: str, records_dir: str | None = None) -> list[str]:
    """Diff ``new_path`` against its baseline; returns ``WARN,...`` lines
    for regressions under the checked-in policy thresholds.

    Delegates to ``repro.analysis.trajectory`` so these verdicts and the
    ``python -m repro.analysis regressions`` CLI are identical by
    construction. Thresholds come from ``benchmarks/policy.json`` (falling
    back to the built-in >30% tok/s rule if it's gone), so tightening a
    bound is a reviewed diff on the policy file, not a CI-config edit. The
    baseline is the latest earlier record of the same mode whose commit is
    on the current commit's lineage — a record produced on a diverged
    branch is never the comparison point. Rows are matched by name; rows
    where *either* side has no extracted value for a policy's metric are
    skipped, so a baseline without the metric can't fabricate a WARN.
    """
    from repro.analysis.trajectory import (
        BenchRecord,
        Trajectory,
        detect_regressions,
        find_baseline,
        load_policies,
    )

    new = BenchRecord.load(new_path)
    traj = Trajectory.load(records_dir or _RECORDS_DIR)
    baseline = find_baseline(traj, new)
    policies = load_policies(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "policy.json")
    )
    return [r.warn_line() for r in detect_regressions(new, baseline, policies)]


def _value(result):
    """Unwrap a TaskResult, surfacing the captured failure instead of a
    NoneType error on ``.value`` access."""
    if not result.ok:
        raise RuntimeError(
            f"benchmark task failed: {result.summary()}\n{result.traceback_str or ''}"
        )
    return result.value


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
    _row("B5_half_failing_run", us, f"ok={len(res.ok)} failed={len(res.failed)} isolated=True")


def bench_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, D = 1, 256, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    fa = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, blk_q=128, blk_k=128))
    rf_ = jax.jit(
        lambda q, k, v: ref.sdpa_ref(
            q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
            k.transpose(0, 2, 1, 3).reshape(B * H, S, D),
            v.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        )
    )
    us_k = _t(lambda: jax.block_until_ready(fa(q, k, v)))
    us_r = _t(lambda: jax.block_until_ready(rf_(q, k, v)))
    _row("B6_flash_attn_256_interp", us_k, f"oracle={us_r:.0f}us (interpret-mode CPU; TPU target)")

    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 256, 256)))
    b = jax.random.normal(ks[1], (2, 256, 256))
    rg = jax.jit(lambda a, b: ops.rglru_op(a, b, blk_t=128, blk_d=256))
    rr = jax.jit(lambda a, b: ref.rglru_ref(a, b))
    _row("B6_rglru_256x256_interp", _t(lambda: jax.block_until_ready(rg(a, b))),
         f"oracle={_t(lambda: jax.block_until_ready(rr(a, b))):.0f}us")


def bench_train_sweep() -> None:
    """B7: one training cell through Memento + experiments.train_sweep."""
    import shutil

    from repro.core import Memento, RunnerConfig
    from repro.experiments import train_matrix, train_sweep

    # Fresh checkpoint dir: a leftover final checkpoint would make the run
    # resume at its last step and train nothing.
    shutil.rmtree("/tmp/repro_bench_train", ignore_errors=True)
    matrix = train_matrix(
        ["llama3.2-3b"], lrs=[1e-3], steps=8, seq_len=64, global_batch=4,
        ckpt_every=1000, log_every=4,
        workdir="/tmp/repro_bench_train",
    )
    eng = Memento(
        train_sweep, namespace="train",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    t0 = time.perf_counter()
    res = eng.run(matrix, cache=False)
    us = (time.perf_counter() - t0) * 1e6
    v = _value(res[0])
    _row(
        "B7_train_sweep_smoke_llama", us,
        f"{v['tokens_per_s']:.0f} tok/s CPU smoke (incl compile), "
        f"loss {v['loss_first']:.3f} -> {v['loss_last']:.3f}",
    )


def bench_serve_throughput() -> None:
    """B9: continuous-batching scheduler under Poisson arrivals with mixed
    prompt lengths, driven through Memento + experiments.serve_sweep."""
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"n_slots": [4]},
        cache_len=64, n_requests=12, prompt_lens=(4, 8, 12),
        max_new_tokens=8, arrival_rate_hz=20.0, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    res = eng.run(matrix, cache=False)
    v = _value(res[0])
    _row(
        "B9_serve_poisson_12req_4slots",
        v["wall_s"] * 1e6,
        f"{v['tokens_per_s']:.1f} tok/s p50={v['latency_p50_s']*1e3:.0f}ms "
        f"p95={v['latency_p95_s']*1e3:.0f}ms decode_traces={v['decode_traces']}",
    )


def bench_serve_paged() -> None:
    """B10: paged-KV serving memory under mixed 32..2048-token prompts.

    One Memento matrix with ``paged`` as an axis replays the same workload
    through the page pool (sized at half the contiguous capacity) and the
    contiguous per-slot baseline; short requests pack around the long ones,
    and peak bytes track live pages, not n_slots x cache_len.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    cache_len, n_slots, page = 2176, 4, 64
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"paged": [False, True]},
        cache_len=cache_len, n_slots=n_slots, page_size=page,
        n_pages=(n_slots * cache_len) // (2 * page),
        n_requests=8, prompt_lens=(32, 64, 2048, 128, 32, 256, 512, 32),
        max_new_tokens=8, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        label = "paged" if v["paged"] else "contig"
        extra = (
            f"peak_cache_bytes={v['peak_cache_bytes']} "
            f"(contiguous_equiv={v['contiguous_cache_bytes']}) "
            f"deferred={v['deferred_admissions']} "
            if v["paged"]
            else f"cache_bytes={n_slots}x{cache_len} rows "
        )
        _row(
            f"B10_serve_{label}_8req_{n_slots}slots",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s p50={v['latency_p50_s']*1e3:.0f}ms "
            f"p95={v['latency_p95_s']*1e3:.0f}ms {extra}"
            f"decode_traces={v['decode_traces']}",
        )


def bench_serve_chunked(smoke: bool = False) -> None:
    """B11: chunked prefill vs whole-prompt prefill on a mixed-size prompt
    workload.

    One Memento matrix with ``chunk_budget`` as the axis replays the same
    Poisson-timed arrival trace — long prompts land *while short requests
    are mid-decode*, so each whole-prompt admission stalls every in-flight
    decode on the chunking-off row — and reports the p50/p95 *inter-token*
    latency streaming clients feel, at comparable throughput. Greedy token
    identity between the two rows is checked here too — the unified step
    is a scheduling change, not a sampling change.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    if smoke:
        cache_len, page, prompts, budget = 64, 8, (8, 40, 12, 33), 16
        rate = 0.0
    else:
        cache_len, page, budget, rate = 4224, 64, 256, 6.0
        prompts = (32, 32, 64, 4096, 32, 64, 2048, 32, 128, 32)
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"chunk_budget": [0, budget]},
        cache_len=cache_len, n_slots=4, page_size=page,
        n_requests=len(prompts), prompt_lens=prompts,
        max_new_tokens=8 if not smoke else 16,
        arrival_rate_hz=rate, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    tokens = {}
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        label = f"chunked_{v['chunk_budget']}" if v["chunk_budget"] else "chunking_off"
        tokens[label] = v["tokens"]
        _row(
            f"B11_serve_{label}_{len(prompts)}req",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s itl_p50={v['itl_p50_s']*1e3:.0f}ms "
            f"itl_p95={v['itl_p95_s']*1e3:.0f}ms chunk_steps={v['chunk_steps']} "
            f"chunk_traces={v['chunk_traces']} decode_traces={v['decode_traces']}",
        )
    vals = list(tokens.values())
    if len(vals) == 2:
        if vals[0] != vals[1]:
            _row("B11_token_identity", 0.0, "MISMATCH between chunked and off",
                 ok=False)
        else:
            _row("B11_token_identity", 0.0, "identical tokens")


def bench_serve_prefix(smoke: bool = False) -> None:
    """B13: prompt-prefix sharing on a shared-system-prompt workload.

    One Memento matrix with ``prefix_sharing`` as the axis drives the same
    workload in which every prompt starts with one shared system prompt. A
    primer request registers the prefix pages before the timed window (its
    solo TTFT is reported as ttft_cold); with sharing on, every timed
    request adopts the registered pages instead of recomputing them —
    warm-prefix TTFT drops below the no-sharing arm's cold-prefix TTFT on
    the identical contended workload, and peak page bytes drop below the
    no-sharing baseline because N slots map one physical copy of the
    prefix. Greedy token identity between the two rows is asserted:
    sharing is a memory/latency change, not a sampling change.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    if smoke:
        cache_len, page, budget, shared_len = 96, 8, 16, 32
        prompts, rate, max_new = (4, 9, 6, 4), 0.0, 4
    else:
        cache_len, page, budget, shared_len = 4224, 64, 256, 1024
        prompts, rate, max_new = (32, 64, 32, 128, 32, 64, 32, 96), 6.0, 8
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"prefix_sharing": [False, True]},
        cache_len=cache_len, n_slots=4, page_size=page, chunk_budget=budget,
        n_requests=len(prompts), prompt_lens=prompts,
        shared_prefix_len=shared_len, prime_prefix=True,
        max_new_tokens=max_new, arrival_rate_hz=rate, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    rows = {}
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        label = "sharing_on" if v["prefix_sharing"] else "sharing_off"
        rows[label] = v
        warm = v["ttft_warm_p50_s"] or v["ttft_p50_s"]
        _row(
            f"B13_serve_prefix_{label}_{len(prompts)}req",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s ttft_cold={v['ttft_cold_s']*1e3:.0f}ms "
            f"ttft_warm_p50={warm*1e3:.0f}ms prefix_hits={v['prefix_hits']} "
            f"hit_tokens={v['prefix_hit_tokens']} "
            f"peak_cache_bytes={v['peak_cache_bytes']}",
        )
    if len(rows) == 2:
        on, off = rows["sharing_on"], rows["sharing_off"]
        if on["tokens"] != off["tokens"]:
            _row("B13_token_identity", 0.0, "MISMATCH between sharing on and off",
                 ok=False)
        else:
            _row("B13_token_identity", 0.0, "identical tokens")
        # cold baseline = the sharing-off arm's TTFT p50: the same timed
        # requests under the same contention, just with cold prefixes (the
        # primer's solo ttft_cold is uncontended and not comparable)
        warm_lt_cold = (on["ttft_warm_p50_s"] or float("inf")) < off["ttft_p50_s"]
        mem_lt_off = on["peak_cache_bytes"] < off["peak_cache_bytes"]
        _row(
            "B13_prefix_wins", 0.0,
            f"warm_ttft_lt_cold={warm_lt_cold} "
            f"({(on['ttft_warm_p50_s'] or 0) * 1e3:.0f}ms vs "
            f"{off['ttft_p50_s'] * 1e3:.0f}ms) "
            f"peak_bytes_lt_nosharing={mem_lt_off} "
            f"({on['peak_cache_bytes']} vs {off['peak_cache_bytes']})",
        )


def bench_serve_spec(smoke: bool = False) -> None:
    """B14: speculative decoding on the mixed-length Poisson workload.

    One Memento matrix with ``speculative`` as the axis replays the same
    arrival trace with and without drafted multi-token steps. The drafter
    is the oracle ReplayDrafter (a muted reference pass collects the
    greedy continuations first), so the row measures the substrate —
    batched verify, rollback, page growth — at the high-acceptance end
    rather than any particular draft heuristic; prefix sharing is off so
    the oracle's reference pass cannot warm the timed rows. Reports
    decode tokens per model step (the figure speculation improves: each
    verify call emits accepted+1 tokens) and inter-token latency; greedy
    token identity between the two rows is asserted — acceptance keeps
    exactly the longest run matching what sequential decode would emit.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    if smoke:
        cache_len, page, budget = 96, 8, 16
        prompts, rate, max_new = (6, 20, 9, 14, 32, 12), 20.0, 16
    else:
        cache_len, page, budget, rate = 4224, 64, 256, 6.0
        prompts = (32, 32, 64, 2048, 32, 64, 1024, 32, 128, 32)
        max_new = 32
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"speculative": [False, True]},
        cache_len=cache_len, n_slots=4, page_size=page, chunk_budget=budget,
        n_requests=len(prompts), prompt_lens=prompts,
        max_new_tokens=max_new, arrival_rate_hz=rate,
        draft_k=7, drafter="oracle", prefix_sharing=False, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    rows = {}
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        label = "spec_on" if v["speculative"] else "spec_off"
        rows[label] = v
        extra = (
            f"spec_steps={v['spec_steps']} replays={v['spec_replays']} "
            f"accept_rate={(v['accept_rate'] or 0.0):.2f} "
            f"fallbacks={v['spec_fallbacks']} verify_traces={v['verify_traces']} "
            if v["speculative"]
            else f"decode_steps={v['decode_steps']} "
        )
        _row(
            f"B14_serve_{label}_{len(prompts)}req",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s "
            f"tok_per_step={v['tokens_per_model_step']:.2f} "
            f"itl_p50={v['itl_p50_s']*1e3:.1f}ms {extra}",
        )
    if len(rows) == 2:
        on, off = rows["spec_on"], rows["spec_off"]
        if on["tokens"] != off["tokens"]:
            _row("B14_token_identity", 0.0,
                 "MISMATCH between speculative and off", ok=False)
        else:
            _row("B14_token_identity", 0.0, "identical tokens")
        ratio = on["tokens_per_model_step"] / off["tokens_per_model_step"]
        itl_better = on["itl_p50_s"] <= off["itl_p50_s"]
        # The ratio is count-based (tokens / model steps), not wall-clock,
        # so the >=1.5x bar is deterministic given the oracle drafter; ITL
        # is wall-clock and reported informationally.
        _row(
            "B14_spec_wins", 0.0,
            f"tok_per_step={ratio:.2f}x (>=1.5x required) "
            f"itl_p50_improved={itl_better} "
            f"({on['itl_p50_s']*1e3:.1f}ms vs {off['itl_p50_s']*1e3:.1f}ms)",
            ok=ratio >= 1.5,
        )


def _prediction(v: dict) -> str:
    """The roofline prediction next to a measured row, or why there is none
    (serve_sweep predicts only on a device kind with published peaks)."""
    pred = v["predicted_step_ms"]
    if pred is None:
        return f"pred=none (no peaks for {v['device']['kind']})"
    return (
        f"pred={pred:.3f}ms ({v['predicted_bottleneck']}-bound) "
        f"ratio={v['itl_p50_s']*1e3/pred:.0f}x"
    )


def bench_serve_sharded(smoke: bool = False) -> None:
    """B15: tensor-parallel sharded stepping vs the single-device step.

    One Memento matrix with ``mesh_shape`` as the axis replays the same
    greedy workload on 1 device and on a (1, model) test mesh (forced host
    devices off-TPU). Greedy token identity across meshes is asserted —
    sharded stepping must be a pure layout change — along with unchanged
    decode/chunk trace counts (one compile per bucket, never per mesh).
    Each row reports measured inter-token latency next to the analytic
    roofline prediction for that mesh (launch/roofline.py): on forced host
    devices the measured/predicted ratio is meaningless in magnitude, but
    the per-mesh predictions are exactly what a real v5e run would be
    gated on.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep
    from repro.launch.mesh import devices_required

    model = 2 if smoke else 4
    if not devices_required(model):
        _row(
            "B15_serve_sharded", 0.0,
            f"skipped: needs {model} XLA devices, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={model} "
            "before running (CI sharded-smoke lane does)",
        )
        return
    if smoke:
        cache_len, page, budget, max_new = 96, 8, 16, 8
        prompts = (6, 20, 9, 14)
    else:
        cache_len, page, budget, max_new = 512, 16, 64, 16
        prompts = (16, 48, 24, 96, 32, 8)
    meshes = ["1x1", f"1x{model}"]
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"mesh_shape": meshes},
        cache_len=cache_len, n_slots=4, page_size=page, chunk_budget=budget,
        n_requests=len(prompts), prompt_lens=prompts,
        max_new_tokens=max_new, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    rows = {}
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        rows[v["mesh"]] = v
        _row(
            f"B15_serve_sharded_{v['mesh']}",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s "
            f"itl_p50={v['itl_p50_s']*1e3:.1f}ms "
            f"{_prediction(v)} "
            f"decode_traces={v['decode_traces']} "
            f"chunk_traces={v['chunk_traces']} devices={v['mesh_devices']}",
        )
    if len(rows) == len(meshes):
        base = rows[meshes[0]]
        sharded = rows[meshes[1]]
        if base["tokens"] != sharded["tokens"]:
            _row("B15_sharded_token_identity", 0.0,
                 f"MISMATCH between {meshes[0]} and {meshes[1]}", ok=False)
        else:
            _row("B15_sharded_token_identity", 0.0,
                 f"identical tokens across {' vs '.join(meshes)}")
        traces_ok = (
            base["decode_traces"] == sharded["decode_traces"]
            and base["chunk_traces"] == sharded["chunk_traces"]
        )
        _row(
            "B15_sharded_trace_bound", 0.0,
            f"decode_traces {base['decode_traces']}=={sharded['decode_traces']} "
            f"chunk_traces {base['chunk_traces']}=={sharded['chunk_traces']} "
            "(one compile per bucket, never per mesh)",
            ok=traces_ok,
        )


def bench_serve_layered(smoke: bool = False) -> None:
    """B16: layered serving core — data-parallel slots + planner overhead.

    One Memento matrix with ``mesh_shape`` as the axis replays the same
    greedy workload on one device and on a (data, 1) mesh, where each data
    shard owns a contiguous slot range and its own page-pool slice (the
    layered core's data-axis partitioning; ``data > 1`` used to merely
    replicate pool state). Greedy token identity across the two rows is
    asserted — partitioning is a layout change, not a scheduling change —
    and each row reports the pure-host plan layer's cost per scheduler
    step next to the step time. ``plan_us_per_step`` is persisted as a
    record field gated by benchmarks/policy.json: the planner must stay
    microseconds against millisecond device steps, and a doubling is a
    regression even when tok/s holds.
    """
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep
    from repro.launch.mesh import devices_required

    data = 2
    if not devices_required(data):
        _row(
            "B16_serve_layered", 0.0,
            f"skipped: needs {data} XLA devices, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data} "
            "before running (CI sharded-smoke lane does)",
        )
        return
    if smoke:
        cache_len, page, budget, max_new = 96, 8, 16, 8
        prompts = (6, 20, 9, 14)
    else:
        cache_len, page, budget, max_new = 512, 16, 64, 16
        prompts = (16, 48, 24, 96, 32, 8)
    meshes = ["1x1", f"{data}x1"]
    matrix = serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"mesh_shape": meshes},
        cache_len=cache_len, n_slots=4, page_size=page, chunk_budget=budget,
        n_requests=len(prompts), prompt_lens=prompts,
        max_new_tokens=max_new, warmup=True,
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    rows = {}
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        rows[v["mesh"]] = v
        _row(
            f"B16_serve_layered_{v['mesh']}",
            v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s "
            f"itl_p50={v['itl_p50_s']*1e3:.1f}ms "
            f"plan={v['plan_us_per_step']:.0f}us/step "
            f"({(v['plan_frac'] or 0.0)*100:.1f}% of wall) "
            f"decode_traces={v['decode_traces']} devices={v['mesh_devices']}",
            metrics={"plan_us_per_step": v["plan_us_per_step"]},
        )
    if len(rows) == len(meshes):
        base, dp = rows[meshes[0]], rows[meshes[1]]
        if base["tokens"] != dp["tokens"]:
            _row("B16_layered_token_identity", 0.0,
                 f"MISMATCH between {meshes[0]} and {meshes[1]}", ok=False)
        else:
            _row("B16_layered_token_identity", 0.0,
                 f"identical tokens across {' vs '.join(meshes)}")


def bench_serve_smoke() -> None:
    """Tiny B9/B10/B11 rows for CI: one smoke-scale model, second-scale
    workloads, still through Memento + serve_sweep end-to-end."""
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    matrix = (
        serve_matrix(
            ["llama3.2-3b"], backends=["xla"],
            scheduler={"paged": [False, True]},
            cache_len=64, n_slots=2, n_requests=4, prompt_lens=(4, 9, 17, 6),
            max_new_tokens=4, warmup=False,
        )
        + serve_matrix(
            ["llama3.2-3b"], backends=["xla"],
            scheduler={"chunk_budget": [16]},
            cache_len=64, n_slots=2, page_size=8, n_requests=3,
            prompt_lens=(40, 8, 21), max_new_tokens=4, warmup=False,
        )
    )
    eng = Memento(
        serve_sweep, namespace="serve",
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    for r in eng.run(matrix, cache=False):
        v = _value(r)
        if v.get("chunk_budget"):
            label = "B11_smoke_chunked"
            extra = f"chunk_steps={v['chunk_steps']} chunk_traces={v['chunk_traces']}"
        elif v["paged"]:
            label = "B10_smoke_paged"
            extra = f"peak_cache_bytes={v['peak_cache_bytes']}"
        else:
            label = "B9_smoke_contig"
            extra = f"p95={v['latency_p95_s']*1e3:.0f}ms"
        _row(
            label, v["wall_s"] * 1e6,
            f"{v['tokens_per_s']:.1f} tok/s decode_traces={v['decode_traces']} {extra}",
        )


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
        )
        assert complete, "kill-one-worker run did not complete the matrix"
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_roofline_summary() -> None:
    try:
        from repro.launch.report import load_results

        rows, skipped = load_results()
    except Exception as e:
        _row("B8_roofline", 0.0, f"no cached sweep ({e})")
        return
    sp = [v for v in rows if v.get("mesh") == "16x16" and v.get("roofline")]
    for v in sorted(sp, key=lambda v: (v["arch"], v["shape"])):
        r = v["roofline"]
        _row(
            f"B8_{v['arch']}_{v['shape']}",
            r["step_time_lower_bound"] * 1e6,
            f"bottleneck={r['bottleneck']} roofline_frac={r['roofline_fraction']:.3f}",
        )


def main(smoke: bool = False) -> None:
    print("name,us_per_call,derived")
    bench_matrix_expansion(smoke)
    if not smoke:
        bench_distributed()
    bench_parallel_speedup(smoke)
    bench_cache_speedup(smoke=smoke)
    bench_checkpoint_overhead(smoke=smoke)
    bench_failure_isolation()
    if smoke:
        bench_serve_smoke()
        bench_serve_prefix(smoke=True)
        bench_serve_spec(smoke=True)
        return
    bench_kernels()
    bench_train_sweep()
    bench_serve_throughput()
    bench_serve_paged()
    bench_serve_chunked()
    bench_serve_prefix()
    bench_serve_spec()
    bench_serve_sharded()
    bench_serve_layered()
    bench_roofline_summary()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke", action="store_true",
        help="B1-B5 only, tiny sizes (CI end-to-end exercise of the experiment layer)",
    )
    ap.add_argument(
        "--distributed-smoke", action="store_true",
        help="tiny B12 only: 1/2-process file-queue drain + kill-recovery row",
    )
    ap.add_argument(
        "--sharded-smoke", action="store_true",
        help="tiny B15+B16 only: sharded vs 1-device stepping and the "
        "data-parallel layered core (needs forced host devices: "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
    )
    ap.add_argument(
        "--records-dir", default=None,
        help="where BENCH_<n>.json records land (default: benchmarks/records)",
    )
    ap.add_argument(
        "--no-records", action="store_true",
        help="print rows only, do not persist a BENCH_<n>.json record",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if args.distributed_smoke:
        print("name,us_per_call,derived")
        bench_distributed(smoke=True)
        mode = "distributed-smoke"
    elif args.sharded_smoke:
        print("name,us_per_call,derived")
        bench_serve_sharded(smoke=True)
        bench_serve_layered(smoke=True)
        mode = "sharded-smoke"
    else:
        main(smoke=args.smoke)
        mode = "smoke" if args.smoke else "full"
    if not args.no_records:
        path = write_records(mode, args.records_dir)
        if path:
            for w in diff_records(path, args.records_dir):
                print(w)
    if _FAILED:
        print(f"IDENTITY/WIN FAILURES: {','.join(_FAILED)}", file=sys.stderr)
        sys.exit(1)
