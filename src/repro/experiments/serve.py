"""Serving sweeps as Memento experiment functions.

One task = one scheduler configuration driven over a deterministic synthetic
workload (optionally Poisson-timed), returning throughput/latency/memory
metrics as a plain dict — picklable, cacheable, and comparable across the
matrix. Every knob is read from the task's params first, then its settings,
then a default, so any knob can be swept as a matrix axis or fixed for the
whole sweep.

Axes/settings understood by :func:`serve_sweep`:

  arch (required)        registry name, e.g. "llama3.2-3b"
  attn_backend           "xla" | "pallas" (default: the config's own)
  n_slots, cache_len     scheduler shape (defaults 4, 128)
  page_size, n_pages,    page-pool knobs (defaults 16, capacity parity)
  prefill_buckets
  chunk_budget           unified token-budget step: per-step tokens shared by
                         decode rows + a prefill chunk (None/0 -> whole-prompt
                         prefill at admission)
  min_chunk              smallest chunk bucket (default 16)
  preemption             "off" | "swap" | "recompute" (reservation-free
                         admission + LRU page reclaim; needs chunk_budget)
  prefix_sharing         adopt indexed prompt-prefix pages (default True;
                         effective on fully-paged streaming models)
  tenant_quota           per-tenant worst-case page cap (default None)
  tenant_weights         {tenant: weight} stride-fair admission (default None)
  speculative            drafted multi-token decode steps with batched
                         verify (default False; greedy slots only)
  draft_k                max draft tokens per verify call (default 4)
  drafter                "ngram" (self-speculative prompt lookup, default)
                         or "oracle" (an untimed reference pass records
                         each request's greedy continuation and replays
                         it — the high-acceptance upper bound; run with
                         prefix_sharing off for row comparability, or the
                         reference pass also warms the prefix index)
  n_requests             workload size (default 8)
  prompt_lens            cycled prompt lengths (default (4, 8, 12))
  shared_prefix_len      tokens of one shared prompt prefix prepended to
                         every request (default 0; the prefix-sharing
                         workload knob — prompt_lens become tail lengths)
  prime_prefix           pre-submit one prefix-only request before timing so
                         the timed requests hit a warm prefix index
                         (default False; its TTFT is reported as ttft_cold_s)
  n_tenants              round-robin requests over this many tenants
                         ("t0".."tN-1", default 1)
  max_new_tokens         per-request decode budget (default 8)
  temperature            0 => greedy (default)
  arrival_rate_hz        Poisson arrival rate; 0/absent => offline batch
  mesh_shape             sharded stepping: (data, model) devices, as a
                         tuple/list or a "1x2" string (default None ->
                         single device). Needs that many visible XLA
                         devices (see launch/mesh.py)
  sharding_profile       ShardingProfile for the mesh (default
                         "decode_default")
  reduced                use the smoke-scale config copy (default True);
                         weights are materialised in the config's compute
                         dtype (bf16 at full width, f32 for smoke configs)
  warmup                 pre-compile per prompt bucket before timing (default True)
  seed                   workload RNG seed (default 0)
"""
from __future__ import annotations

import time
from dataclasses import replace
from typing import Any

import numpy as np

from repro.analysis.metrics import MetricSpec
from repro.configs.registry import get_config
from repro.core.task import Context
from repro.serve.request import Request
from repro.serve.plan import pow2_ceil
from repro.serve.scheduler import Scheduler, SchedulerConfig
from repro.sharding.rules import ShardingCtx, get_profile

# Declarative registration for repro.analysis: the serve metrics worth
# extracting from sweep results (``Examiner(SERVE_METRIC_SPECS)``). Raw keys
# of the serve_sweep result dict plus derived ms-scale latencies.
SERVE_METRIC_SPECS: tuple[MetricSpec, ...] = (
    MetricSpec("tokens_per_s", unit="tok/s"),
    MetricSpec("wall_s", unit="s"),
    MetricSpec("latency_p50_s", unit="s"),
    MetricSpec("latency_p95_s", unit="s"),
    MetricSpec("ttft_p50_s", unit="s"),
    MetricSpec("itl_p50_s", unit="s"),
    MetricSpec("itl_p95_s", unit="s"),
    MetricSpec("accept_rate"),
    MetricSpec("tokens_per_model_step", unit="tok/step"),
    MetricSpec("peak_cache_bytes", unit="B"),
    MetricSpec(
        "itl_p50_ms", unit="ms",
        extract=lambda v: None if v.get("itl_p50_s") is None
        else v["itl_p50_s"] * 1e3,
    ),
    MetricSpec(
        "ttft_p50_ms", unit="ms",
        extract=lambda v: None if v.get("ttft_p50_s") is None
        else v["ttft_p50_s"] * 1e3,
    ),
    MetricSpec("predicted_step_ms", unit="ms"),
    # Measured inter-token latency over the analytic roofline bound. The
    # bound exists only on a device kind with published peaks
    # (launch/roofline.py PEAKS), so the ratio is None off the chip.
    MetricSpec(
        "roofline_ratio",
        extract=lambda v: (
            None
            if not v.get("predicted_step_ms") or v.get("itl_p50_s") is None
            else v["itl_p50_s"] * 1e3 / v["predicted_step_ms"]
        ),
    ),
)


def _mesh_shape_opt(value: Any) -> tuple[int, int] | None:
    """Normalize a mesh_shape knob: None, (d, m), [d, m], or "dxm"."""
    if value is None:
        return None
    if isinstance(value, str):
        d, m = value.lower().split("x")
        return (int(d), int(m))
    d, m = value
    return (int(d), int(m))


def _opt(ctx: Context, name: str, default: Any) -> Any:
    """Param if swept, else setting, else default."""
    try:
        return ctx[name]
    except KeyError:
        return default


def serve_matrix(
    archs,
    backends=("xla",),
    scheduler: dict[str, Any] | None = None,
    **workload: Any,
):
    """Build the (arch x attn_backend x scheduler-knob) ConfigMatrix.

    ``scheduler`` maps extra axis names to value lists (e.g.
    ``{"chunk_budget": [0, 64]}``); ``workload`` keys become matrix settings.
    The result is a plain ConfigMatrix — compose with ``+``/``*``/``where``.
    """
    from repro.core.matrix import ConfigMatrix

    params: dict[str, Any] = {"arch": list(archs), "attn_backend": list(backends)}
    for name, values in (scheduler or {}).items():
        params[name] = list(values)
    return ConfigMatrix.from_dict({"parameters": params, "settings": dict(workload)})


def serve_sweep_distributed(
    matrix,
    queue_dir,
    workdir,
    namespace: str = "serve",
    lease_s: float = 600.0,
    max_attempts: int = 3,
    notification_provider=None,
    runner_config=None,
    stream: bool = False,
    owner: str | None = None,
):
    """Drain one serving sweep cooperatively across launcher hosts.

    Every host calls this with the same ``matrix``, ``queue_dir`` and
    ``workdir`` (both on a shared filesystem); tasks are leased through the
    file queue, metrics land in the shared FsCache, and each host returns
    the *full* sweep's ResultSet (or, with ``stream=True``, an iterator of
    results in completion order — cache hits first, then completions from
    any host). The default lease is generous because one serving cell
    includes model compiles; the runtime's background renewer keeps it
    alive however long a cell runs.
    """
    from repro.core import Memento, RunnerConfig

    eng = Memento(
        serve_sweep,
        notification_provider=notification_provider,
        workdir=workdir,
        namespace=namespace,
        runner_config=runner_config
        or RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    method = eng.stream_distributed if stream else eng.run_distributed
    return method(
        matrix, queue_dir, lease_s=lease_s, max_attempts=max_attempts, owner=owner
    )


def serving_ctx(
    mesh_shape: tuple[int, int] | None, profile: str = "decode_default"
) -> ShardingCtx:
    """The ("data", "model") mesh context for ``mesh_shape`` over the first
    visible devices, or the null context for one device."""
    if mesh_shape is None or mesh_shape[0] * mesh_shape[1] == 1:
        return ShardingCtx.null()
    from repro.launch.mesh import make_test_mesh

    return ShardingCtx(make_test_mesh(*mesh_shape), get_profile(profile))


def init_serving_params(cfg, sctx: ShardingCtx, seed: int) -> Any:
    """Random weights in the config's compute dtype (f32 weights of a 3B
    model do not fit a 16 GB chip), materialised at their mesh placement so
    that no device ever holds an unsharded copy next to its shard."""
    import jax

    from repro.models import lm
    from repro.models.schema import init_params, sharding_tree

    schema = lm.model_schema(cfg)
    return jax.jit(
        lambda key: init_params(schema, key, dtype=cfg.compute_dtype),
        out_shardings=sharding_tree(schema, sctx),
    )(jax.random.PRNGKey(seed))


def serve_sweep(ctx: Context) -> dict[str, Any]:
    """Experiment function: drive one serving configuration, return metrics."""
    arch = ctx["arch"]
    cfg = get_config(arch)
    if _opt(ctx, "reduced", True):
        cfg = cfg.reduced()
    backend = _opt(ctx, "attn_backend", cfg.attn_backend)
    cfg = replace(cfg, attn_backend=backend)

    import jax

    from repro.models import lm

    chunk_budget = _opt(ctx, "chunk_budget", None) or None
    mesh_shape = _mesh_shape_opt(_opt(ctx, "mesh_shape", None))
    profile = str(_opt(ctx, "sharding_profile", "decode_default"))
    sctx = serving_ctx(mesh_shape, profile)
    schema = lm.model_schema(cfg)
    params = init_serving_params(cfg, sctx, int(_opt(ctx, "seed", 0)))
    sched_cfg = SchedulerConfig(
        n_slots=int(_opt(ctx, "n_slots", 4)),
        cache_len=int(_opt(ctx, "cache_len", 128)),
        page_size=int(_opt(ctx, "page_size", 16)),
        n_pages=_opt(ctx, "n_pages", None),
        prefill_buckets=bool(_opt(ctx, "prefill_buckets", True)),
        chunk_budget=None if chunk_budget is None else int(chunk_budget),
        min_chunk=int(_opt(ctx, "min_chunk", 16)),
        preemption=str(_opt(ctx, "preemption", "off")),
        prefix_sharing=bool(_opt(ctx, "prefix_sharing", True)),
        tenant_quota=_opt(ctx, "tenant_quota", None),
        tenant_weights=_opt(ctx, "tenant_weights", None),
        speculative=bool(_opt(ctx, "speculative", False)),
        draft_k=int(_opt(ctx, "draft_k", 4)),
        seed=int(_opt(ctx, "seed", 0)),
        mesh_shape=mesh_shape,
        sharding_profile=profile,
    )
    drafter_kind = str(_opt(ctx, "drafter", "ngram"))
    if drafter_kind not in ("ngram", "oracle"):
        raise ValueError(f"unknown drafter {drafter_kind!r}")
    sched = Scheduler(cfg, params, sctx, sched_cfg)

    rng = np.random.default_rng(int(_opt(ctx, "seed", 0)))
    n_req = int(_opt(ctx, "n_requests", 8))
    prompt_lens = [int(p) for p in _opt(ctx, "prompt_lens", (4, 8, 12))]
    shared_len = int(_opt(ctx, "shared_prefix_len", 0))
    n_tenants = int(_opt(ctx, "n_tenants", 1))
    max_new = int(_opt(ctx, "max_new_tokens", 8))
    temperature = float(_opt(ctx, "temperature", 0.0))
    lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_req)]
    shared = rng.integers(0, cfg.vocab_size, size=shared_len).astype(np.int32)
    requests = [
        Request(
            np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, size=p).astype(np.int32)]
            ),
            max_new_tokens=max_new,
            temperature=temperature,
            tenant=f"t{i % n_tenants}",
        )
        for i, p in enumerate(lens)
    ]

    if _opt(ctx, "warmup", True):
        # Compile every prompt-length bucket + the decode step outside the
        # timed window so the measured run sees steady-state latencies.
        warm_lens = {shared_len + p for p in lens}
        if shared_len and _opt(ctx, "prime_prefix", False):
            warm_lens.add(shared_len)  # the primer's own bucket
        for p in sorted(warm_lens):
            sched.submit(Request(np.zeros(p, np.int32), max_new_tokens=2))
        sched.run()
        if sched_cfg.speculative:
            # Compile the verify + rollback programs for every k-bucket
            # outside the timed window: a draft of out-of-vocab sentinels
            # can never be accepted, so one request per bucket exercises
            # verify and the rejection path (replay or pos fixup).
            from repro.serve.draft import ScriptDrafter

            wlen = max(shared_len + p for p in lens)
            seen: set[int] = set()
            for d in range(sched_cfg.draft_k, 0, -1):
                b = pow2_ceil(d + 1)
                if b in seen:
                    continue
                seen.add(b)
                sched.set_drafter(ScriptDrafter([np.full(d, -2, np.int32)]))
                sched.submit(Request(np.zeros(wlen, np.int32), max_new_tokens=d + 2))
                sched.run()
        sched.mem.reset_peaks()
        sched.deferred_admissions = 0

    if sched_cfg.speculative:
        if drafter_kind == "oracle":
            # Untimed reference pass: run the workload with drafting muted
            # (empty ScriptDrafter proposes nothing -> plain greedy) to
            # record each request's continuation, then replay it as a
            # perfect draft — the acceptance upper bound for this workload.
            from repro.serve.draft import ReplayDrafter, ScriptDrafter

            sched.set_drafter(ScriptDrafter([]))
            ref_rids = [
                sched.submit(
                    Request(
                        r.prompt, max_new_tokens=r.max_new_tokens,
                        temperature=r.temperature, tenant=r.tenant,
                    )
                )
                for r in requests
            ]
            while sched.pending or sched.num_active:
                ctx.heartbeat()
                sched.step()
            seqs = [
                np.concatenate(
                    [requests[i].prompt,
                     np.asarray(sched.result(rid).tokens, np.int32)]
                )
                for i, rid in enumerate(ref_rids)
            ]
            sched.set_drafter(ReplayDrafter(seqs))
            sched.mem.reset_peaks()
        else:
            from repro.serve.draft import NgramDrafter

            sched.set_drafter(NgramDrafter())

    ttft_cold = None
    if shared_len and _opt(ctx, "prime_prefix", False):
        # Prime the prefix index: one prefix-only request registers the
        # shared pages (its TTFT is the cold-prefix cost), so every timed
        # request adopts instead of recomputing the shared span.
        primer = sched.submit(Request(shared, max_new_tokens=1))
        while sched.pending or sched.num_active:
            sched.step()
        ttft_cold = sched.result(primer).ttft_s
        sched.mem.reset_peaks()

    rate = float(_opt(ctx, "arrival_rate_hz", 0.0) or 0.0)
    # Scope work counters past warmup (trace counters stay cumulative:
    # warmup exists precisely to absorb the compiles).
    steps_before = sched.total_decode_steps
    chunks_before = sched.total_chunk_steps
    preempts_before = sched.preemptions_total
    hits_before = sched.prefix_hits
    hit_tokens_before = sched.prefix_hit_tokens
    spec_before = sched.total_spec_steps
    replays_before = sched.total_spec_replays
    plan_before = sched.plan_time_s
    fallbacks_before = sched.spec_fallbacks
    drafted_before = sched.drafted_tokens_total
    accepted_before = sched.accepted_tokens_total
    t0 = time.perf_counter()
    if rate > 0.0:
        arrivals = np.cumsum(rng.exponential(scale=1.0 / rate, size=n_req))
        rids, i = [], 0
        while i < n_req or sched.pending or sched.num_active:
            ctx.heartbeat()
            now = time.perf_counter() - t0
            while i < n_req and arrivals[i] <= now:
                rids.append(sched.submit(requests[i]))
                i += 1
            if not sched.step() and i < n_req:
                time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
    else:
        rids = [sched.submit(r) for r in requests]
        while sched.pending or sched.num_active:
            ctx.heartbeat()
            sched.step()
    wall = time.perf_counter() - t0

    done = [sched.result(r) for r in rids]
    toks = sum(len(rs.tokens) for rs in done)
    lat = np.array([rs.latency_s for rs in done])
    ttft = np.array([rs.ttft_s for rs in done])
    # Inter-token latency across all in-flight decodes: the gap a streaming
    # client sees between consecutive tokens. Un-chunked long prefills of
    # *other* requests stall every in-flight decode and surface here as p95
    # spikes; the unified token-budget step is measured by this number.
    itl = [gap for rs in done for gap in rs.inter_token_s()]
    itl_a = np.array(itl) if itl else np.zeros(1)
    cache_bytes = sched.paged_cache_bytes()
    warm_ttft = np.array([rs.ttft_s for rs in done if rs.adopted_tokens > 0])
    decode_steps = sched.total_decode_steps - steps_before
    chunk_steps = sched.total_chunk_steps - chunks_before
    spec_steps = sched.total_spec_steps - spec_before
    spec_replays = sched.total_spec_replays - replays_before
    # Host-planner share: time spent in the pure plan layer (serve/plan.py)
    # over every scheduler step the timed window paid — the layered core's
    # overhead budget (microseconds against millisecond device steps).
    plan_s = sched.plan_time_s - plan_before
    plan_steps = decode_steps + chunk_steps + spec_steps + spec_replays
    drafted = sched.drafted_tokens_total - drafted_before
    accepted = sched.accepted_tokens_total - accepted_before
    # The headline speculation metric: generated tokens per model-step-
    # equivalent (decode steps + verify calls + rollback replays — every
    # forward pass the decode phase paid). Plain decoding pins this at
    # ~min(n_slots, live requests); speculation lifts it by accepted
    # tokens per verify.
    model_steps = decode_steps + spec_steps + spec_replays
    # Analytic roofline for one decode step at this batch and mesh, on a
    # device with published peaks only — recorded next to the measured
    # latencies so analysis can report the measured/predicted ratio.
    from repro.launch.roofline import PEAKS, predict_decode_step
    from repro.models.schema import count_params

    dev = jax.devices()[0]
    pred = None
    if dev.device_kind in PEAKS:
        pred = predict_decode_step(
            cfg,
            count_params(schema),
            batch=sched_cfg.n_slots,
            mesh_shape=mesh_shape or (1, 1),
            device_kind=dev.device_kind,
        )
    return {
        "arch": arch,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": jax.device_count(),
        },
        "attn_backend": backend,
        "n_requests": n_req,
        "generated_tokens": toks,
        "tokens_per_s": toks / wall if wall > 0 else float("inf"),
        "wall_s": wall,
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p95_s": float(np.percentile(lat, 95)),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "itl_p50_s": float(np.percentile(itl_a, 50)),
        "itl_p95_s": float(np.percentile(itl_a, 95)),
        "decode_steps": decode_steps,
        "chunk_steps": chunk_steps,
        "spec_steps": spec_steps,
        "spec_replays": spec_replays,
        "spec_fallbacks": sched.spec_fallbacks - fallbacks_before,
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "accept_rate": accepted / drafted if drafted else None,
        "tokens_per_model_step": toks / model_steps if model_steps else None,
        "plan_time_s": plan_s,
        "plan_us_per_step": plan_s * 1e6 / plan_steps if plan_steps else None,
        "plan_frac": plan_s / wall if wall > 0 else None,
        "decode_traces": sched.decode_traces,
        "prefill_traces": sched.prefill_traces,
        "chunk_traces": sched.chunk_traces,
        "verify_traces": sched.verify_traces,
        "deferred_admissions": sched.stats()["deferred_admissions"],
        "quota_deferrals": sched.quota_deferrals,
        "preemptions": sched.preemptions_total - preempts_before,
        "prefix_hits": sched.prefix_hits - hits_before,
        "prefix_hit_tokens": sched.prefix_hit_tokens - hit_tokens_before,
        "ttft_cold_s": ttft_cold,
        "ttft_warm_p50_s": (
            float(np.percentile(warm_ttft, 50)) if warm_ttft.size else None
        ),
        "peak_cache_bytes": cache_bytes["peak_bytes"],
        "contiguous_cache_bytes": cache_bytes["contiguous_bytes"],
        "cache_bytes_per_page_per_device": cache_bytes[
            "bytes_per_page_per_device"
        ],
        "mesh": "1x1" if mesh_shape is None else f"{mesh_shape[0]}x{mesh_shape[1]}",
        "mesh_devices": sched.sctx.device_count(),
        "predicted_step_ms": pred.step_time_lower_bound * 1e3 if pred else None,
        "predicted_bottleneck": pred.bottleneck if pred else None,
        "paged": sched.mem.paged,
        "chunk_budget": sched_cfg.chunk_budget,
        "preemption": sched_cfg.preemption,
        "prefix_sharing": sched_cfg.prefix_sharing,
        "speculative": sched_cfg.speculative,
        "draft_k": sched_cfg.draft_k if sched_cfg.speculative else None,
        "drafter": drafter_kind if sched_cfg.speculative else None,
        "tokens": [rs.tokens for rs in done],
    }
