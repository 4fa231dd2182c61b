"""Smoke run of the serving path on TPU: llama3.2-3b at its published width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path only, on four chips

One chip, three phases:

  a. Require the TPU platform before any work. ``jax_platforms`` is set to
     ``"tpu"``, so a chip that cannot be opened is an error, not a CPU run.
  b. Serve REQUESTS through ``Memento(serve_sweep)`` with the XLA attention
     path and chunked prefill. Every prompt fits one chunk bucket and one
     page bucket, so the chunk and decode programs compile once each, and
     ``decode_traces`` must be 1.
  c. Serve the same prompts with ``attn_backend="pallas"``; the Pallas
     kernel must be in the compiled decode and chunk programs. Then run one
     prefill and DECODE_STEPS decode steps on both paths, feeding both the
     XLA path's greedy tokens, and require their logits to agree within
     LOGIT_RTOL. Greedy-token agreement is reported, not required: with
     random weights the logits are nearly flat and argmax ties flip.

With ``--chips 4`` the script runs only the sharded path: the same prefill
and decode steps with the Pallas backend on meshes 1x4 (heads over
``model``) and 4x1 (slots and page pool over ``data``, ``shard_map``'d
paged kernels), each compared with 1x1 in the same process by the same
tolerance, after checking that weights and state occupy all four chips.

Everything is reported on earlier lines. The last line of stdout is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from collections import Counter
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "llama3.2-3b"
# Prompt lengths in (64, 128]: with a 128-token chunk budget every prompt is
# one chunk of the 128 bucket backed by the 8-page bucket (page_size 16).
PROMPT_LENS = (72, 96, 128, 80, 112, 100)
REQUESTS = len(PROMPT_LENS)
MAX_NEW_TOKENS = 32
DECODE_STEPS = 4
SEED = 0
# Logits of the bf16 model on two attention paths (or two meshes): XLA's
# einsums against the Pallas kernel's f32 online softmax, or a different
# reduction order under tensor parallelism. Each layer rounds its output to
# bf16 (relative step 2**-8), and 28 layers compound that; the bound is
# max |a - b| <= LOGIT_RTOL * max |b| over the vocabulary, set from the
# dtype before any chip run.
LOGIT_RTOL = 0.02


def serve_settings(reduced: bool) -> dict:
    """The serving workload; ``reduced`` swaps in the smoke-scale model."""
    return dict(
        reduced=reduced,
        n_slots=8,
        cache_len=256,
        page_size=16,
        chunk_budget=128,
        min_chunk=128,
        n_requests=REQUESTS,
        prompt_lens=PROMPT_LENS,
        max_new_tokens=MAX_NEW_TOKENS,
        warmup=True,
        seed=SEED,
    )


# -- phase a: the device ------------------------------------------------------
def check_platform(devices) -> dict:
    """The device record of a TPU backend; raises naming what was found."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind})"
        )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def require_tpu() -> dict:
    import jax

    jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise RuntimeError(f"chip_smoke needs a TPU; JAX could not open one: {e}") from e
    return check_platform(devices)


# -- phase b/c: serving through Memento ----------------------------------------
def serve_phase(backend: str, *, mesh: str | None = None, reduced: bool = False) -> dict:
    """Serve the workload through ``Memento(serve_sweep)``; returns the row."""
    from repro.core import Memento, RunnerConfig
    from repro.experiments import serve_matrix, serve_sweep

    matrix = serve_matrix(
        [ARCH], backends=[backend], mesh_shape=mesh, **serve_settings(reduced)
    )
    eng = Memento(
        serve_sweep,
        runner_config=RunnerConfig(max_workers=1, enable_speculation=False, retries=0),
    )
    (res,) = eng.run(matrix, cache=False)
    gc.collect()  # the task's scheduler holds the weights in reference cycles
    if not res.ok:
        raise RuntimeError(f"serve task failed:\n{res.summary()}")
    v = res.value
    want = REQUESTS * MAX_NEW_TOKENS
    if v["generated_tokens"] != want:
        raise RuntimeError(f"generated {v['generated_tokens']} tokens, want {want}")
    if v["decode_traces"] != 1:
        raise RuntimeError(f"decode_traces={v['decode_traces']}, want 1")
    return v


# -- phase c and --chips 4: logits on two paths ----------------------------------
def forced_logits(cfg, *, backend: str, mesh_shape=None, forced=None):
    """One prefill and DECODE_STEPS decode steps of the first prompt.

    Each decode step is fed ``forced[i]`` (or, when ``forced`` is None, the
    path's own greedy token), so two paths see the same inputs even where
    their argmax differs. Returns ``(logits, fed, bytes_in_use)``: the
    prefill logits then one row per decode step, the tokens fed, and each
    device's bytes in use while the weights and state were live."""
    import jax

    from repro.experiments.serve import init_serving_params, serving_ctx
    from repro.serve.request import Request
    from repro.serve.scheduler import Scheduler, SchedulerConfig

    s = serve_settings(reduced=False)
    cfg = replace(cfg, attn_backend=backend)
    sctx = serving_ctx(mesh_shape)
    params = init_serving_params(cfg, sctx, SEED)
    sched = Scheduler(
        cfg, params, sctx,
        SchedulerConfig(
            n_slots=s["n_slots"], cache_len=s["cache_len"], page_size=s["page_size"],
            chunk_budget=s["chunk_budget"], min_chunk=s["min_chunk"],
            seed=SEED, mesh_shape=mesh_shape,
        ),
    )
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=PROMPT_LENS[0]
    ).astype(np.int32)
    sched.submit(Request(prompt, max_new_tokens=DECODE_STEPS + 1))
    # Admit and stream the prompt (one chunk), then dispatch one decode step
    # at a time and read it before the next, rather than sched.step(), which
    # queues the next step ahead: each decode input can then be forced.
    sched._admit_pending()
    sched._prefill_chunk_step()
    ((_, slot, tokens, last),) = sched._ev["firsts"]
    V = cfg.vocab_size
    logits = [np.asarray(last, np.float32).reshape(-1)[:V]]
    fed = []
    for i in range(DECODE_STEPS):
        tok = int(forced[i]) if forced is not None else int(np.asarray(tokens)[slot, 0])
        fed.append(tok)
        sched._place(slot, tok)
        sched._dispatch()
        step = sched._flights.pop()
        tokens = step["toks"]
        logits.append(np.asarray(step["logits"][slot, -1, :V], np.float32))
    devices = sctx.mesh.devices.flat if sctx.mesh is not None else jax.devices()[:1]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]
    del sched, params
    gc.collect()
    return logits, fed, in_use


def compare_logits(name: str, got: list, ref: list) -> dict:
    """Max |got - ref| over max |ref| per step; raises past LOGIT_RTOL."""
    worst = 0.0
    agree = 0
    for g, r in zip(got, ref):
        worst = max(worst, float(np.max(np.abs(g - r)) / np.max(np.abs(r))))
        agree += int(np.argmax(g) == np.argmax(r))
    row = {
        "compare": name,
        "steps": len(ref),
        "max_rel_err": worst,
        "rtol": LOGIT_RTOL,
        "greedy_agree": f"{agree}/{len(ref)}",
    }
    print(json.dumps(row))
    if not worst <= LOGIT_RTOL:
        raise RuntimeError(f"{name}: logits differ by {worst:.4g} > {LOGIT_RTOL}")
    return row


def kernel_programs(dump_dir: str) -> dict[str, bool]:
    """Which lowered step programs hold a Mosaic kernel call."""
    found = {"decode": False, "chunk": False}
    for f in Path(dump_dir).glob("*.mlir"):
        for prog in found:
            if f"_{prog}_fn" in f.name and "tpu_custom_call" in f.read_text():
                found[prog] = True
    return found


# -- instrumentation ---------------------------------------------------------------
class CompileStats:
    """Seconds spent getting executables (compiling or loading them from the
    persistent cache) and persistent-cache hits and misses, by phase."""

    def __init__(self):
        import jax

        self.totals = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.totals["compile_s"] += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.totals["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.totals["cache_misses"] += 1

    def phase(self, name: str, fn, *args, **kw):
        before = Counter(self.totals)
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        d = self.totals - before
        print(json.dumps({
            "phase": name,
            "wall_s": time.perf_counter() - t0,
            "compile_s": d["compile_s"],
            "cache_hits": d["cache_hits"],
            "cache_misses": d["cache_misses"],
        }))
        return out


# -- the two runs ---------------------------------------------------------------------
def one_chip(stats: CompileStats, cfg) -> None:
    import jax

    for backend in ("xla", "pallas"):
        with tempfile.TemporaryDirectory() as dump:
            jax.config.update("jax_dump_ir_to", dump)
            v = stats.phase(f"serve_{backend}", serve_phase, backend)
            jax.config.update("jax_dump_ir_to", "")
            kernels = kernel_programs(dump)
        print(json.dumps({
            "serve": backend,
            "device": v["device"],
            "generated_tokens": v["generated_tokens"],
            "decode_traces": v["decode_traces"],
            "chunk_traces": v["chunk_traces"],
            "tokens_per_s": v["tokens_per_s"],
            "ttft_p50_s": v["ttft_p50_s"],
            "itl_p50_s": v["itl_p50_s"],
            "kernel_in": kernels,
        }))
        if backend == "pallas" and not all(kernels.values()):
            raise RuntimeError(f"Pallas kernel missing from programs: {kernels}")
        if backend == "xla" and any(kernels.values()):
            raise RuntimeError(f"XLA path compiled a Pallas kernel: {kernels}")
    ref, fed, _ = stats.phase("logits_xla", forced_logits, cfg, backend="xla")
    got, _, _ = stats.phase(
        "logits_pallas", forced_logits, cfg, backend="pallas", forced=fed
    )
    compare_logits("pallas_vs_xla", got, ref)


def four_chips(stats: CompileStats, cfg) -> None:
    ref, fed, _ = stats.phase("logits_1x1", forced_logits, cfg, backend="pallas")
    for mesh in ((1, 4), (4, 1)):
        name = f"{mesh[0]}x{mesh[1]}"
        v = stats.phase(f"serve_{name}", serve_phase, "pallas", mesh=name)
        print(json.dumps({
            "serve": "pallas",
            "mesh": v["mesh"],
            "mesh_devices": v["mesh_devices"],
            "generated_tokens": v["generated_tokens"],
            "decode_traces": v["decode_traces"],
            "chunk_traces": v["chunk_traces"],
        }))
        if v["mesh_devices"] != 4:
            raise RuntimeError(f"{name}: served on {v['mesh_devices']} devices")
        got, _, in_use = stats.phase(
            f"logits_{name}", forced_logits, cfg, backend="pallas",
            mesh_shape=mesh, forced=fed,
        )
        print(json.dumps({"mesh": name, "bytes_in_use_per_device": in_use}))
        # Each chip holds at least a quarter of the weights (all of them
        # under 4x1); one holding under an eighth means placement collapsed
        # onto fewer chips.
        if min(in_use) < weight_bytes(cfg) // 8:
            raise RuntimeError(f"{name}: arrays did not spread over 4 chips: {in_use}")
        compare_logits(f"{name}_vs_1x1", got, ref)


def weight_bytes(cfg) -> int:
    import jax.numpy as jnp

    from repro.models import lm
    from repro.models.schema import count_params

    return count_params(lm.model_schema(cfg)) * jnp.dtype(cfg.compute_dtype).itemsize


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the sharded path (meshes 1x4 and 4x1 against 1x1)",
    )
    args = ap.parse_args()
    device = require_tpu()
    if device["count"] != args.chips:
        raise RuntimeError(f"--chips {args.chips} but JAX sees {device['count']} chips")

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm
    from repro.models.schema import count_params

    print(json.dumps({"compile_cache": enable_compile_cache(ROOT)}))
    cfg = get_config(ARCH)
    print(json.dumps({
        "arch": ARCH,
        "n_layers": cfg.n_layers,
        "d_model": cfg.d_model,
        "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim,
        "d_ff": cfg.d_ff,
        "vocab_size": cfg.vocab_size,
        "params": count_params(lm.model_schema(cfg)),
        "weight_dtype": cfg.compute_dtype,
        "device": device,
    }))
    stats = CompileStats()
    (one_chip if args.chips == 1 else four_chips)(stats, cfg)
    print(json.dumps({
        "peak_bytes_in_use": [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()],
        "compile_s_total": stats.totals["compile_s"],
        "cache_hits_total": stats.totals["cache_hits"],
        "cache_misses_total": stats.totals["cache_misses"],
    }))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
