"""Continuous-batching serving demo, driven through the Memento core.

A serving sweep is an experiment matrix like any other: three state
families (dense GQA KV, hybrid RG-LRU + window ring buffer, xLSTM recurrent
matrix state) crossed with scheduler settings, run through
``repro.experiments.serve_sweep`` so the sweep inherits caching and
streaming — re-run the demo and every row returns instantly from cache.
Watch ``decode_traces`` stay at 1: requests join/leave mid-decode on one
fixed-shape jitted step. The cache namespace names the device, so a result
computed on one device is never served as another's. A failed row makes
the demo exit non-zero.

    PYTHONPATH=src python examples/serve_demo.py
"""
import sys

import jax

import repro.core as memento
from repro.experiments import serve_matrix, serve_sweep

matrix = serve_matrix(
    ["llama3.2-3b", "recurrentgemma-2b", "xlstm-1.3b"],
    backends=["xla"],
    scheduler={"n_slots": [2]},
    cache_len=64,
    n_requests=3,
    prompt_lens=(12, 6, 9),
    max_new_tokens=8,
    warmup=False,
)

dev = jax.devices()[0]
eng = memento.Memento(
    serve_sweep,
    memento.ConsoleNotificationProvider(verbose=False),
    workdir=".memento-serve-demo",
    namespace=f"serve-{dev.platform}-{dev.device_kind}",
    runner_config=memento.RunnerConfig(
        max_workers=1, enable_speculation=False, retries=0
    ),
)

failed = 0
for r in eng.stream(matrix):
    if not r.ok:
        print(r.summary())
        failed += 1
        continue
    v = r.value
    d = v["device"]
    print(
        f"{v['arch']:22s} [{r.status:6s}] on {d['platform']} ({d['kind']}): "
        f"{v['generated_tokens']} tokens "
        f"@ {v['tokens_per_s']:.1f} tok/s  p50={v['latency_p50_s']*1e3:.0f}ms "
        f"decode_traces={v['decode_traces']}"
    )
    for i, toks in enumerate(v["tokens"]):
        print(f"  req{i} -> {len(toks)} tokens: {toks}")
sys.exit(1 if failed else 0)
