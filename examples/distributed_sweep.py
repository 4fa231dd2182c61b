"""Distributed sweep demo: several launcher "hosts" drain one matrix.

Spawns N worker processes, each a full `Memento.run_distributed` participant
on a shared queue directory + shared result cache — exactly what N real
launcher hosts on one shared filesystem would run. The parent is itself a
participant: it streams results as they complete anywhere, renders the
cluster-wide per-host progress line, and ends up with the full ResultSet.

    PYTHONPATH=src python examples/distributed_sweep.py [--hosts 3] [--serve]

``--serve`` swaps the toy task for a real (smoke-scale) serving sweep via
``experiments.serve_sweep_distributed`` — the distributed serve sweep from
the ROADMAP. One model compile per host, so expect ~a minute on CPU. The
stand-in hosts are processes on one machine, and an accelerator chip belongs
to one process at a time, so ``--serve`` pins every host to the CPU
(``JAX_PLATFORMS=cpu``); real launcher hosts each drive their own chips.
"""
from __future__ import annotations

import argparse
import multiprocessing
import os
import tempfile
import time


def simulated_experiment(ctx):
    """A stand-in for a real experiment: sleeps, then returns a metric."""
    time.sleep(0.05 + 0.01 * (ctx["width"] % 3))
    return {"width": ctx["width"], "depth": ctx["depth"],
            "score": ctx["width"] * ctx["depth"]}


MATRIX = {"parameters": {"width": [64, 128, 256, 512], "depth": [2, 4, 8]}}


def _worker(root: str, owner: str) -> None:
    from repro.core import CallbackNotificationProvider, Memento, RunnerConfig

    eng = Memento(
        simulated_experiment,
        notification_provider=CallbackNotificationProvider(lambda e: None),
        workdir=os.path.join(root, "workdir"),
        runner_config=RunnerConfig(max_workers=2, enable_speculation=False),
    )
    eng.run_distributed(MATRIX, queue_dir=os.path.join(root, "queue"), owner=owner)


def main_toy(n_hosts: int) -> None:
    from repro.core import (
        DistributedConfig,
        Memento,
        ProgressNotificationProvider,
        RunnerConfig,
    )

    root = tempfile.mkdtemp(prefix="memento_distributed_")
    print(f"shared dir: {root}  ({n_hosts} worker hosts + this one)")
    mp = multiprocessing.get_context("spawn")
    workers = [
        mp.Process(target=_worker, args=(root, f"host-{i}"))
        for i in range(n_hosts)
    ]
    for p in workers:
        p.start()

    prov = ProgressNotificationProvider(total=12)
    eng = Memento(
        simulated_experiment,
        notification_provider=prov,
        workdir=os.path.join(root, "workdir"),
        runner_config=RunnerConfig(max_workers=2, enable_speculation=False),
    )
    t0 = time.time()
    results = []
    for r in eng.stream_distributed(
        MATRIX,
        queue_dir=os.path.join(root, "queue"),
        owner="parent",
        distributed_config=DistributedConfig(progress_every_s=0.5),
    ):
        results.append(r)
        print(f"  {r.spec.describe()} -> {r.status} on {r.host}")
    for p in workers:
        p.join()
    print(f"\n{len(results)} results in {time.time() - t0:.2f}s; "
          f"best score: {max(r.value['score'] for r in results)}")


def _serve_matrix():
    from repro.experiments import serve_matrix

    return serve_matrix(
        ["llama3.2-3b"], backends=["xla"], scheduler={"n_slots": [2, 4]},
        cache_len=64, n_requests=4, prompt_lens=(5, 9, 13), max_new_tokens=4,
        warmup=False,
    )


def _serve_worker(root: str, owner: str) -> None:
    from repro.experiments import serve_sweep_distributed

    serve_sweep_distributed(
        _serve_matrix(), queue_dir=os.path.join(root, "queue"),
        workdir=os.path.join(root, "workdir"), owner=owner,
    )


def main_serve(n_hosts: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by the spawned hosts
    from repro.experiments import serve_sweep_distributed

    root = tempfile.mkdtemp(prefix="memento_distserve_")
    mp = multiprocessing.get_context("spawn")  # each host needs its own jax
    workers = [
        mp.Process(target=_serve_worker, args=(root, f"serve-host-{i}"))
        for i in range(max(n_hosts - 1, 0))
    ]
    for p in workers:
        p.start()
    res = serve_sweep_distributed(
        _serve_matrix(), queue_dir=os.path.join(root, "queue"),
        workdir=os.path.join(root, "workdir"), owner="parent",
    )
    for p in workers:
        p.join()
    for r in res:
        v = r.value
        print(f"n_slots={r.spec.params['n_slots']} cell on {r.host}: "
              f"{v['tokens_per_s']:.1f} tok/s (status={r.status})")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=3, help="worker processes")
    ap.add_argument("--serve", action="store_true",
                    help="run a real smoke-scale serving sweep instead of the toy task")
    args = ap.parse_args()
    (main_serve if args.serve else main_toy)(args.hosts)
