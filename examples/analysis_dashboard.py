"""Distributed serve sweep with a live fleet dashboard + post-run analysis.

Two worker processes cooperatively drain one serve-sweep matrix through the
file queue while the parent serves a live dashboard: per-host throughput,
queue depth, ETA, and failure drill-down with the real tracebacks the
distributed runtime propagates. Open the printed URL in a browser while it
runs (or curl ``/api/state``).

When the sweep finishes, the results render as a grouped comparison table
twice — once through the Python API (``repro.analysis.compare``), once
through the CLI (``python -m repro.analysis table``) — and the two outputs
are asserted token-for-token identical.

    PYTHONPATH=src python examples/analysis_dashboard.py [--fast] [--port 8321]

``--fast`` swaps the real serve model for a synthetic workload (no compile;
finishes in seconds) — the orchestration, dashboard, and analysis paths are
identical.

The stand-in hosts are processes on one machine, and an accelerator chip
belongs to one process at a time, so the demo pins every process to the CPU
(``JAX_PLATFORMS=cpu``, inherited by the spawned workers). Real launcher
hosts each drive their own chips.
"""
import argparse
import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # before anything imports jax

import repro.core as memento  # noqa: E402
from repro.analysis import AnalysisNotificationProvider, Dashboard, compare  # noqa: E402
from repro.analysis.metrics import MetricFrame  # noqa: E402
from repro.core import DistributedConfig, RunnerConfig  # noqa: E402
from repro.experiments import serve_matrix, serve_sweep  # noqa: E402


def fast_sweep(ctx):
    """Synthetic stand-in for serve_sweep: same result-dict shape, no model.
    One param combination fails on purpose so the dashboard's failure
    drill-down has a real traceback to show."""
    import random

    rng = random.Random(ctx.key)
    time.sleep(0.2 + rng.random() * 0.3)
    if ctx["n_slots"] == 2 and ctx["chunk_budget"] == 16:
        raise RuntimeError("synthetic failure: n_slots=2 chunk_budget=16 "
                           "is the demo's broken cell")
    toks = 64 * ctx["n_slots"]
    wall = 0.5 + rng.random() * 0.2
    return {
        "n_slots": ctx["n_slots"],
        "chunk_budget": ctx["chunk_budget"],
        "tokens_per_s": toks / wall,
        "wall_s": wall,
        "itl_p50_s": 0.004 + rng.random() * 0.002,
        "accept_rate": 0.8 + rng.random() * 0.15,
        "generated_tokens": float(toks),
    }


def build_matrix(fast: bool):
    if fast:
        return memento.ConfigMatrix.from_dict(
            {"parameters": {"n_slots": [2, 4], "chunk_budget": [0, 16, 32]}}
        )
    return serve_matrix(
        ["llama3.2-3b"], backends=["xla"],
        scheduler={"n_slots": [2, 4], "chunk_budget": [0, 16]},
        cache_len=64, page_size=8, n_requests=4, prompt_lens=(4, 9, 17, 6),
        max_new_tokens=4, warmup=False,
    )


def worker(root: str, owner: str, fast: bool, journal: str) -> None:
    """One drain participant: full local Runner against the shared queue,
    teeing its events into the shared journal the dashboard tails."""
    prov = AnalysisNotificationProvider(journal_path=journal)
    eng = memento.Memento(
        fast_sweep if fast else serve_sweep,
        notification_provider=prov,
        workdir=os.path.join(root, "w"),
        namespace="serve",
        runner_config=RunnerConfig(max_workers=1, retries=0,
                                   enable_speculation=False),
    )
    eng.run_distributed(
        build_matrix(fast),
        queue_dir=os.path.join(root, "q"),
        owner=owner,
        distributed_config=DistributedConfig(
            poll_s=0.05, claim_ahead=1, progress_every_s=0.5
        ),
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="synthetic workload, no model compile")
    ap.add_argument("--port", type=int, default=8321)
    ap.add_argument("--linger", type=float, default=0.0,
                    help="keep the dashboard up this many seconds after "
                         "the sweep finishes")
    args = ap.parse_args()

    root = tempfile.mkdtemp(prefix="memento_dash_demo_")
    journal = os.path.join(root, "events.jsonl")
    matrix = build_matrix(args.fast)
    total = len(matrix.task_list())

    # The parent owns the dashboard; workers append to the shared journal
    # and the dashboard provider tails it — exactly the multi-host layout,
    # just on one machine.
    prov = AnalysisNotificationProvider(total=total)
    dash = Dashboard(prov, port=args.port)
    url = dash.start()
    print(f"dashboard: {url}   (state: {url}/api/state)")

    mp = multiprocessing.get_context("spawn")
    procs = [
        mp.Process(target=worker, args=(root, f"host{i}", args.fast, journal))
        for i in range(2)
    ]
    t0 = time.time()
    for p in procs:
        p.start()
    offset = 0
    while any(p.is_alive() for p in procs):
        offset = prov.replay_journal(journal, offset)
        time.sleep(0.2)
    for p in procs:
        p.join()
    prov.replay_journal(journal, offset)
    state = prov.state()
    print(f"\nsweep drained in {time.time() - t0:.1f}s: "
          f"{state['done']} done, {state['failed']} failed, "
          f"hosts={list(state['hosts'])}")
    for f in state["failures"]:
        print(f"  failure on {f['host']}: {f['error']}")

    # -- post-run analysis: API table == CLI table, token for token --------
    eng = memento.Memento(
        fast_sweep if args.fast else serve_sweep,
        notification_provider=memento.CallbackNotificationProvider(lambda e: None),
        workdir=os.path.join(root, "w"),
        namespace="serve",
    )
    results = eng.run_distributed(
        build_matrix(args.fast), queue_dir=os.path.join(root, "q"),
        publish=False,
    )
    csv_path = os.path.join(root, "results.csv")
    results.to_csv(csv_path)

    frame = MetricFrame.from_results_csv(csv_path)
    rows, cols = ["n_slots"], ["chunk_budget"]
    api_table = compare(
        frame, rows=rows, cols=cols, metric="tokens_per_s", agg="mean"
    ).to_markdown()

    cli = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "table",
         "--csv", csv_path, "--rows", *rows, "--cols", *cols,
         "--metric", "tokens_per_s", "--agg", "mean", "--format", "md"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             os.environ.get("PYTHONPATH", "")])},
    )
    cli_table = cli.stdout.strip()
    print("\ntokens/s by n_slots x chunk_budget:\n")
    print(api_table)
    assert cli_table == api_table, (
        "CLI and API tables differ:\n--- CLI ---\n"
        f"{cli_table}\n--- API ---\n{api_table}"
    )
    print("\nCLI table output is token-for-token identical to the API table.")

    if args.linger:
        print(f"dashboard stays up {args.linger:.0f}s — {url}")
        time.sleep(args.linger)
    dash.stop()
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
