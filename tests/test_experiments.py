"""Workload adapters: serve/train sweeps running *through* the Memento core.

The acceptance scenario for the v2 experiment API: a 2-model x 2-backend
serving sweep driven by ``experiments.serve_sweep`` inherits caching — the
second run executes nothing and is served entirely from cache.
"""
import numpy as np
import pytest

import repro.core as memento
from repro.experiments import serve_matrix, serve_sweep, train_matrix, train_sweep

ARCHS = ["llama3.2-3b", "recurrentgemma-2b"]
BACKENDS = ["xla", "pallas"]


def _runner_config():
    return memento.RunnerConfig(max_workers=1, retries=0, enable_speculation=False)


class TestServeSweep:
    @pytest.fixture(scope="class")
    def sweep_runs(self, tmp_path_factory):
        workdir = tmp_path_factory.mktemp("serve-sweep")
        matrix = serve_matrix(
            ARCHS,
            backends=BACKENDS,
            scheduler={"n_slots": [2]},
            cache_len=64,
            n_requests=2,
            prompt_lens=(5, 9),
            max_new_tokens=3,
            warmup=False,
        )
        eng = memento.Memento(
            serve_sweep,
            memento.RecordingProvider(),
            workdir=workdir,
            namespace="serve",
            runner_config=_runner_config(),
        )
        first = eng.run(matrix)
        second = eng.run(matrix)
        return first, second

    def test_two_by_two_sweep_runs_through_memento(self, sweep_runs):
        first, _ = sweep_runs
        assert len(first) == len(ARCHS) * len(BACKENDS)
        assert [r.status for r in first] == ["ok"] * 4
        combos = {(r.value["arch"], r.value["attn_backend"]) for r in first}
        assert combos == {(a, b) for a in ARCHS for b in BACKENDS}
        for r in first:
            v = r.value
            assert v["generated_tokens"] == 2 * 3  # n_requests x max_new_tokens
            assert v["decode_traces"] == 1  # hot path compiled once per task
            assert v["tokens_per_s"] > 0

    def test_second_run_served_entirely_from_cache(self, sweep_runs):
        first, second = sweep_runs
        assert [r.status for r in second] == ["cached"] * 4
        # Cached values are the real run's values, keyed identically.
        for a, b in zip(first, second):
            assert a.spec.key == b.spec.key
            assert a.value["tokens"] == b.value["tokens"]

    def test_backends_token_identical(self, sweep_runs):
        """Greedy decode: the pallas kernel path must match XLA per arch."""
        first, _ = sweep_runs
        by_combo = {(r.value["arch"], r.value["attn_backend"]): r.value for r in first}
        for arch in ARCHS:
            assert by_combo[arch, "xla"]["tokens"] == by_combo[arch, "pallas"]["tokens"]

    def test_sweep_composes_with_matrix_algebra(self):
        m = serve_matrix(ARCHS, backends=BACKENDS, n_requests=2) * {
            "parameters": {"prefix_sharing": [True, False]}
        }
        tasks = m.task_list()
        assert len(tasks) == 8
        assert {t.params["prefix_sharing"] for t in tasks} == {True, False}

    def test_serve_sweep_distributed_across_hosts(self, tmp_path):
        """The ROADMAP item: a serve sweep drained through the file-queue.
        One 'host' executes the cells; a second host (same shared workdir +
        queue) assembles the identical full ResultSet without re-running —
        everything arrives via the shared cache / done records."""
        from repro.experiments import serve_sweep_distributed

        matrix = serve_matrix(
            ["llama3.2-3b"], backends=["xla"], scheduler={"n_slots": [2]},
            cache_len=64, n_requests=2, prompt_lens=(5, 9), max_new_tokens=3,
            warmup=False,
        )
        first = serve_sweep_distributed(
            matrix, queue_dir=tmp_path / "q", workdir=tmp_path / "w",
            owner="host-a",
        )
        assert [r.status for r in first] == ["ok"]
        assert first[0].value["generated_tokens"] == 2 * 3
        second = serve_sweep_distributed(
            matrix, queue_dir=tmp_path / "q", workdir=tmp_path / "w",
            owner="host-b",
        )
        assert [r.status for r in second] == ["cached"]
        assert second[0].value["tokens"] == first[0].value["tokens"]


class TestTrainSweep:
    def test_train_sweep_through_memento_and_cache(self, tmp_path):
        matrix = train_matrix(
            ["llama3.2-3b"], lrs=[1e-3], steps=4, seq_len=16, global_batch=2,
            ckpt_every=100, log_every=2, workdir=str(tmp_path / "ckpts"),
        )
        eng = memento.Memento(
            train_sweep,
            workdir=tmp_path / "memento",
            namespace="train",
            runner_config=_runner_config(),
        )
        first = eng.run(matrix)
        assert [r.status for r in first] == ["ok"]
        v = first[0].value
        assert np.isfinite(v["loss_first"]) and np.isfinite(v["loss_last"])
        assert v["steps"] == 4
        second = eng.run(matrix)
        assert [r.status for r in second] == ["cached"]
        assert second[0].value["loss_last"] == v["loss_last"]

    def test_namespaces_partition_a_shared_workdir(self, tmp_path):
        # serve and train sweeps can share one workdir without key collisions
        # even if their matrices coincide (the namespace splits them).
        m = {"parameters": {"arch": ["llama3.2-3b"]}}
        ka = memento.as_matrix(m).task_list(namespace="serve")[0].key
        kb = memento.as_matrix(m).task_list(namespace="train")[0].key
        assert ka != kb
