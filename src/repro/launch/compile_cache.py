"""JAX's persistent compilation cache, for entry points that run on a chip.

The entry point ``chip_smoke.py`` calls :func:`enable_compile_cache` once
at startup; importing the library never turns the cache on. A cache that
moves never hits, so the default location is fixed: ``<checkout>/.jax_cache``,
never a temporary, per-process or time-stamped directory.
``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left alone (JAX reads
it itself).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def enable_compile_cache(checkout: str | Path) -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
