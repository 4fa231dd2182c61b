"""JAX's persistent compilation cache for a benchmark run.

Only the first run of a cell in a checkout compiles; later runs load every
program. The directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set
(JAX reads it itself), else ``<checkout>/.jax_cache``. A cache directory
that moves never hits, so it is never a temporary or per-process path.
"""
from __future__ import annotations

import os
from pathlib import Path


def enable_compile_cache(checkout: Path) -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(checkout).resolve() / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, however quick to compile: a run pays the
    # compile of any program left out, in its set-up, every time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileStats:
    """Seconds spent getting executables (compiling or loading them from
    the persistent cache), and the persistent cache's hits and misses."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": self.compile_s, "compiles": self.compiles,
            "cache_hits": self.hits, "cache_misses": self.misses,
        }
