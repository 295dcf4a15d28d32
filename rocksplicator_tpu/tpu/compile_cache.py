"""The one place this repo points JAX's persistent compilation cache.

The merge-resolve programs take from seconds to minutes to compile for
the chip (PERF.md "Chip status"), and they are identical run to run, so
every process that launches them shares one on-disk cache:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  this helper sets nothing — the operator (or the chip tool) placed the
  cache;
- where it is not, the cache goes to ``.jax_cache/`` at the root of the
  checkout (git-ignored). The path is part of the cache key, so it is
  fixed, never a temp dir.

Callers run this before their first compile; processes that never
launch a kernel never call it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")
# programs cheaper than this recompile faster than a cache round-trip
MIN_COMPILE_SECS = 1.0


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache; returns the directory
    in effect. Idempotent."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return DEFAULT_CACHE_DIR
