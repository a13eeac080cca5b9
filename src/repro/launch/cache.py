"""Where JAX's persistent compilation cache lives.

Call ``enable_compile_cache()`` once, before the first compile. The cache
path is part of every entry's key, so it must not move between runs: it is
never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
