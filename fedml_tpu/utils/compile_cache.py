"""One place that decides where JAX's persistent compile cache lives.

Every entry point calls ``configure_compile_cache()`` before its first
compilation.  ``JAX_COMPILATION_CACHE_DIR``, where set, is left alone —
JAX reads it into its own config — so a caller (a chip run, a CI job)
can place the cache from outside.  Otherwise the cache is one fixed
directory inside the checkout: the path is part of the cache key, so a
directory named after a pid, a time or ``tempfile`` never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache(min_compile_secs: float = 2.0) -> str:
    """Point this process at the persistent compile cache and return its
    directory.  ``min_compile_secs`` is the smallest compilation worth
    writing to disk (programs 2.0; the test suite passes 0.0 so that
    identical small programs dedupe across test modules)."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return path
