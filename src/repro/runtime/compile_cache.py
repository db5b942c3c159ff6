"""JAX's persistent compilation cache, at one fixed place.

Entry points that compile large programs (``chip_smoke.py``, the
training driver, the benchmark CLIs) call :func:`enable_compile_cache`
before their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already keeps its cache there and nothing else is set.  Otherwise
the cache goes to ``<repo>/.jax_cache``: the directory is part of each
entry's key, so it never depends on a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
