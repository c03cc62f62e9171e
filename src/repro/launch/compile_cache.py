"""JAX persistent compilation cache at a stable place.

A path made from a temp dir, a pid or the time is new on every run, so a
later run would find nothing there.  Entry points call
``enable_compile_cache`` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is changed; otherwise the cache goes to ``.jax_cache/`` at the repo root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
