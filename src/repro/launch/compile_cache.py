"""Where JAX keeps its persistent compilation cache.

A compiled program is cached under a key that includes the cache's own
path, so the path must not move between runs: a directory named after
a temporary file, a process id or the time never hits.  The launchers
call :func:`use_compile_cache` once, before their first compile; it is
never called on import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    (git-ignored)."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
