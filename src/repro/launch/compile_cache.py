"""JAX's persistent compilation cache, at one fixed place.

Launchers and ``chip_smoke.py`` call :func:`enable_compile_cache` before
they compile anything. Importing ``repro`` never does: tests and library
users keep whatever cache configuration they chose.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/src/repro/launch/compile_cache.py -> <repo>
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
    the path is part of each entry's key, so it never varies from run to
    run (no tmp name, pid or timestamp)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
