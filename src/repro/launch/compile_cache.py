"""JAX's persistent compilation cache for the entry points.

A chip machine starts with no compiled programs; keeping compiled XLA
executables on disk lets later runs on the same disk skip compilation. The
cache directory is part of the cache key, so it is a fixed path and never
built from a temporary name, a pid or the time. Entry points call
`enable_compile_cache()` from their `main`; importing this module sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py);
# listed in .gitignore
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Optional[dict] = None) -> str:
    """The directory the cache uses: $JAX_COMPILATION_CACHE_DIR when set
    (JAX reads that variable itself), else the fixed in-repo path."""
    env = os.environ if environ is None else environ
    return env.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. When the
    environment variable is set, JAX already uses it and nothing else is
    set here."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
