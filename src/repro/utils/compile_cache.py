"""JAX's persistent compilation cache, for the entry points.

Called by ``launch/train.py`` and ``chip_smoke.py`` when they start, never
on import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is
the cache and no other is set. Otherwise the cache is ``<repo>/.jax_cache``:
a fixed path, because a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
