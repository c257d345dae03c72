"""JAX persistent compilation cache placement for the entry points.

Called at the start of ``train.main``, ``serve.main`` and
``chip_smoke.py``, never at import. ``JAX_COMPILATION_CACHE_DIR``, when
set, is read by JAX itself and nothing else is configured; otherwise the
cache lives at the fixed ``<checkout>/.jax_cache`` so that a later run
from the same checkout finds its entries (the directory is part of the
cache key, so it never depends on a pid, a timestamp or a temp name).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
