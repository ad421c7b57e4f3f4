"""Where JAX's persistent compilation cache lives for the command-line entry
points (``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m repro.launch.train``).

The cache key includes the cache path, so the path must not move between
runs: with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it and nothing is
set here; otherwise the cache sits at the fixed ``<checkout>/.jax_cache``
(gitignored).  Library code and tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
