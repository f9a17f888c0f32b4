"""JAX's persistent compilation cache for the entry points.

``serve.py`` and ``chip_smoke.py`` call ``enable_compile_cache`` once, before
their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets nothing. Otherwise the cache lives in
``<checkout>/.jax_cache`` (ignored by git): a fixed path, because a cache
whose directory moves between runs is never hit. Tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
