"""Persistent JAX compilation cache for the entry points.

A cold full-width run compiles one chunked-prefill program per context
width, a decode step per context width and a verify step per `n_ctx`, each
a whole-model program; the persistent cache lets the next process on the
same machine skip them. Entry points (`chip_smoke.py`,
`examples/analytics_serving.py`) call `enable_compilation_cache()` once,
before anything is jitted.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at `<checkout>/.jax_cache`: a fixed
path, never a temp name, since a directory that moves between runs never
hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
