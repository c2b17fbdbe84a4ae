"""Persistent compilation cache for the entry points.

Every entry point (``repro.launch.sisso``, ``repro.launch.serve_sisso``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing else is set.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what the cache is found by, so a temporary or per-process path would never
be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
