"""Where the entry points keep JAX's persistent compilation cache.

Only entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) call this; library modules and tests leave the cache
alone.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
nothing is set here.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
fixed path, since the path is part of the cache key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
