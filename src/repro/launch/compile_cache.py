"""Where JAX keeps its persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when the environment sets it, wins: JAX
reads it itself and this module sets no other path.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache`` (never
a temporary, pid- or time-derived directory: a cache that moves never
hits).  Entry points that compile for the chip call
``enable_compile_cache()`` before their first compile; tests leave the
cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> Path:
    """The directory ``enable_compile_cache`` points JAX at."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else REPO_CACHE


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
