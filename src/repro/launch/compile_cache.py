"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that moves
(a temporary path, a pid, a timestamp) never hits. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the entry points place the cache at one fixed
directory of the checkout, ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache its directory; returns it.

    Call before the first compile. Leaves JAX's configuration untouched
    where ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
