"""JAX's persistent compilation cache, placed once for every entry point.

A fresh process on a fresh machine compiles every executable again; the
persistent cache lets later processes of the same checkout skip that.  The
cache key includes the directory, so it lives at one fixed path.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    Call it from an entry point, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
