"""Where this repository's entry points keep JAX's persistent compile cache.

Call :func:`enable_compile_cache` once, at the start of an entry point and
before its first compile; nothing is configured at import time.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
    directory is set in code.
  * unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path
    (listed in .gitignore), because the directory is part of the cache key
    and a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout-local cache directory used when the environment names none
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
