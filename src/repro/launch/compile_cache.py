"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points call ``enable_compile_cache()`` before their first compile;
importing the library never does.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins and is left to JAX as it is.  Otherwise the cache lives in
``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
because the path is part of every cache key and a directory that moves
never hits.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
