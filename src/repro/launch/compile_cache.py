"""One home for JAX's persistent compilation cache.

``chip_smoke.py`` and the CLIs (``launch/pagerank.py``, ``launch/ppr_serve.py``)
call :func:`use_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path,
so a later run from the same checkout finds what an earlier one compiled (the
directory is part of the cache key, so a path built from a temp name, a pid or
the time would never hit).
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# src/repro/launch/compile_cache.py -> the checkout root, three levels up
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CHECKOUT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
