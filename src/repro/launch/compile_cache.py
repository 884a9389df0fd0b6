"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``launch/train.py``,
``launch/serve.py`` and ``chip_smoke.py`` when they start, never at
import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps
its cache there and nothing is changed. Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of
what a later run must find again.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
