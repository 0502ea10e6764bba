"""Where the persistent XLA compile cache lives for runs on a chip.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins:
this module then sets nothing.  Otherwise :func:`enable_compile_cache`
points the cache at ``<checkout>/.jax_cache``.  The path is fixed (never a
temporary name, a pid or the time) because it is part of what a later run
must find again.

Only entry points call this (``chip_smoke.py``, the benchmark scripts'
``__main__`` blocks); importing the library or running the tests leaves the
cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
