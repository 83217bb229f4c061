"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold start on a TPU compiles every step program, which can take as long
as the run itself.  The cache keeps compiled programs across processes.
Its directory is part of what a later run must find again, so it never
depends on a temporary name, a pid or the time: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself), otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

#: ``<checkout>/.jax_cache`` -- this file is ``src/repro/runtime/...``
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
