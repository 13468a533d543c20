"""JAX's persistent compilation cache, at one fixed place per checkout.

The entry points call :func:`enable_compile_cache` before their first
compile, so a second process over the same checkout (a restarted server, the
next ``chip_smoke.py`` run) loads its programs instead of compiling them.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set. Otherwise the cache is ``<checkout>/.jax_cache``,
    a path built from no temporary name, pid or time, so every run over
    the checkout finds what the runs before it wrote.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
