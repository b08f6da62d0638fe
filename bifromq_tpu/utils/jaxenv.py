"""Where this checkout keeps JAX's persistent compilation cache.

Every entry point that compiles device programs (``python -m
bifromq_tpu``, ``dist.worker_main``, ``kv.store_main``, ``chip_smoke.py``,
``benchmarks/sut.py``) calls :func:`setup_compile_cache` before its first
jit. The
cache path is part of JAX's cache key, so it must not move between runs:
it is either wherever the operator points ``JAX_COMPILATION_CACHE_DIR``
(JAX reads that variable itself — nothing is set in code then) or the
fixed ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
