"""One place for JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``, the examples) call :func:`enable_compile_cache`
before their first compile, so every process of a checkout shares one
cache:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps the cache
  there and this module sets no other directory;
* otherwise the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed — no temporary directory, pid or
  time — because it is part of the cache key: a directory that moves
  never hits.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory (see the module docstring for where it goes)."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
