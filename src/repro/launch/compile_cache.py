"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called once by every entry point that
compiles for the chip (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory from the
  environment itself, and nothing here names another;
* otherwise: ``<checkout>/.jax_cache/`` (listed in ``.gitignore``).  The path
  is fixed because it is part of the cache key: a directory that moves from
  run to run never hits.

Library code and the test suite never call it, so they compile without a
persistent cache.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
