"""Persistent XLA compile cache, switched on by the entry points.

Only entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_compile_cache`;
importing a library module never changes JAX's configuration.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this module sets nothing. Otherwise the cache lives in ``.jax_cache/``
at the root of the checkout (listed in ``.gitignore``): one fixed path,
so later runs from the same checkout find what earlier runs compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
