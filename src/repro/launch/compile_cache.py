"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py`` and the ``benchmarks/`` mains call :func:`use_compile_cache`
once, before their first compile. Library code never calls it, so tests
and importers keep JAX's own setting.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  set.
* Otherwise the cache lives at a fixed path inside the checkout
  (``<repo>/.jax_cache``, gitignored). The path is part of the cache key,
  so it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
