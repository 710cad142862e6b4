"""Where JAX keeps its persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` from the environment by itself;
when it is set, this module sets nothing. Otherwise the cache goes to a
fixed directory inside the checkout (``<repo>/.jax_cache``, gitignored):
the path is part of a cache entry's key, so it is resolved from the
package location and never from a temp name, a pid or the time.

Entry points call :func:`enable_compile_cache` once at start-up; importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
