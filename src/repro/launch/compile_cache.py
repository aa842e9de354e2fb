"""JAX persistent compilation cache at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives in ``.jax_cache``
at the root of the checkout (git-ignored).  The path is part of each cache
entry's key, so it never depends on a temporary directory, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; return its directory."""
    # cache every program, not only those that took a second to compile:
    # the measured tuner compiles one small program per layer
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
