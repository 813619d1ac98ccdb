"""Where JAX keeps its persistent compilation cache.

Call ``enable_compile_cache()`` once per process, before the first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing else is configured here.  Otherwise the cache lives
in one fixed directory of the checkout, ``.jax_cache`` (git-ignored),
because the cache key includes the path.  A process pinned to the CPU
(``JAX_PLATFORMS=cpu``, the test mode) gets no cache, so test runs
never fill that directory.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Place the cache; return its directory, or None when it is off."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
