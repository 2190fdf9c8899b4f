"""JAX's persistent compilation cache for the entry points that run on a chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise, on a TPU, the cache goes to one fixed
directory inside the checkout (``.jax_cache``, gitignored), so a second run
from the same checkout finds the programs the first one compiled.  Off the
TPU nothing is written: the CPU test suite never fills the cache.

Called from ``launch/train.py:main`` and ``chip_smoke.py``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for this process; returns its
    directory, or None where nothing is cached (not a TPU)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
