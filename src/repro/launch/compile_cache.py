"""JAX's persistent compilation cache, set up the same way by every entry
point (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``) and by
the test suite.

The directory is part of the cache's key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself;
set it empty to turn the cache off), otherwise ``.jax_cache`` at the root
of the checkout. Never a temporary or per-process path.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on; returns its directory (None = off).

    Touches no backend, so it is safe before the chip is taken."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env == "":
        return None
    if env is None:
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    # cache every program: the round and serve programs repeat across runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return env or str(CHECKOUT_CACHE)
