"""Checkout paths, the persistent compilation cache and seed keys.

``configure()`` runs before JAX is imported: it points JAX's persistent
cache at a fixed directory inside the checkout (the directory is part of
the cache key, so it never moves) and keeps the TPU runtime's logs out of
fixed paths outside the checkout.
"""
from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
WORK = os.path.join(ROOT, ".bench_work")          # git-ignored
CACHE_DIR = os.path.join(WORK, "jax_cache")
TRACE_DIR = os.path.join(WORK, "trace")


def configure() -> None:
    """Environment for one benchmark process; call before importing jax."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # no size cap: a cap turns on LRU eviction, and a cell's programs must
    # all be found again by its next run
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.makedirs(CACHE_DIR, exist_ok=True)
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, traffic mix or cell."""
    path = os.path.join(BENCH, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def prng_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``. Seeds may exceed 32 bits:
    ``PRNGKey`` keeps only the low 32, so the high bits are folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def np_rng(seed: int, stream: int):
    import numpy as np
    return np.random.default_rng([seed, stream])
