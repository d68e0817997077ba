"""Tests of the benchmark itself, on the CPU: ``python3 -m pytest bench/tests``.
They put ``bench/`` and the program's ``src/`` on the path and hold JAX to
the CPU."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import env  # noqa: E402

env.configure()

import jax  # noqa: E402

# the tests compile small programs on the CPU; keep them out of the cache
jax.config.update("jax_enable_compilation_cache", False)
