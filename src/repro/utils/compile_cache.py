"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once, before their first compile. The tests do
not: a test run compiles for the CPU, and nothing of that is worth keeping.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory, used when the environment names none.
#: It is a fixed path, so every run from this checkout finds the entries the
#: last one wrote.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it, and
    the cache stays there. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
