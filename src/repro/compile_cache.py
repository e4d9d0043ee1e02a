"""Where JAX keeps compiled executables between runs.

A cold run on the chip compiles every solver executable, seconds to tens
of seconds each.  Entry points that run on the chip — ``chip_smoke.py``, the
``benchmarks/`` drivers and ``examples/`` — call
:func:`enable_compile_cache` before their first compile, so a later run
on the same disk loads them instead.  Importing :mod:`repro` never turns
the cache on: the tests must not read or write a persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (listed in .gitignore).  A fixed path, never a
# temp-, pid- or time-derived one, so the next run finds it again.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turns on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and no
    other directory is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
