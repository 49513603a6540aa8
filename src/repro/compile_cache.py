"""Where the entry points keep JAX's persistent compilation cache.

Importing the library sets nothing; the scripts (``chip_smoke.py``,
``benchmarks/chip/run.py``, ``examples/*``) call
:func:`enable_compile_cache` once at start-up.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's own cache directory (gitignored).  The path is part of
#: the cache key, so it is fixed: never temporary, per-process or dated.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
