"""JAX's persistent compilation cache, kept in one fixed place.

The entry points (``python -m repro``, ``benchmarks/run.py`` and
``chip_smoke.py``) call `enable_compile_cache` before their first compile,
so that a second process on the same checkout finds the kernels and
mega-steps the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    and this sets no other directory.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, never one built from a
    temporary name, a process id or the time, since a cache that moves is
    never hit.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
