"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points (``chip_smoke.py``, ``benchmarks.run``, the examples) call
:func:`enable_compile_cache` before their first compile.  A cache only
hits when its directory is the same from run to run (the path is part of
the key), so the fallback path is fixed: ``.jax_cache/`` at the repo
root, never a temporary name, a process id or a time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "CACHE_ENV", "DEFAULT_CACHE_DIR"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it: leave it
    alone and set no other path.  Otherwise use :data:`DEFAULT_CACHE_DIR`.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
