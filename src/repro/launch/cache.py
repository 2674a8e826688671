"""JAX's persistent compilation cache, at one fixed place per checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets no other path.  Otherwise the cache lives in ``.jax_cache`` at
the repository root — a fixed path, because the path is part of what a
later process must find again.  Entry points call ``enable_compile_cache``
before their first compile.
"""
from __future__ import annotations

import os
import pathlib

#: ``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/launch/cache.py``)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the compile cache uses: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<repo>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
