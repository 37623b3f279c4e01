"""Where JAX keeps its persistent compilation cache — decided in one place.

Call :func:`enable_compile_cache` once, before the first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
directory is set here; otherwise the cache lives at the caller's fixed
``default_dir``.  The directory is part of what a cache hit needs, so it is
never a temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Union

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(default_dir: Union[str, Path]) -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every compiled program is cached (the simulator's programs compile in
    about a second, under JAX's default one-second threshold).
    """
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(default_dir)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
