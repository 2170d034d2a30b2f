"""JAX's persistent compilation cache for the repo's entry points.

`chip_smoke.py` and `benchmarks/run.py` call `enable_compile_cache(root)`
before their first compile. `JAX_COMPILATION_CACHE_DIR`, when set, wins: JAX
reads it itself and no other directory is set here. Otherwise the cache
lives at the fixed path `<root>/.jax_compile_cache`: the path is part of the
cache key, so it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DIRNAME = ".jax_compile_cache"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(os.path.abspath(root), DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under JAX's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
