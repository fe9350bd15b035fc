"""Where JAX keeps its persistent compilation cache for this repository.

One rule, used by ``chip_smoke.py`` and ``benchmarks/run.py``: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here; otherwise the cache lives at the fixed path ``<repo>/.jax_cache``
(git-ignored).  The path is part of the cache key, so it must not move
between runs for later processes to hit.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the smoke's kernels compile in well under the
    # default one-second threshold but there are hundreds of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
