"""JAX's persistent compilation cache, switched on by the entry points.

A fresh process compiles every executable it runs, which at published
widths takes minutes. With the cache on, a later process that runs the
same programs loads them from disk instead. Library code and tests never
call this: a test must not write the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (this file is <repo>/src/repro/launch/compile_cache.py)
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory. Otherwise
    it is ``<repo>/.jax_cache``: a fixed path, so that every run of this
    checkout finds what the runs before it compiled.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    # keep every executable, not only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
