"""JAX persistent compilation cache for the repo's entry points.

A cold chip run compiles every AOT session executable from scratch; the
persistent cache lets the next process in the same checkout load them
instead.  Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the
``impact_throughput`` / ``impact_train`` mains) call
``use_compilation_cache`` once at start-up; importing the package never
touches the cache.

The cache key includes the directory, so the path is fixed:
``<repo>/.jax_cache`` (git-ignored).  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and no other path is set here.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<repo>/.jax_cache``: this file sits at ``<repo>/src/repro/``.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compilation_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``REPO_CACHE_DIR``.
    Every compile is cached (no minimum compile time): a kernel compiles
    in well under the default one-second floor, and a smoke run is made
    of such compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
