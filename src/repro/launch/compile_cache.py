"""Persistent compilation cache placement for the entry points that run on
a chip (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``).

Importing the package sets nothing, so tests stay uncached; an entry point
calls ``use_compile_cache()`` once, before its first compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "REPO_CACHE_DIR"]

# a fixed path: the cache directory is part of the cache key, so a path
# built from a temp name, a pid or the time would never hit
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    this sets no other directory.  Otherwise the cache lives at
    ``<repo root>/.jax_cache`` (git ignores it).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
