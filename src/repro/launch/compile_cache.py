"""Persistent XLA compilation cache for the entry points.

A full-width serving step compiles for seconds to tens of seconds; the
cache lets a second process (or a second engine in the same process) load
the executable instead.  JAX keys the cache on the directory too, so the
directory must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — that directory and no other
    (JAX reads the variable itself; this only re-applies it when the
    variable was set after ``jax`` was imported);
  * unset — ``<checkout>/.jax_cache``, a fixed path that git ignores.

Called by ``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks.serving_bench``; never at import and never by tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (``.gitignore`` lists it).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(CHECKOUT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
