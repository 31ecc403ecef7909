"""JAX's persistent compilation cache, set up once per entry point.

A 32-layer serving program takes a quarter of a minute to a minute and a
half to compile per executable; with the cache on, a second run of the
same program reads its executables back instead.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()   # before the first compile

``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX keeps the cache
and nothing here overrides it.  Otherwise the cache lives in a fixed
directory of the checkout (``.jax_cache/``, git-ignored): never a temp,
per-process or time-stamped path, which no later run would find again.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX reads the variable itself
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
