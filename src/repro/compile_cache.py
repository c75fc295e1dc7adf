"""JAX's persistent compilation cache, in one place for every entry point.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the benchmark and
launch modules' ``main``) call :func:`enable_compile_cache` first thing;
nothing sets a cache at import.  A ``JAX_COMPILATION_CACHE_DIR`` in the
environment wins -- JAX reads it itself, so no other directory is set --
and otherwise the cache lives at ``.jax_cache/`` in the checkout: one
fixed path, so a later run in the same checkout finds what an earlier one
compiled (the path is part of the cache key; a moving directory never
hits).
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
