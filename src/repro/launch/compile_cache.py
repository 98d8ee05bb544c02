"""Persistent XLA compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``) calls
:func:`enable_compile_cache` once, before its first compile. It is never
called at package import, so library users and the test suite keep no
cache unless they ask for one.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it at
import and the cache goes there; no other directory is set in code.
Otherwise the cache goes to a fixed ``<checkout>/.jax_cache``: the path
is part of what a later run must find again, so it is never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where this process's compile cache lives (environment first)."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
