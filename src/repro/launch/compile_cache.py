"""Persistent XLA compilation cache for the repository's entry points.

A fresh process starts with no compiled code; with the persistent cache
a later process loads a program instead of compiling it again. JAX keys
the cache on its directory, so the directory must not move between runs:
it is ``JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise
``.jax_cache/`` at the root of the checkout (git-ignored).

Entry points call :func:`enable_compile_cache` from ``main``; importing
this module changes nothing. Tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
