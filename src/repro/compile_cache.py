"""Persistent XLA compilation cache placement.

A full-width step program takes tens of seconds to compile; the cache lets
a second process (or a second run on the same machine) load it instead.
The cache key includes the directory, so the directory must not move from
run to run.

Call :func:`enable_compile_cache` at the top of an entry point's ``main``
— never at import, so importing the package leaves JAX's configuration
alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: one fixed directory in the checkout (git-ignored)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets no other directory. Otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
