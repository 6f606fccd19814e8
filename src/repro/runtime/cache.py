"""Persistent XLA compilation cache, turned on by every entry point.

A solve at real size compiles for tens of seconds; with the cache a
second process compiling the same program reads it back instead.  The
directory is part of the cache's identity, so it never moves:

  * ``JAX_COMPILATION_CACHE_DIR`` set  -> JAX reads it itself; nothing
    else is configured here.
  * otherwise                          -> ``<checkout>/.jax_cache``
    (gitignored), one fixed path per checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: src/repro/runtime/cache.py -> the checkout root, three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.  Call before the first compile."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
