"""Persistent JAX compilation cache, placeable from outside.

Every process that compiles the serving or training programs calls
:func:`enable` before its first compile. ``JAX_COMPILATION_CACHE_DIR``,
when set, is honoured as JAX reads it and nothing else is configured;
otherwise the cache lives in ``.jax_cache/`` at the root of this checkout —
a fixed path, so a later process of the same checkout finds its entries.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
