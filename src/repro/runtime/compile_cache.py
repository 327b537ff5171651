"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
this module sets nothing. Otherwise the cache goes to one fixed directory
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``).
The path is part of the cache's key, so it never depends on a temporary
name, a process id or the time: a later run from the same checkout finds
what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory (the environment's, or the fixed in-checkout default)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
