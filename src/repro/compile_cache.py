"""Where the repo's entry points keep JAX's persistent compilation cache.

A cache entry is found again only at the same path, so the default is a
fixed directory inside the checkout (``<checkout>/.jax_cache``, listed in
``.gitignore``) and never a temporary or per-process one. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory the cache lives in: the environment's, else the
    checkout's ``.jax_cache``."""
    return (os.environ.get(_ENV)
            or str(Path(__file__).resolve().parents[2] / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile. Sets nothing when the environment
    variable is set, since JAX has already taken the directory from it.
    """
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
