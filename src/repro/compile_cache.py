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

    Call before the first compile. Sets no directory when the environment
    variable is set, since JAX has already taken the directory from it.

    The cache key includes the programs' metadata. By default JAX strips
    it from the key, and an entry then hands back the executable of
    whichever program with the same operations was compiled first, with
    that program's op names: a program whose ``jax.named_scope`` stages
    (``repro.core.segments.QUERY_STAGES``) changed, or were added, would
    show the old names, or none, in a profiler trace.
    """
    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
