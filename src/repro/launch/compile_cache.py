"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` before their first compile,
so a second run of the same program on the same device reads its compiled
executables back instead of compiling again. The cache's directory is part
of every entry's lookup, so it is a fixed path, never a temporary one.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The default cache location: ``<repo>/.jax_cache`` (listed in .gitignore).
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    A ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX reads it
    itself and nothing is set here. Otherwise the cache lives in
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
