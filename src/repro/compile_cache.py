"""Persistent XLA compilation cache for the repository's entry points.

A cold run on a TPU compiles every engine program again; the cache keeps
the compiled executables between runs.  Its path is part of the cache
key, so it is fixed: `JAX_COMPILATION_CACHE_DIR` when set (JAX reads that
variable itself, so nothing is changed here), else `<repo>/.jax_cache`.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
