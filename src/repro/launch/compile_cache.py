"""Persistent XLA compile cache for the entry points.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache`` (git-ignored), so the next run of the same
checkout finds what this one compiled. The path never depends on a temp
directory, a pid or the time: it is part of each entry's key.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
