"""Persistent XLA compilation cache for the entry points.

A full-width model takes tens of seconds to compile per step function; the
persistent cache lets a second run of the same program on the same chip
reuse those executables.  The cache directory is part of each entry's key,
so it must be a fixed path: never a temporary name, process id or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives at a fixed
    ``.jax_cache/`` in the checkout.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = REPO_ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
