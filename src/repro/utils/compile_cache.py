"""Placement of JAX's persistent compilation cache.

Every entry point that compiles at full size (``chip_smoke.py``,
``launch.train``, ``launch.cluster``, ``benchmarks/run.py``) calls
:func:`setup_compile_cache` first, so a second run of the same programs
loads their executables instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout, git-ignored: the cache directory is part of
# what a later run must find again, so it is never derived from a
# temporary name, a pid or the time
CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    JAX's own setting and is left to JAX; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
