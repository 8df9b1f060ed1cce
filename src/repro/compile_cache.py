"""Persistent XLA compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``benchmarks.run``, ``repro.launch.serve``)
call ``configure()`` once at start, never at import.  A cold process on a
fresh machine otherwise recompiles every engine and model step; with the
cache, a second process with the same programs loads them from disk.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (the path is part of what a later process has
#: to find again) and listed in ``.gitignore``
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is changed here; otherwise the cache goes to ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
