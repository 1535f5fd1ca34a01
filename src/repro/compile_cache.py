"""Persistent compilation cache placement for the program's entry points.

Scripts (``chip_smoke.py``, ``benchmarks/*``, ``examples/*``) call
:func:`enable_compile_cache` once before they compile anything; library
imports and tests never do.  ``JAX_COMPILATION_CACHE_DIR``, when set,
wins and JAX reads it itself.  Otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored), never a
temporary or per-process name: a directory that moves between runs is
never found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
