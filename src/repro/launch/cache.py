"""Placement of JAX's persistent compilation cache.

A compiled program is keyed by, among other things, the cache directory it
is read from, so the directory must not move between runs: it is either
what ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself,
and nothing is set here) or one fixed, git-ignored path inside the
checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (call before
    the first compile) and return that directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
