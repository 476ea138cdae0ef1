"""Persistent XLA compilation cache shared by the launchers.

A full-width serving run compiles for minutes; the persistent cache
lets the next process on the same machine load those programs instead.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed directory inside the checkout: every process of this checkout
# looks in the same place, so a second run finds the first run's entries
REPO_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                     / ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where that is set
    the cache stays there and nothing is set here.  Otherwise the cache
    goes to ``REPO_CACHE_DIR``.  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
