"""Where JAX keeps its persistent compilation cache for the entry points.

The cache is found again only when a later run points at the same
directory, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set
(JAX reads the variable itself), else ``.jax_cache`` at the root of the
checkout.  Entry points call ``enable_compile_cache()`` before their
first compile; the test suite never does.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
