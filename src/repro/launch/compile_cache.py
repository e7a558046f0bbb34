"""JAX's persistent compilation cache, at one fixed place.

A directory that is new on every run never finds what an earlier run
compiled, so the directory is fixed.  ``JAX_COMPILATION_CACHE_DIR``,
where it is set, is left to JAX, which reads it itself; otherwise the
cache lives in ``.jax_cache`` at the root of the checkout (listed in
``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
