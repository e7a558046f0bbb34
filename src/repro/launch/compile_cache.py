"""JAX's persistent compilation cache, at one fixed place.

A directory that is new on every run never finds what an earlier run
compiled, so the directory is fixed.  ``JAX_COMPILATION_CACHE_DIR``,
where it is set, is left to JAX, which reads it itself; otherwise the
cache lives in ``.jax_cache`` at the root of the checkout (listed in
``.gitignore``).

A program's key includes its metadata: each instruction's ``op_name``,
which carries the program's named scopes into the device trace.  JAX
leaves it out by default, and then a program that differs from a cached
one in its scopes alone loads the cached executable, whose ops name the
other program's scopes.  Source files enter the key relative to the
checkout, so the same code in another directory still finds its
programs.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

__all__ = ["CHECKOUT", "CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
