"""The one persistent compilation cache of every entry-point script.

JAX keys a cached executable by the program and keeps it under one
directory; a directory that moves between runs never hits.  So the cache
lives at ONE path: ``JAX_COMPILATION_CACHE_DIR`` where the environment
sets it (JAX reads that variable itself, and nothing here overrides it),
otherwise ``.jax_cache/`` at the root of this checkout — never a
temporary, per-process or time-stamped name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Call it before the first compilation.  Importing the package does not
    call it: tests and library users keep whatever cache they configured.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
