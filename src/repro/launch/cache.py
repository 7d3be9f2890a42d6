"""Persistent XLA compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself: where it is set, that
directory stands and nothing is set here.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout.  The path is fixed (no pid, time
or temporary name) because a later run only finds what an earlier run cached
at the same path.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; call before the first
    compile.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
