"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once from ``main()`` — never at
import time and never from tests, so importing ``repro`` or running the
suite writes no cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this helper sets nothing.
* Otherwise: the fixed path ``<checkout>/.jax_cache`` (git-ignored).  The
  path is part of a cache entry's key, so it is never a temp, pid or
  time-stamped directory: a path that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
