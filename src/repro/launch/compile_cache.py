"""Where the persistent XLA compilation cache lives.

Entry points (``launch/serve.py``, ``launch/train.py``,
``chip_smoke.py``) call ``enable_compile_cache()`` before anything
compiles.  The directory is placed from outside the program:

  * ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself and this
    code sets no other directory;
  * unset -> ``<checkout>/.jax_cache`` (git-ignored).

The directory is looked up by path, so it is fixed: never built from a
temp name, a pid or the time.  A warm cache turns the engine's slot-grid
warm-up (tens of executables per model) into reads.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
