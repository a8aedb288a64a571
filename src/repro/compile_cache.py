"""Persistent XLA compilation cache for the repo's entry points.

A full NODE training step (18 adaptive-solve layers) takes tens of
seconds to compile on a TPU; the cache lets a second process on the
same checkout load it instead.  Entry points call ``enable_compile_cache()`` first thing in
``main`` — never at import, so importing the library changes no global
jax configuration.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed per checkout: a directory named after a temp dir, pid or time
# would never be found again by the next process
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
