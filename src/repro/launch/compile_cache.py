"""JAX's persistent compilation cache for the repo's entry points.

Scripts (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile, so a second
run finds the programs the first one compiled.  Importing the library or
running the tests never calls it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache goes to ``<repo>/.jax_cache``
— a fixed path, since the path is part of what makes a later run hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository root (``src/repro/launch`` → three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
