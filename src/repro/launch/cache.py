"""Placement of JAX's persistent compilation cache for the entry points.

:func:`use_compile_cache` is called first thing by each command-line entry
point (``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``), never when the library is imported.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of what a later run must find again
(git ignores it).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
