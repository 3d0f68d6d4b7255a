"""JAX's persistent compilation cache for the repo's entry points.

Called by the launchers, the benchmarks and ``chip_smoke.py`` before they
compile anything, never at package import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is set here. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout, a fixed path (the path is part of each entry's key),
which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
