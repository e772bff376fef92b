"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``repro.launch.train`` and ``benchmarks.run``
before their first compile — never on import, so tests and library users
keep JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache lives at the fixed,
    git-ignored ``<repo>/.jax_cache``: the path is part of what a later run
    must find again, so it never carries a temp name, a pid or a time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
