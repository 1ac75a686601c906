"""JAX's persistent compilation cache, placed from outside or in the repo.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls ``enable_compile_cache``
first, so a process reuses what an earlier one compiled.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache lives at the fixed path ``<repo>/.jax_cache``
(git-ignored).  The path is never built from a temp name, a pid or the time:
a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
