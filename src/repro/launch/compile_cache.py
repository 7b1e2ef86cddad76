"""Persistent XLA compile cache for the command-line entry points.

``use_compile_cache()`` is called by scripts under their ``__main__``
guard (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``) and
never at library import, so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed, so that a later process in the same checkout finds the
#: entries again; git ignores it
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Keep compiled executables across processes and return where.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache lives at
    ``<repo>/.jax_cache``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
