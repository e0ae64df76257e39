"""Persistent XLA compilation cache for the entry points.

Called by the entry points (``chip_smoke.py``, ``benchmarks.run``,
``repro.launch.serve``, ``repro.launch.train``), never at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed.  Otherwise the cache goes to ``.jax_cache/`` at the repository
root: a fixed path, because the path is part of the cache key and a
directory that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
