"""JAX's persistent compilation cache, at a place the next run finds again.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``examples/serve.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile; nothing calls it at
import.  A run finds only what earlier runs wrote to the same directory, so
it must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the repo's."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX
    already reads it, and this sets no other directory."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
