"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once, before their first compile; library
code and tests never do.  The cache key includes the directory, so the
path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache`` — never a temporary name, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/core/compile_cache.py → the checkout root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["use_compile_cache", "DEFAULT_DIR", "ENV_VAR"]
