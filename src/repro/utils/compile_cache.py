"""Persistent JAX compilation cache for the program entry points.

Called by ``chip_smoke.py``, ``repro.launch.serve.main`` and
``benchmarks.run.main`` — never at import, so library users and the
tests keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# A fixed directory in the checkout (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, and no
    other directory is set), else ``.jax_cache/`` at the checkout root."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
