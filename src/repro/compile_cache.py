"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``configure_compile_cache()`` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout (git-ignored): a fixed path, so a later run of the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
