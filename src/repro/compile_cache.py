"""JAX's persistent compilation cache, placed where a run can find it again.

``enable()`` is called by the entry points that compile the whole model
(``chip_smoke.py``, ``benchmarks/run.py``).  Tests leave the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed default location: the cache key includes the path, so a
#: directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to ``.jax_cache/``
    at the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
