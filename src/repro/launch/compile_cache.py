"""JAX's persistent compilation cache, placed for the entry points.

The entry points (``launch.train``, ``launch.serve``, ``chip_smoke.py``)
call ``enable()`` once at start; nothing calls it on import, so library
users and the test suite keep whatever cache setting they have.

Placement: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets no directory. Otherwise the cache lives in
``CACHE_DIR``, one fixed directory inside the checkout, listed in
``.gitignore``. The path is part of every cache key, so it never
depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
