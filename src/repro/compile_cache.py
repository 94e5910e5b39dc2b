"""JAX's persistent compilation cache for the entry points.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable` before their first compile.  Tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache directory is part of the
# cache key, so a path derived from a pid, the time or a temp name never
# hits.  Git-ignored.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else the checkout's
    ``.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set in code."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return cache_dir()
