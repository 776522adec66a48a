"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX handles it and nothing
here overrides it.  Otherwise the entry points (``serve``, ``train``,
``benchmarks.run``, ``chip_smoke.py``) cache in ``<repo>/.jax_cache``: a
fixed path, because the path is part of the cache key and a directory
that moves never hits.  Importing this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the compilation cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
