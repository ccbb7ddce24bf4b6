"""JAX's persistent compilation cache for the entry points.

Only entry points call `enable_compile_cache` (chip_smoke.py and
`launch/serve.py` main): importing a library module never changes where JAX caches, and the tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX has already read it and
    nothing is changed. Otherwise the cache lives at the fixed
    `<repo>/.jax_cache`: a fixed path, so a later run of the same checkout
    finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
