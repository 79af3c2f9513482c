"""Persistent XLA compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
overrides it. Unset: the cache lives at the fixed ``<checkout>/.jax_cache``
(git-ignored). The directory is part of what makes a cache entry findable
again, so it is never derived from a temp dir, a pid or a clock.

Call ``setup_compile_cache()`` before the first compile (the train and
serve drivers and ``chip_smoke.py`` do).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
