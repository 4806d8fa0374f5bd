"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_persistent_cache()`` before their first compile, so
a second process in the same checkout loads the programs the first one
compiled instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the cache directory is part of what a later run must match,
# so it is never derived from a temp name, a pid or the time.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Point JAX's compilation cache at the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` is set, in which case JAX reads that
    variable itself and nothing is changed.  Returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
