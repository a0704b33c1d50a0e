"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.pac_cluster``, ``repro.launch.dryrun``) calls
``setup_compile_cache`` once, before its first compile.  Processes that
compile the same programs then share one cache.  The directory is part of
the cache key, so it is a fixed path, never one made from a temporary
name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE", "setup_compile_cache"]

# inside the checkout, listed in .gitignore
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; no other directory is set here), else ``CHECKOUT_CACHE``.
    Returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
