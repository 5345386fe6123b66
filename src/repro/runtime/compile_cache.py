"""JAX persistent compilation cache placement for the entry points.

Called by the launchers, the bench mains and ``chip_smoke.py`` before their
first compile — never at import.  ``JAX_COMPILATION_CACHE_DIR`` in the
environment wins (JAX reads it itself, so nothing is set in code);
otherwise the cache goes to one fixed directory inside the checkout,
``<repo>/.jax_cache`` (git-ignored).  The path is part of what makes a
cache entry findable again, so it is never a temporary name, a process id
or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["configure_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
