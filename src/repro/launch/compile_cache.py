"""Placement of JAX's persistent compilation cache.

Entry points call :func:`configure_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``:
    a fixed path (never a temporary, pid- or time-stamped one), so a later
    run of the same checkout finds what an earlier one compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
