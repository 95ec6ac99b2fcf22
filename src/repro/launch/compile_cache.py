"""Persistent XLA compilation cache for the entry points.

Called from the ``main()`` of the launchers and from ``chip_smoke.py``, never
when ``repro`` is imported.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is
set; otherwise the cache lives at the fixed, git-ignored ``.jax_cache/`` at
the root of the checkout.  The directory is part of the cache key, so it is
never derived from a temporary directory, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
