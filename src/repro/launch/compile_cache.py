"""JAX's persistent compilation cache, set in one place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call :func:`enable_compile_cache` from their main
function — never at import, so importing the library changes no JAX state.

The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.jax_cache``: the cache path is part of the
cache key, so it must not carry a temp name, a PID or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
