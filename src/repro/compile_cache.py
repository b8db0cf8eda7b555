"""Where JAX's persistent compilation cache lives for this checkout.

Entry points (``chip_smoke.py``, the ``benchmarks`` mains) call
:func:`enable_compile_cache` before their first compile; the library never
calls it at import.  A set ``JAX_COMPILATION_CACHE_DIR`` is used as given
(JAX reads it itself).  Otherwise the cache is ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later process must
find again.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

__all__ = ["CACHE_DIR", "enable_compile_cache"]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its place; returns it."""
    import jax

    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
