"""JAX's persistent compilation cache, at one fixed place.

Call :func:`enable_compile_cache` once, before the first compile, from an
entry point (``launch/train.py:main``, ``chip_smoke.py``) — never while a
module is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

#: ``.jax_cache`` at the root of the checkout (git-ignored).  A fixed path,
#: never built from a temporary name, a process id or the time, so the
#: next run of the same checkout finds what this one compiled.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else
    :data:`DEFAULT_CACHE_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
