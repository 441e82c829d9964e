"""Where JAX keeps its persistent compilation cache.

Compiling the fused kernels and the jitted chunk steps at published size
takes most of a cold run, so entry points place JAX's persistent cache
before their first compile.  The path is part of the cache's key: a
directory derived from a temp name, PID or time would never hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "configure_compile_cache"]

#: Environment variable JAX itself reads the cache directory from.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: The fixed in-checkout fallback (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory.

    Call from an entry point's ``main()`` before the first compile, never
    at import.  When ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX has already
    read it and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
