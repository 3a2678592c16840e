"""One persistent JAX compilation cache for the repo's entry points.

Compiling the emulated GEMM for a TPU takes tens of seconds per policy; the
cache lets later processes on the same machine skip it. Its directory is
part of what makes an entry findable again, so it is fixed, never derived
from a temp dir, a pid or the time.
"""
from __future__ import annotations

import os

import jax

#: Environment variable JAX itself reads for the cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Default directory: ``.jax_cache`` at the checkout root (gitignored).
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    A directory given in ``JAX_COMPILATION_CACHE_DIR`` is honoured as is
    (JAX reads the variable itself; nothing is set here). Otherwise the
    cache lives in ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
