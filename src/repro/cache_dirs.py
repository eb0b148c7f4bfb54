"""Persistent caches, kept inside the checkout.

Two caches outlive a process: JAX's compiled programs and the kernel
autotune decisions (``repro.kernels.dispatch``).  Both default to fixed
paths under ``<checkout>/.cache/`` (listed in ``.gitignore``): a compile
cache whose directory moves never hits, and a checkout measured beside
another (a parent and its change) never takes the other's tile decisions.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ROOT = os.path.join(REPO_ROOT, ".cache")
AUTOTUNE_CACHE_PATH = os.path.join(CACHE_ROOT, "autotune.json")
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Called by the entry points before their first compile, never at import
    (the test suite compiles for described chips it cannot read back).
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing here changes it; otherwise the cache goes to
    ``<checkout>/.cache/jax``."""
    env = os.environ.get(COMPILE_CACHE_ENV)
    if env:
        return env
    import jax
    path = os.path.join(CACHE_ROOT, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
