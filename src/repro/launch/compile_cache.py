"""JAX's persistent compilation cache, kept in one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve``) call ``enable_compile_cache()`` before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here; otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout (gitignored).  The directory is fixed — never a
temporary name — because a later run finds its entries only under the
same path.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py → <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory compiled programs are cached in."""
    return os.environ.get(ENV) or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on (in-checkout default unless the
    environment names a directory); returns the directory."""
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return compile_cache_dir()
