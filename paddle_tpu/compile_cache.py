"""Where the persistent XLA compile cache lives.

Entry points (chip_smoke.py, bench.py, the tools/_*_ab.py mains,
tests/conftest.py) call `configure()` once before their first compile. The
directory is part of a cache entry's key, so it must not move between runs:
it is either whatever JAX_COMPILATION_CACHE_DIR says (jax reads that
itself; nothing is set here) or `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure() -> str:
    """Point jax's persistent compile cache at its fixed place and return
    the directory in use. jax's default write thresholds are kept."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
