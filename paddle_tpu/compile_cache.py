"""Where the persistent XLA compile cache lives.

Entry points (chip_smoke.py, benchmark/run.py, tools/_mc_ab.py,
tests/conftest.py) call `configure()` once before their first compile. The
directory is part of a cache entry's key, so it must not move between runs:
it is either whatever JAX_COMPILATION_CACHE_DIR says (jax reads that
itself; nothing is set here) or `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# jax writes an executable to the cache when its compile took at least this
# long (default 1 s). A serving program of one scanned layer compiles in
# about that: it was written in some runs and not in others, and the warm
# set-up of `zaya1_8b.decode.sat` drifted from 85 to 58 s over six runs of
# the same code (my chip run, PR 25). A quarter of a second still keeps the
# one-primitive programs of eager jax calls out.
MIN_COMPILE_SECS = 0.25


def configure() -> str:
    """Point jax's persistent compile cache at its fixed place and return
    the directory in use. Entries are written from `MIN_COMPILE_SECS` of
    compile time on; jax's other thresholds are kept."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
