"""Where compiled programs are kept between processes.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, the
examples, the segment recorder): the directory is placed from OUTSIDE
through ``JAX_COMPILATION_CACHE_DIR`` — jax reads that variable itself —
and only when nothing placed it does the program fall back to one fixed
path, ``<checkout>/.jax_cache`` (gitignored). The path is part of the
cache key, so it never moves with the user, the temp dir or the pid.
"""

from __future__ import annotations

import os

import jax

__all__ = ["ensure_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    A directory an application configured itself (a ``jax.config``
    update, e.g. the test suite's) is left alone with its thresholds.
    A directory from the environment variable is used as placed — no
    other is set in code — and, like the fallback directory, is made to
    keep EVERY program: jax's default skips what compiled in under a
    second, and the first call of a ``to_static`` function runs eagerly
    (a thousand sub-second programs that a second process would
    otherwise compile again)."""
    placed = jax.config.jax_compilation_cache_dir
    if placed and placed != os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return placed
    if not placed:
        placed = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed
