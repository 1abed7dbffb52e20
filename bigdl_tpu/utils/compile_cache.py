"""Where XLA's persistent compile cache lives — one rule, applied by
every entry point (``models/train.py``, ``models/perf.py``,
``benchmark/run.py``, ``chip_smoke.py``, ``InferenceServer.start``).

The directory is part of the cache key's world: a path that moves never
hits.  So the place is chosen from OUTSIDE the program when
``JAX_COMPILATION_CACHE_DIR`` is set — jax reads that variable itself
and this module sets nothing — and is otherwise one fixed, git-ignored
directory inside the checkout, the same for a trainer, a replica and a
benchmark, so each finds what the others compiled.  jax's own floors
(compile time, entry size) stay as they are.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "ensure_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure a persistent compile cache is in effect and return its
    directory.  Idempotent; call before the first compile."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
