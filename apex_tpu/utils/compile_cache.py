"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmark/run.py``,
the examples, ``tests/conftest.py``): if ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it and nothing here sets a directory in code; otherwise
the cache is ``<checkout>/.jax_compile_cache`` — a fixed path, because
the directory is part of what makes a later run find the entries again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["configure_compile_cache", "default_cache_dir"]


def default_cache_dir() -> str:
    """``<checkout>/.jax_compile_cache`` (the directory that holds the
    ``apex_tpu`` package; listed in ``.gitignore``)."""
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_compile_cache")


def configure_compile_cache() -> str:
    """Place the compile cache and return the directory in use.  Call
    before the first compile of the process."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
