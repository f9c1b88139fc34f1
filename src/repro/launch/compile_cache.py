"""Where JAX's persistent compilation cache lives for this checkout.

A cache hits only when the directory is the same from one process to the
next, so the default is one fixed directory inside the checkout, derived
from this file's location — never a temp name, a pid or a time.  A
deployment that sets ``JAX_COMPILATION_CACHE_DIR`` keeps the cache there:
JAX reads that variable itself, and nothing here overrides it.

Entry points call :func:`use_compile_cache` once at start-up; the tests
never do, so a test run writes no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["compile_cache_dir", "use_compile_cache"]


def compile_cache_dir() -> str | None:
    """The directory to set, or None when ``JAX_COMPILATION_CACHE_DIR`` is
    set and JAX already uses it.  The default, <checkout>/.jax_compile_cache,
    is git-ignored."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return str(Path(__file__).resolve().parents[3] / ".jax_compile_cache")


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`."""
    path = compile_cache_dir()
    if path is not None:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
