"""Where JAX's persistent compilation cache lives.

One rule, for every entry point that compiles (chip_smoke.py, bench.py):
the cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise
to ``<checkout>/.jax_cache`` — a fixed, gitignored path, because the
directory is part of what a later process must find again.  No other
cache directory is set in code (``MXNET_AOT_CACHE_DIR`` has no
default), and nothing here runs at package import: an entry point
calls `enable()` before its first compile.

This module imports nothing from the package at load time, so the path
rule can be checked without JAX.
"""
from __future__ import annotations

import os

__all__ = ["cache_dir", "enable", "entry_count"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir() -> str:
    """The directory `enable()` will use."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()` and
    cache every executable that took compiling.  Touches `jax.config`
    only — no backend is initialised."""
    import jax
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


def entry_count(path: str) -> int:
    """Executables stored under `path` (0 when it does not exist)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
