"""Where JAX's persistent compilation cache lives.

One rule, for every entry point that compiles (chip_smoke.py, bench.py):
the cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise
to ``<checkout>/.jax_cache`` — a fixed, gitignored path, because the
directory is part of what a later process must find again.  It is the
package's only cache of compiled code: no other directory is set
anywhere, and nothing here runs at package import — an entry point
calls `enable()` before its first compile.

`enable()` also puts compile time into the phase log
(`telemetry/spans.py`): one `compile.jax.<event>` row for each trace,
lowering, backend compile and cache retrieval of a millisecond or more
that JAX reports through `jax.monitoring`, the > 100 small eager programs that no wrapper of
this package sees among them.  A retrieval lies inside its backend
compile, and a nested `jit` is traced inside its caller's trace: take
the union of the rows, not their sum.

This module imports nothing from the package at load time, so the path
rule can be checked without JAX.
"""
from __future__ import annotations

import os
import time

__all__ = ["cache_dir", "enable", "entry_count"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_listening = False      # enable() may be called again: one listener


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
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return path


def _on_duration(event, duration_secs, fun_name=None, **_):
    """A JAX compile event that just ended, as a row of the phase log
    (ident = the function's name, where JAX gives it).
    `compile_time_saved_sec` is an estimate, not an interval.  Events
    under a millisecond are left out: the nested trace of every `jnp`
    call inside a larger trace, nine rows in ten and 0.2 % of the time."""
    kind = event.rsplit("/", 1)[-1]
    if duration_secs >= 1e-3 and "/compil" in event \
            and kind != "compile_time_saved_sec":
        from .telemetry import spans
        t1 = time.monotonic()
        spans.phase_at("compile.jax." + kind, t1 - duration_secs, t1,
                       fun_name)


def entry_count(path: str) -> int:
    """Executables stored under `path` (0 when it does not exist)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
