"""Dispatch layer ("engine").

TPU-native stand-in for the reference dependency engine
(ref: include/mxnet/engine.h, src/engine/threaded_engine*.cc).

There is deliberately NO thread-pool scheduler here: XLA/PJRT dispatch is
already asynchronous and per-buffer ordered, which is exactly what the
ThreadedEngine's var-queue machinery provided (SURVEY §7.0).  What remains
at framework level:

- `MXNET_ENGINE_TYPE=NaiveEngine` — synchronous debug mode: every op
  blocks until ready (the reference's engine-bisection tool, SURVEY §5.2).
- dispatch hooks — profiler instrumentation wraps every imperative op
  (ref: ThreadedEngine::ExecuteOprBlock profiling).
- `wait_all()` ≙ Engine::WaitForAll.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, List

__all__ = ["naive_mode", "set_naive_mode", "wait_all", "add_dispatch_listener",
           "remove_dispatch_listener", "_dispatch_hook", "bulk",
           "set_bulk_size"]

from . import config as _cfg
_NAIVE = _cfg.get("MXNET_ENGINE_TYPE") == "NaiveEngine"

# Listeners: callables (name, ctx, elapsed_s) — used by the profiler.
_LISTENERS: List[Callable] = []


def naive_mode() -> bool:
    return _NAIVE


def set_naive_mode(flag: bool) -> bool:
    global _NAIVE
    prev = _NAIVE
    _NAIVE = bool(flag)
    return prev


def add_dispatch_listener(fn: Callable):
    _LISTENERS.append(fn)


def remove_dispatch_listener(fn: Callable):
    if fn in _LISTENERS:
        _LISTENERS.remove(fn)


import threading as _threading

# Trace-time op collector: while a cached-op pure function is being
# traced, every imperative dispatch records its op name here — the
# composition of the (later fully fused) executable.  Keyed per-thread:
# tracing can nest across threads in the DataLoader.
_TRACE_COLLECT = _threading.local()


@contextlib.contextmanager
def collect_op_names():
    """Collect (op name, est_seconds) entries dispatched inside this
    scope (used while tracing a hybridized block; the list is the fused
    program's op composition for the profiler's aggregate table).
    est_seconds is a roofline estimate (see `roofline_estimate`),
    computed only when the profiler is listening at trace time — start
    the profiler BEFORE the first forward for full attribution."""
    prev = getattr(_TRACE_COLLECT, "ops", None)
    _TRACE_COLLECT.ops = []
    try:
        yield _TRACE_COLLECT.ops
    finally:
        _TRACE_COLLECT.ops = prev


def has_listeners() -> bool:
    return bool(_LISTENERS)


# Roofline constants for per-op attribution INSIDE a fused executable
# (v5e datasheet-order: bf16 peak and HBM stream bandwidth).  Only the
# PROPORTIONS between ops matter for the aggregate table; absolute
# values are labelled estimates.  (ref: src/profiler/profiler.cc
# measures real per-op stamps; one XLA program has no such stamps —
# XPlane via profiler.start_jax_trace is the measured alternative.)
_PEAK_FLOPS = 1.97e14
_PEAK_BYTES = 8.19e11


def roofline_estimate(flops: float, bytes_accessed: float) -> float:
    """Estimated seconds an op contributes inside a fused program:
    max of its MXU time and its HBM-stream time."""
    return max(flops / _PEAK_FLOPS, bytes_accessed / _PEAK_BYTES)


def host_const(shape, dtype, fill=0.0, device=None):
    """Constant built on the HOST and device_put — the one idiom for
    creating zeros/ones/hyper vectors: an eager `jnp.zeros`-style
    creation op compiles one program per (shape, dtype) (the idiom was
    chosen on an earlier setup where each cost seconds; not
    re-measured on this chip).  Used by the
    backward seed constants, optimizer state/hyper builds, and (via
    numpy + NDArray) param init and attach_grad."""
    import numpy as _nph
    import jax
    a = (_nph.zeros(shape, dtype) if not fill
         else _nph.full(shape, fill, dtype=dtype))
    return jax.device_put(a, device)


def emit_fused_ops(step_name: str, ctx, op_entries):
    """Report the per-op composition of a fused executable that just
    dispatched as one event.  Entries are (name, est_seconds) pairs
    from `collect_op_names` (bare names accepted — zero duration);
    durations are ROOFLINE ESTIMATES from per-op HLO cost analysis at
    trace time, not measurements — the parent event carries the real
    total; XPlane (profiler.start_jax_trace) measures for real.
    Callers guard with `has_listeners()` so the hot path never builds
    the name lists for nobody."""
    for fn in _LISTENERS:
        for op in op_entries:
            if isinstance(op, tuple):
                fn("%s[fused]" % op[0], ctx, float(op[1]))
            else:
                fn("%s[fused]" % op, ctx, 0.0)


@contextlib.contextmanager
def _dispatch_hook(name: str, ctx, cost_fn=None):
    coll = getattr(_TRACE_COLLECT, "ops", None)
    if coll is not None:
        # cost estimation (an HLO lowering per op) only when someone is
        # listening — plain hybridize traces skip it entirely
        cost = 0.0
        if cost_fn is not None and _LISTENERS:
            try:
                cost = float(cost_fn())
            except Exception:
                cost = 0.0
        coll.append((name, cost))
    if not _LISTENERS:
        yield
        return
    t0 = time.perf_counter()
    yield
    dt = time.perf_counter() - t0
    for fn in _LISTENERS:
        fn(name, ctx, dt)


def wait_all():
    """Engine::WaitForAll — barrier on all outstanding device work.

    Blocking on an INDEPENDENT op says nothing about enqueued work, so
    this walks every live jax array and blocks on each.  Prefer
    blocking on a result you actually need for timing loops."""
    import jax
    from . import autograd as _ag
    _ag.flush_pending("all")    # deferred programs must dispatch first
    for arr in jax.live_arrays():
        try:
            arr.block_until_ready()
        except Exception:
            pass                # deleted/donated buffers mid-walk
    try:
        jax.effects_barrier()
    except Exception:
        pass


# Bulking knobs kept for API familiarity (ref: MXNET_EXEC_BULK_EXEC_*).
# XLA fusion inside jitted executables is the actual bulking mechanism;
# these are accepted and recorded but change nothing imperatively.
_BULK_SIZE = int(_cfg.get("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN"))


def set_bulk_size(size: int) -> int:
    global _BULK_SIZE
    prev, _BULK_SIZE = _BULK_SIZE, int(size)
    return prev


@contextlib.contextmanager
def bulk(size: int):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)
