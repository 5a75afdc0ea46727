"""Profiler (ref: src/profiler/profiler.{h,cc}, python/mxnet/profiler.py).

Same user surface: set_config / set_state('run'|'stop') / pause / resume /
dump / dumps(aggregate), custom scopes (Task/Frame/Marker).  Mechanism:
the engine dispatch hook records one event per imperative op (the analogue
of ThreadedEngine::ExecuteOprBlock's begin/end stamps); dump() writes
chrome://tracing JSON.  For inside-executable visibility use
`jax.profiler` (XPlane) — `start_jax_trace`/`stop_jax_trace` wrap it.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import defaultdict

from . import engine

__all__ = ["set_config", "set_state", "pause", "resume", "dump", "dumps",
           "Task", "Frame", "Marker", "scope", "start_jax_trace",
           "stop_jax_trace", "add_trace_event"]

_CONFIG = {"filename": "profile.json", "profile_all": False,
           "profile_imperative": True, "aggregate_stats": True}
# `registered` tracks whether OUR dispatch listener is installed — the
# run/stop transitions key on it so stop-before-run and double-stop are
# idempotent no-ops instead of unregistering a listener never added
_STATE = {"running": False, "paused": False, "registered": False}
_EVENTS = []
_LOCK = threading.Lock()
_T0 = time.perf_counter()


def _append_event(name, cat, t0_s, dur_s, args=None, ph="X", pid=None,
                  tid=None):
    """Build one chrome-trace event (shared ts/tid conventions) and
    append it to the sink unconditionally."""
    ev = {"name": name, "cat": cat, "ph": ph,
          "ts": (t0_s - _T0) * 1e6, "dur": dur_s * 1e6,
          "pid": os.getpid() if pid is None else int(pid),
          "tid": (threading.get_ident() % 100000) if tid is None
          else int(tid)}
    if args:
        ev["args"] = dict(args)
    with _LOCK:
        _EVENTS.append(ev)


def add_trace_event(name, cat, t0_s, dur_s, args=None, ph="X",
                    pid=None, tid=None):
    """Append one complete event to the shared chrome-trace sink.
    `t0_s` is a `time.perf_counter()` stamp (converted to this
    module's trace origin), `dur_s` seconds.  Telemetry spans use this
    so framework-thread intervals (feed transfers, serving dispatch,
    checkpoint writes) land on the SAME timeline `dump()` renders for
    the op-dispatch events.  `pid`/`tid` override the event's process/
    thread row — `telemetry.emit_foreign` files a decode worker's span
    under the WORKER's pid so the merged timeline shows it as its own
    process.  Dropped while the profiler is stopped — the sink is
    unbounded, and a span that merely STARTED while it was collecting
    (a long checkpoint straddling set_state('stop')) must not grow it
    afterwards."""
    if not _STATE["running"] or _STATE["paused"]:
        return
    _append_event(name, cat, t0_s, dur_s, args=args, ph=ph, pid=pid,
                  tid=tid)


def _listener(name, ctx, elapsed):
    if not _STATE["running"] or _STATE["paused"]:
        return
    now = time.perf_counter()
    _append_event(name, "operator", now - elapsed, elapsed,
                  args={"ctx": repr(ctx)})


def set_config(**kwargs):
    _CONFIG.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    """'run' installs the dispatch listener (once) and starts
    collecting; anything else stops.  Idempotent in both directions:
    stop-before-run and double-stop only unregister a listener that
    was actually added, run-while-running never double-registers."""
    if state == "run":
        if not _STATE["registered"]:
            engine.add_dispatch_listener(_listener)
            _STATE["registered"] = True
        _STATE["running"] = True
        _STATE["paused"] = False
    else:
        _STATE["running"] = False
        if _STATE["registered"]:
            engine.remove_dispatch_listener(_listener)
            _STATE["registered"] = False


def pause(profile_process="worker"):
    _STATE["paused"] = True


def resume(profile_process="worker"):
    _STATE["paused"] = False


def dump(finished=True, profile_process="worker"):
    engine.wait_all()
    with _LOCK:
        events = list(_EVENTS)
    with open(_CONFIG["filename"], "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return _CONFIG["filename"]


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate per-op stats table (ref: AggregateStats::DumpTable)."""
    with _LOCK:
        events = list(_EVENTS)
        if reset:
            _EVENTS.clear()
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])
    for e in events:
        rec = agg[e["name"]]
        rec[0] += 1
        rec[1] += e["dur"]
        rec[2] = min(rec[2], e["dur"])
        rec[3] = max(rec[3], e["dur"])
    rows = sorted(agg.items(),
                  key=lambda kv: kv[1][1] if sort_by == "total" else kv[1][0],
                  reverse=not ascending)
    lines = ["%-40s %8s %12s %12s %12s %12s" %
             ("Name", "Calls", "Total(us)", "Avg(us)", "Min(us)", "Max(us)")]
    for name, (n, total, mn, mx) in rows:
        lines.append("%-40s %8d %12.1f %12.1f %12.1f %12.1f"
                     % (name[:40], n, total, total / n, mn, mx))
    return "\n".join(lines)


class _Scope:
    def __init__(self, name, cat):
        self.name = name
        self.cat = cat
        self._t = None

    def start(self):
        self._t = time.perf_counter()

    def stop(self):
        if self._t is None:
            return
        _append_event(self.name, self.cat, self._t,
                      time.perf_counter() - self._t)
        self._t = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Scope):
    def __init__(self, name, domain=None):
        super().__init__(name, "task")


class Frame(_Scope):
    def __init__(self, name, domain=None):
        super().__init__(name, "frame")


class Marker:
    def __init__(self, name, domain=None):
        self.name = name

    def mark(self, scope="process"):
        with _LOCK:
            _EVENTS.append({
                "name": self.name, "cat": "marker", "ph": "i",
                "ts": (time.perf_counter() - _T0) * 1e6,
                "pid": os.getpid(), "s": "p",
                "tid": threading.get_ident() % 100000,
            })


scope = _Scope


def start_jax_trace(logdir="/tmp/jax-trace"):
    """XLA-level tracing (XPlane/TensorBoard) — inside-executable timeline
    the op-level chrome trace cannot see.  The phase log's intervals
    (telemetry/spans.py) are in it as host events.  The Python tracer is
    off, as in the benchmark's sessions: it slows the host it measures."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop_jax_trace():
    import jax
    jax.profiler.stop_trace()
