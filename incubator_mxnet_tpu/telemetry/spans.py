"""Spans: one always-on phase log, and the id-minting spans on top of it.

**The phase log (PR 27).**  `phase(name, ident, parent, n)` is a context
manager that is always on.  Entering it enters a
`jax.profiler.TraceAnnotation(name)` (resolved on first use, skipped
while `jax` is not imported; free while no profiler session collects)
and reads `time.monotonic()`; leaving it appends one tuple

    (name, t0, t1, ident, parent, n)

to a process-wide ring of the newest 262144 rows.  No lock, no id minted,
no string built, no dict: whatever the caller passed is stored as it is.
`phase_at(...)` appends an interval whose stamps the caller already
holds (a request's phases cross threads); it is not mirrored to the
profiler.  `phase_log(since, until, prefix)` reads rows by start time,
`phase_totals(prefix)` gives `{name: (count, seconds, n)}`.

One clock: `time.monotonic()`, which is also the clock the benchmark
opens and closes its window on, so a reader filters rows to a window
with no conversion.  The `TraceAnnotation` puts the same interval on
the profiler's clock, in the `/host:CPU` plane of a trace, beside the
device's ops: a reduction that names an idle gap of the device by the
host event over it names it by phase.  The names, who writes them and
which metric reads them are listed in docs/observability.md.

**Spans** (ISSUE 4; `MXNET_TELEMETRY=1` / `telemetry.enable()`) are
the heavier kind: they mint a trace id and a span id, propagate
parents across threads and processes, and land in the chrome-trace
sink profiler.py dumps and in the flight-recorder ring.  A span is
also a phase: it enters the same `TraceAnnotation` and leaves the same
row (ident = span id, parent = parent span id), so every span site is
on the profiler's clock too.

    with telemetry.span("serve.dispatch"):
        ...

Spans carry a trace id (one per causal chain) and a span id, with
EXPLICIT cross-thread parent propagation — thread-locals cannot follow
a request from the submitting thread onto the dispatcher:

    ctx = telemetry.current()           # producer thread
    ...
    with telemetry.span("feed.transfer", parent=ctx):   # worker thread
        ...

Completed spans are appended to the SAME chrome-trace sink profiler.py
dumps (`profiler.add_trace_event`), so `profiler.dump()` renders feed
transfers, dispatch→infer chains and checkpoint writes on one timeline
with the op events; trace/span/parent ids ride in each event's `args`.

Cost model (revised in ISSUE 5): span OBJECTS exist whenever telemetry
is enabled (`telemetry.enable()` / `MXNET_TELEMETRY=1`); with
telemetry off, `span()` returns a shared no-op — one bool read, no
allocation.  A completed span lands in the phase log and in TWO sinks
with independent gates:

- the profiler's chrome-trace sink, ONLY while the profiler is
  collecting (`set_state("run")`, not paused — the sink is unbounded,
  `recording()` reports this gate);
- the flight-recorder ring (flightrec.py), whenever the recorder is
  armed — the ring is bounded, so span completions survive into
  black-box dumps even on runs nobody is tracing.

**Cross-process propagation (ISSUE 11).**  A fleet is many processes:
decode workers, serving hosts, per-replica trainers.  Three additions
make one request/step traceable across all of them:

- `TraceContext` — the SERIALIZABLE form of a span context
  (trace_id, parent span_id, global step): `propagate()` captures the
  innermost open span + current global step as a plain tuple that
  crosses any wire (a queue message, a kvstore key, an env var);
  `TraceContext.from_wire()` rebuilds it on the far side, ready to be
  passed as `parent=`.
- `set_global_step(step)` — every span completed while a global step
  is set carries `step` in its args/ring record, so traces from
  DIFFERENT processes (trainer rank 0, a decode worker, a serving
  host) correlate on the same step id even when their trace ids never
  meet.  Trainers stamp it each step.
- `emit_foreign(...)` — record a completed span ON BEHALF of another
  process (a jax-free decode worker reports wall-clock timing in its
  batch message; the consumer emits the span with the WORKER's pid,
  re-parented under the consumer's current span).  The chrome view
  then renders the worker's decode interval in its own process row of
  the same timeline.

Spans also take free-form tags: ``span("kv.push", gen=3, rank=0)`` —
tags land in the chrome event args and the ring record.
"""
from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time

from .. import config as _cfg
from .. import profiler as _prof
from . import flightrec as _bb

__all__ = ["SpanContext", "TraceContext", "enabled", "enable", "span",
           "current", "recording", "propagate", "set_global_step",
           "get_global_step", "emit_foreign", "wall_of", "phase",
           "phase_at", "phase_log", "phase_totals"]

# -- the phase log ------------------------------------------------------
# The newest rows (name, t0, t1, ident, parent, n), stamps from
# time.monotonic().  deque.append is atomic and drops the oldest row
# itself: writers take no lock.  The length is a constant, not a knob:
# room for a whole benchmark run of an engine that ticks every 1.3 ms (a
# tick writes five rows, a request five more; at 65536 rows a run of
# `nmt_base.online_steady` with 4.5 ms ticks lost its set-up's rows
# before the readers came for them, PERF.md PR 34).
_LOG = collections.deque(maxlen=1 << 18)
_now = time.monotonic
_ANNOTATION = None      # jax.profiler.TraceAnnotation, once jax is there


def _annotation():
    """`jax.profiler.TraceAnnotation`, or None while `jax` is not
    imported: the log must not be what imports it."""
    global _ANNOTATION
    jax = sys.modules.get("jax")
    if jax is not None and hasattr(jax, "profiler"):
        _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


class phase:
    """An always-on interval: `with phase("gen.tick", 7, n=3) as ph:`
    (or `.start()` / `.stop()`).  `ident` names the thing (a tick, a
    request, a step), `parent` is the ident of the phase that caused
    it, `n` counts what it handled and may be set until the exit.
    `t0`/`t1` (and `seconds` after the exit) are the stamps the row
    carries, for a caller that needs the duration too."""

    __slots__ = ("name", "ident", "parent", "n", "t0", "t1", "_ann")

    def __init__(self, name, ident=None, parent=None, n=0):
        self.name, self.ident, self.parent, self.n = name, ident, parent, n
        self.t0 = self.t1 = self._ann = None

    def start(self):
        cls = _ANNOTATION or _annotation()
        if cls is not None:
            self._ann = cls(self.name)
            self._ann.__enter__()
        self.t0 = _now()
        return self

    def stop(self):
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = _now()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _LOG.append((self.name, self.t0, self.t1, self.ident, self.parent,
                     self.n))

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()
        return False

    @property
    def seconds(self):
        return self.t1 - self.t0


def phase_at(name, t0, t1, ident=None, parent=None, n=0):
    """A row for an interval whose `time.monotonic()` stamps the caller
    already holds.  Not mirrored to the profiler."""
    _LOG.append((name, t0, t1, ident, parent, n))


def phase_log(since=None, until=None, prefix=None):
    """Rows (name, t0, t1, ident, parent, n) that START in
    [since, until] and whose name starts with `prefix`, oldest first."""
    while True:
        try:
            rows = list(_LOG)
            break
        except RuntimeError:        # a writer appended mid-copy
            continue
    return [r for r in rows
            if (since is None or r[1] >= since)
            and (until is None or r[1] <= until)
            and (prefix is None or r[0].startswith(prefix))]


def phase_totals(prefix=None):
    """{name: (count, seconds, n)} over the rows the ring still holds."""
    out = {}
    for name, t0, t1, _, _, n in phase_log(prefix=prefix):
        c, s, k = out.get(name, (0, 0.0, 0))
        out[name] = (c + 1, s + (t1 - t0), k + n)
    return out


def wall_of(t_mono):
    """The `time.time()` epoch stamp corresponding to a
    `time.monotonic()` reading taken earlier in THIS process.

    Interval stamps on the hot path are monotonic (immune to clock
    steps), but the flight-recorder ring and `record_at` speak epoch
    time.  Both clocks advance at wall rate, so the reading was
    (monotonic-now − t_mono) seconds ago.  This is the conversion the
    admission-time stamping discipline rides on (ISSUE 19 satellite —
    same family as `emit_foreign`'s end-stamping): convert the
    ORIGINAL stamp at emit time rather than stamping delivery time."""
    return time.time() - (time.monotonic() - float(t_mono))

_ids = itertools.count(1)       # CPython-atomic next(); no lock needed
_tls = threading.local()

# per-process id salt: trace/span ids must be unique ACROSS processes
# (ISSUE 11 — `blackbox merge` joins timelines on trace_id equality,
# and a bare counter starting at 1 would collide between any two
# processes, fabricating cross-process correlations).  pid + a time
# component survives pid recycling within one merge's inputs.
_PROC = "%08x" % ((os.getpid() << 12 ^ time.time_ns()) & 0xffffffff)


def _new_id(prefix):
    return "%s%s-%06x" % (prefix, _PROC, next(_ids))

# None = follow the MXNET_TELEMETRY knob live (config.set / env work
# like every other registered knob); enable() installs an explicit
# process-local override
_enabled = None


def enabled() -> bool:
    """Whether telemetry instrumentation (spans + per-step training
    counters) is switched on for this process."""
    if _enabled is not None:
        return _enabled
    return bool(_cfg.get("MXNET_TELEMETRY"))


def enable(flag=True):
    """Flip telemetry instrumentation on/off (None = revert to the
    MXNET_TELEMETRY knob); returns the previous effective state (so
    tests can restore it)."""
    global _enabled
    prev = enabled()
    _enabled = None if flag is None else bool(flag)
    return prev


def recording() -> bool:
    """Whether a span completed now would reach the CHROME-TRACE sink:
    telemetry enabled AND the profiler collecting.  (Ring recording
    into the flight recorder needs only `enabled()` — see the module
    docstring.)"""
    return (enabled() and _prof._STATE["running"]
            and not _prof._STATE["paused"])


class SpanContext:
    """Immutable (trace_id, span_id) handle for cross-thread parenting.
    Hand it to a worker thread and open child spans with
    ``span(name, parent=ctx)``."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):
        return "SpanContext(trace=%s, span=%s)" % (self.trace_id,
                                                   self.span_id)


class TraceContext(SpanContext):
    """The SERIALIZABLE span context for crossing a process boundary:
    (trace_id, parent span_id, global step).  `to_wire()` is a plain
    tuple of primitives — safe in a multiprocessing queue message,
    a kvstore payload, or JSON; `from_wire()` rebuilds it on the far
    side, and the result is a valid `parent=` for `span()` /
    `emit_foreign()` (it IS a SpanContext).  `step` rides along so the
    receiver can adopt the sender's global step (`set_global_step`)
    and its spans correlate on the same step id."""

    __slots__ = ("step",)

    def __init__(self, trace_id: str, span_id: str, step=None):
        super().__init__(trace_id, span_id)
        self.step = None if step is None else int(step)

    def to_wire(self):
        """(trace_id, span_id, step) — primitives only."""
        return (self.trace_id, self.span_id, self.step)

    @classmethod
    def from_wire(cls, wire):
        """Rebuild from `to_wire()` output (or any 2/3-tuple of
        primitives).  None in, None out."""
        if wire is None:
            return None
        t = tuple(wire)
        return cls(str(t[0]), str(t[1]),
                   t[2] if len(t) > 2 else None)

    def __repr__(self):
        return "TraceContext(trace=%s, span=%s, step=%s)" % (
            self.trace_id, self.span_id, self.step)


def propagate():
    """The current position in the trace as a serializable
    `TraceContext` (innermost open span on this thread + the global
    step), for handing to ANOTHER PROCESS.  None when telemetry is
    disabled or no span is open AND no global step is set — a bare
    step still propagates (trace ids are minted lazily on the far
    side)."""
    ctx = current()
    step = get_global_step()
    if ctx is None and step is None:
        return None
    if ctx is None:
        # no open span: mint a trace so the far side still correlates
        return TraceContext(_new_id("t"),
                            _new_id("s"), step)
    return TraceContext(ctx.trace_id, ctx.span_id, step)


# global step id (process-wide): trainers stamp it every step; every
# span completed while it is set carries `step` in its args/ring
# record, which is what lets traces from DIFFERENT processes correlate
# on one step even when their trace ids never meet.  A plain attribute
# write/read — torn reads are impossible for a python int slot, so no
# lock on the hot path.
_GSTEP = {"step": None}


def set_global_step(step):
    """Stamp the process's current global step id onto every span
    completed from now on (None clears it).  Returns the previous
    value so scoped users can restore.

    Lifecycle contract: trainers stamp it each step and
    `ShardedTrainer.release()` clears it — a stamp that outlives its
    run would mark unrelated later spans (serving, checkpoint
    verifies) with a dead step id and fabricate cross-process
    correlations in `blackbox merge`.  Ad-hoc users (bench proofs,
    tests) clear it themselves."""
    prev = _GSTEP["step"]
    _GSTEP["step"] = None if step is None else int(step)
    return prev


def get_global_step():
    """The current global step id (None when unset)."""
    return _GSTEP["step"]


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current():
    """The innermost open span's context on THIS thread (None outside
    any span, or when telemetry is disabled).  Capture it before
    handing work to another thread — that thread's spans pass it as
    `parent=` to join the same trace."""
    if not enabled():
        return None
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


class _NullSpan:
    """Shared no-op for the disabled path — `with` works, nothing is
    recorded, nothing is allocated per call."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def start(self):
        return self

    def stop(self):
        pass


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "ctx", "parent_id", "tags", "_t0", "_phase")

    def __init__(self, name, parent, tags=None):
        if parent is None:
            parent = current()
        if parent is not None:
            trace = parent.trace_id
            self.parent_id = parent.span_id
        else:
            trace = _new_id("t")
            self.parent_id = None
        self.ctx = SpanContext(trace, _new_id("s"))
        self.name = name
        self.tags = tags
        self._t0 = None
        # the same interval in the phase log and on the profiler's clock
        self._phase = phase(name, self.ctx.span_id, self.parent_id)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def start(self):
        self._phase.start()
        self._t0 = time.perf_counter()
        _stack().append(self.ctx)
        return self

    def stop(self):
        if self._t0 is None:
            return
        t0, self._t0 = self._t0, None
        st = _stack()
        if st and st[-1] is self.ctx:
            st.pop()
        elif self.ctx in st:        # mispaired stop(): drop ours only
            st.remove(self.ctx)
        dur = time.perf_counter() - t0
        self._phase.stop()
        args = {"trace_id": self.ctx.trace_id,
                "span_id": self.ctx.span_id}
        if self.parent_id is not None:
            args["parent_id"] = self.parent_id
        step = _GSTEP["step"]
        if step is not None:
            args["step"] = step
        if self.tags:
            args.update(self.tags)
        # chrome sink: add_trace_event self-gates on the profiler state
        # (a span that STARTED while collecting must not grow the sink
        # after set_state('stop'))
        _prof.add_trace_event(self.name, "span", t0, dur, args=args)
        # flight-recorder ring: bounded, so span completions survive
        # into black-box dumps with NO profiler running (ISSUE 5) —
        # record() is one bool read when the recorder is disarmed
        extra = dict(self.tags) if self.tags else {}
        if step is not None:
            extra["step"] = step
        _bb.record("span", self.name, dur_us=int(dur * 1e6),
                   trace=self.ctx.trace_id, span=self.ctx.span_id,
                   parent=self.parent_id, **extra)


def span(name: str, parent: SpanContext = None, **tags):
    """Open a span (use as a context manager, or `.start()`/`.stop()`).
    `parent` joins an existing trace across threads/processes (a
    `SpanContext` or a deserialized `TraceContext`); by default the
    innermost open span on this thread is the parent.  Free-form
    `tags` (e.g. ``gen=3, rank=0``) ride in the completion's args and
    ring record.  Returns a shared no-op when telemetry is disabled;
    enabled, the completion reaches the chrome sink and/or the
    flight-recorder ring per their own gates (see module docstring)."""
    if not enabled():
        return _NULL
    return _Span(name, parent, tags or None)


def emit_foreign(name, t0_wall, dur_s, parent=None, pid=None, tid=None,
                 **tags):
    """Record a COMPLETED span on behalf of another process.

    The fleet's jax-free workers (decode processes) cannot import the
    telemetry stack; they report wall-clock timing in their messages
    and the consumer calls this on delivery — the span lands in the
    chrome sink / flight-recorder ring with the WORKER's `pid` (its
    own process row in the merged timeline), re-parented under
    `parent` (default: the consumer's innermost open span), and
    stamped with the current global step.

    `t0_wall` is a `time.time()` epoch stamp from the foreign process
    (epoch time IS comparable across processes on one host, unlike
    `perf_counter`); `dur_s` seconds.  Returns the new span's
    `SpanContext` (None when telemetry is disabled)."""
    if not enabled():
        return None
    if parent is None:
        parent = current()
    if parent is not None:
        trace = parent.trace_id
        parent_id = parent.span_id
    else:
        trace = _new_id("t")
        parent_id = None
    ctx = SpanContext(trace, _new_id("s"))
    args = {"trace_id": trace, "span_id": ctx.span_id}
    if parent_id is not None:
        args["parent_id"] = parent_id
    step = _GSTEP["step"]
    if step is not None:
        args["step"] = step
    if tags:
        args.update(tags)
    # map the foreign epoch stamp onto this process's perf_counter
    # origin (the chrome sink's timebase): both clocks advance at
    # wall rate, so the offset is (now_wall - t0_wall) ago
    t0_perf = time.perf_counter() - max(0.0, time.time() - t0_wall)
    _prof.add_trace_event(name, "span", t0_perf, dur_s, args=args,
                          pid=pid, tid=tid)
    extra = dict(tags) if tags else {}
    if step is not None:
        extra["step"] = step
    if pid is not None:
        extra["pid"] = int(pid)
    # stamp the ring event at the interval's true END (the foreign
    # process's clock), not at delivery — a prefetched batch's decode
    # slice must not shift right by its queue wait in the dump view
    _bb.record_at(t0_wall + dur_s, "span", name,
                  dur_us=int(dur_s * 1e6), trace=trace,
                  span=ctx.span_id, parent=parent_id, **extra)
    return ctx
