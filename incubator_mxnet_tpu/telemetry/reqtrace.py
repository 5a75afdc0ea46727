"""Per-request lifecycle journal with tail-based exemplar retention
(ISSUE 19 tentpole).

Every aggregate surface so far — percentile rings, burn rates, cost
tables — answers "how slow", never "WHICH request and WHERE did its
time go".  When ``serve-p99-high`` fires, the on-call needs the
autopsy, not the gauge.  This module keeps it:

- **A compact record per request.**  Engines allocate one pre-sized
  `Record` (``__slots__`` struct) at submit and fill its phase stamps
  from timestamps they ALREADY compute — no extra clock reads on the
  hot path beyond the stamps the engine takes anyway.  The serve
  ladder is queue-wait → coalesce → dispatch → device-infer →
  join/D2H → future-resolution; generation maps queue → prefill →
  first token → decode → resolution onto the same slots (the first
  three are the phase log's `gen.req.queue` / `.admit` / `.first`
  rows, from the same stamps).  Sheds and deadline kills
  record their termination reason and which phase ate the budget (the
  first phase whose end stamp never landed).
- **A bounded per-engine ring** (`MXNET_REQTRACE_RING`) of retired
  records — the recent-request journal `Journal.snapshot()` /
  teletop render.
- **Tail-based exemplar promotion**, decided OFF the hot path at
  retire time: a request whose e2e lands above its lane's rolling p99
  (window `MXNET_REQTRACE_WINDOW`; pin the threshold with
  `MXNET_REQTRACE_PIN_P99_US` for deterministic tests), and every
  terminal failure (shed / deadline / error), is promoted to an
  **exemplar**: the full phase waterfall goes to the flight-recorder
  ring (stamped at ADMISSION time — the same end-vs-delivery
  discipline as `spans.emit_foreign`), to a durable ``reqtrace``
  history row, and into the bounded process-wide exemplar set that
  SLO alerts attach the worst match from (`worst_exemplar`).

Surfaces: `block()` feeds `dump_blackbox()` / ``/metrics.json`` /
teletop; ``python -m incubator_mxnet_tpu.tools.blackbox autopsy``
renders the waterfall + phase-dominance verdict; `telemetry/slo.py`
attaches the worst matching exemplar to every firing serving /
generation rule.

Overhead contract: `tools/check_overhead.py` holds the serving loop
with journaling on vs off to <2% — records are pre-sized structs, the
submit path pays one allocation + plain attribute writes, and ALL
classification (phase math, p99 compare, promotion) happens at retire
time.  ``MXNET_REQTRACE=0`` (or `enable(False)`) makes `start()`
return None and every stamp a no-op.
"""
from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque

from .. import config as _cfg
from ..monitor import events
from . import flightrec as _bb
from . import spans as _sp

__all__ = ["Record", "Journal", "journal", "enabled", "enable",
           "exemplars", "worst_exemplar", "block", "reset", "PHASES"]

#: per-engine-kind phase ladders: (phase, end-stamp slot) pairs walked
#: in order from ``t_enq``.  A record terminated before a stamp landed
#: charges the remaining wall to that phase — "which phase ate the
#: budget" for sheds and deadline kills.
PHASES = {
    "serve": (("queue", "t_collect"), ("coalesce", "t_exec"),
              ("dispatch", "t_infer0"), ("infer", "t_infer1"),
              ("join", "t_fin"), ("resolve", "t_done")),
    "gen": (("queue", "t_collect"), ("prefill", "t_exec"),
            ("first", "t_first"), ("decode", "t_fin"),
            ("resolve", "t_done")),
}

#: rolling-p99 promotion needs this many completed requests in the
#: lane window first — without the floor, the first request after
#: start would always out-tail an empty window
MIN_WINDOW = 20

#: retire-time p99 cache: re-sort the lane window only every N
#: retires (the tail moves slowly; an exact per-retire sort would be
#: the kind of hidden O(n log n) the overhead gate exists to catch)
_P99_EVERY = 32

# None = follow the MXNET_REQTRACE knob; enable() installs an explicit
# process-local override (the flightrec/spans pattern — what the
# overhead gate's on/off trial flips)
_enabled = None


def enabled() -> bool:
    """Whether the request journal is armed for this process."""
    if _enabled is not None:
        return _enabled
    return bool(_cfg.get("MXNET_REQTRACE"))


def enable(flag=True):
    """Flip journaling on/off (None = revert to the MXNET_REQTRACE
    knob); returns the previous effective state."""
    global _enabled
    prev = enabled()
    _enabled = None if flag is None else bool(flag)
    return prev


_rids = itertools.count(1)      # CPython-atomic next(); no lock


def next_rid():
    """A fresh request id from the journal's own counter: what an engine
    names a request by in the phase log while the journal is off."""
    return next(_rids)


class Record:
    """One request's lifecycle struct — pre-sized slots, filled by
    plain attribute writes from stamps the engine already takes.
    Monotonic seconds throughout; phase math happens once, at retire
    or render time, never on the submit path."""

    __slots__ = ("rid", "lane", "tenant", "bucket", "n",
                 "t_enq", "t_collect", "t_exec", "t_first", "t_infer0",
                 "t_infer1", "t_fin", "t_done",
                 "status", "reason", "e2e_us")

    def __init__(self, t_enq, lane, tenant):
        self.rid = next_rid()
        self.lane = lane
        self.tenant = tenant
        self.bucket = None
        self.n = 1
        self.t_enq = t_enq
        self.t_collect = None
        self.t_exec = None
        self.t_first = None
        self.t_infer0 = None
        self.t_infer1 = None
        self.t_fin = None
        self.t_done = None
        self.status = None
        self.reason = None
        self.e2e_us = None


def _status_of(exc):
    """(status, reason) from the engine's terminal exception — typed
    errors map onto stable status strings the autopsy families key
    on."""
    if exc is None:
        return "ok", None
    name = type(exc).__name__
    msg = str(exc)
    if len(msg) > 120:
        msg = msg[:117] + "..."
    if name == "Shed":
        return "shed", msg
    if name == "DeadlineExceeded":
        return "deadline", msg
    if name == "QueueFull":
        return "queue_full", msg
    if name == "EngineClosed":
        return "closed", msg
    return "error", "%s: %s" % (name, msg)


def _phases(rec, kind):
    """(phase µs dict, budget phase) for one retired record: an exact
    partition of [t_enq, t_done] along the kind's ladder.  A missing
    stamp means the request terminated INSIDE that phase — it is
    charged the remaining wall and named the budget phase; a complete
    record's budget phase is its dominant one."""
    ladder = PHASES.get(kind, PHASES["serve"])
    phases, cur, budget = {}, rec.t_enq, None
    for name, attr in ladder:
        t = getattr(rec, attr)
        if t is None:
            phases[name] = max(0.0, (rec.t_done - cur) * 1e6)
            budget = name
            break
        phases[name] = max(0.0, (t - cur) * 1e6)
        cur = t
    else:
        budget = max(phases, key=phases.get) if phases else None
    return phases, budget


def record_summary(rec, kind):
    """A retired record as a plain dict (ring snapshots / teletop)."""
    phases, budget = _phases(rec, kind)
    return {"rid": rec.rid, "lane": rec.lane or "-",
            "tenant": rec.tenant, "bucket": rec.bucket, "n": rec.n,
            "status": rec.status, "reason": rec.reason,
            "e2e_us": round(rec.e2e_us or 0.0, 1),
            "phases": {k: round(v, 1) for k, v in phases.items()},
            "dominant": max(phases, key=phases.get) if phases
            else None,
            "budget_phase": budget}


class Journal:
    """One engine's bounded request journal + per-lane tail tracker.

    Engines call `start()` at submit (None when disabled — every
    later stamp guards on the record), fill stamps as the request
    crosses phases, and `retire()` exactly once at resolution.
    Everything that costs more than an attribute write — phase math,
    the p99 compare, exemplar promotion — happens inside `retire()`,
    off the submit path."""

    def __init__(self, kind, model, version=None, ring=None,
                 window=None, keep=None):
        self.kind = str(kind)
        self.model = str(model)
        self.version = version
        self._ring = deque(maxlen=int(
            ring if ring is not None
            else _cfg.get("MXNET_REQTRACE_RING")))
        self._window = int(window if window is not None
                           else _cfg.get("MXNET_REQTRACE_WINDOW"))
        self._ex = deque(maxlen=int(
            keep if keep is not None
            else _cfg.get("MXNET_REQTRACE_EXEMPLARS")))
        self._lane_e2e = {}         # lane -> deque of completed e2e µs
        self._lane_p99 = {}         # lane -> [cached p99, age]
        self._lock = threading.Lock()
        self.records = 0
        self.promoted = 0

    # -- hot path ------------------------------------------------------
    def start(self, t_enq, lane, tenant=None):
        """A fresh record for an admitted request (None when the
        journal is disabled — stamps and retire() no-op on None)."""
        if not enabled():
            return None
        return Record(t_enq, lane, tenant)

    # -- retire path (off the submit path) -----------------------------
    def retire(self, rec, exc=None, status=None, reason=None,
               t_done=None):
        """Classify one finished record: status from the terminal
        exception (or explicit ``status=``), e2e, ring append, lane
        tail update, and the promotion decision.  Idempotence is the
        CALLER's contract (engines null the request's rec reference
        before calling)."""
        if rec is None:
            return None
        rec.t_done = float(t_done) if t_done is not None \
            else time.monotonic()
        if status is not None:
            rec.status, rec.reason = str(status), reason
        else:
            rec.status, rec.reason = _status_of(exc)
        rec.e2e_us = (rec.t_done - rec.t_enq) * 1e6
        lane = rec.lane or "-"
        promote = rec.status != "ok"
        with self._lock:
            self._ring.append(rec)
            self.records += 1
            if rec.status == "ok":
                dq = self._lane_e2e.get(lane)
                if dq is None:
                    dq = self._lane_e2e[lane] = \
                        deque(maxlen=self._window)
                dq.append(rec.e2e_us)
                promote = rec.e2e_us > self._p99_locked(lane, dq)
        events.incr("reqtrace.records")
        if promote:
            self._promote(rec)
        return rec

    def _p99_locked(self, lane, dq):
        """The lane's promotion threshold: the pinned value when
        `MXNET_REQTRACE_PIN_P99_US` > 0 (deterministic tests), else
        the rolling window's p99, re-sorted every `_P99_EVERY`
        retires.  Infinite until the window has MIN_WINDOW samples."""
        pin = float(_cfg.get("MXNET_REQTRACE_PIN_P99_US") or 0.0)
        if pin > 0.0:
            return pin
        if len(dq) < MIN_WINDOW:
            return float("inf")
        cached = self._lane_p99.get(lane)
        if cached is not None and cached[1] < _P99_EVERY:
            cached[1] += 1
            return cached[0]
        xs = sorted(dq)
        p = xs[min(len(xs) - 1, int(0.99 * len(xs)))]
        self._lane_p99[lane] = [p, 0]
        return p

    def _promote(self, rec):
        """Exemplar promotion: full waterfall into the flight-recorder
        ring (admission-stamped), a durable history row, and the
        bounded exemplar sets alerts/dumps read."""
        phases, budget = _phases(rec, self.kind)
        dominant = max(phases, key=phases.get) if phases else None
        wall0 = _sp.wall_of(rec.t_enq)
        ex = {"rid": rec.rid, "engine": self.kind, "model": self.model,
              "lane": rec.lane or "-", "tenant": rec.tenant,
              "bucket": rec.bucket, "n": rec.n,
              "status": rec.status, "reason": rec.reason,
              "e2e_us": round(rec.e2e_us, 1),
              "phases": {k: round(v, 1) for k, v in phases.items()},
              "dominant": dominant, "budget_phase": budget,
              "ts": wall0}
        if self.version is not None:
            ex["version"] = str(self.version)
        with self._lock:
            self._ex.append(ex)
            self.promoted += 1
        with _GLOCK:
            _EXEMPLARS.append(ex)
        events.incr("reqtrace.exemplars")
        events.incr("reqtrace.exemplars", labels={"lane": ex["lane"]})
        # ring event stamped at ADMISSION (the emit_foreign end-stamp
        # discipline, satellite 3): the dump timeline shows the
        # exemplar where its wait BEGAN, so queue growth and the
        # victim line up instead of the exemplar appearing after the
        # backlog already drained
        _bb.record_at(wall0, "reqtrace", "exemplar", rid=rec.rid,
                      engine=self.kind, model=self.model,
                      lane=ex["lane"], status=rec.status,
                      e2e_us=int(rec.e2e_us), dominant=str(dominant),
                      **{"%s_us" % k: int(v)
                         for k, v in phases.items()})
        try:
            from . import history as _hist
            _hist.record("reqtrace", "exemplar", rec.e2e_us,
                         labels={"engine": self.kind,
                                 "lane": ex["lane"],
                                 "model": self.model},
                         rid=rec.rid, status=rec.status,
                         reason=rec.reason, dominant=dominant,
                         phases=ex["phases"])
        except Exception:           # noqa: BLE001 — durability is
            pass                    # best-effort, never the request

    # -- introspection -------------------------------------------------
    def exemplars(self):
        with self._lock:
            return [dict(e) for e in self._ex]

    def snapshot(self):
        """The journal's block for dumps / /metrics.json / teletop:
        counts, per-lane window p99 + slowest recent request (with its
        waterfall), and the retained exemplars."""
        with self._lock:
            recs = list(self._ring)
            windows = {ln: (len(dq), list(dq))
                       for ln, dq in self._lane_e2e.items()}
            exs = [dict(e) for e in self._ex]
        slow = {}
        for rec in recs:
            ln = rec.lane or "-"
            cur = slow.get(ln)
            if rec.e2e_us is not None and \
                    (cur is None or rec.e2e_us > cur.e2e_us):
                slow[ln] = rec
        lanes = {}
        for ln in set(windows) | set(slow):
            n, vals = windows.get(ln, (0, []))
            entry = {"window_n": n}
            if vals:
                xs = sorted(vals)
                entry["p99_us"] = round(
                    xs[min(len(xs) - 1, int(0.99 * len(xs)))], 1)
            if ln in slow:
                entry["slowest"] = record_summary(slow[ln], self.kind)
            lanes[ln] = entry
        out = {"engine": self.kind, "model": self.model,
               "records": self.records, "promoted": self.promoted,
               "ring": len(recs), "lanes": lanes, "exemplars": exs}
        if self.version is not None:
            out["version"] = str(self.version)
        return out


# -- process-wide registry (dumps, alerts, teletop) --------------------
_GLOCK = threading.Lock()
_JOURNALS = []                  # weakrefs — journals die with engines
_EXEMPLARS = deque(maxlen=64)   # newest promotions across all engines


def journal(kind, model, version=None, **kw) -> Journal:
    """Create + register one engine's journal.  Held by WEAKREF here:
    a journal lives exactly as long as its engine, and a torn-down
    engine's journal must not pin its ring in every later dump."""
    j = Journal(kind, model, version=version, **kw)
    with _GLOCK:
        _JOURNALS[:] = [r for r in _JOURNALS if r() is not None]
        _JOURNALS.append(weakref.ref(j))
    return j


def _live_journals():
    with _GLOCK:
        refs = list(_JOURNALS)
    return [j for j in (r() for r in refs) if j is not None]


def exemplars(lane=None, engine=None, model=None):
    """Recent promoted exemplars across every engine, oldest first,
    optionally filtered by lane / engine kind / model."""
    with _GLOCK:
        out = list(_EXEMPLARS)
    if lane is not None:
        out = [e for e in out if e.get("lane") == str(lane)]
    if engine is not None:
        out = [e for e in out if e.get("engine") == str(engine)]
    if model is not None:
        out = [e for e in out if e.get("model") == str(model)]
    return out


def worst_exemplar(lane=None, engine=None, model=None):
    """The retained exemplar with the largest e2e matching the
    filters (None when nothing matches) — what a firing SLO rule
    attaches as its autopsy."""
    best = None
    for ex in exemplars(lane=lane, engine=engine, model=model):
        if best is None or ex.get("e2e_us", 0) > best.get("e2e_us", 0):
            best = ex
    return best


def block() -> dict:
    """The ``reqtrace`` block for dumps / /metrics.json / teletop:
    every live journal's snapshot + the newest cross-engine
    exemplars.  Empty dict when nothing was journaled."""
    js = [j.snapshot() for j in _live_journals()]
    js = [s for s in js if s["records"]]
    with _GLOCK:
        exs = list(_EXEMPLARS)
    if not js and not exs:
        return {}
    return {"journals": js, "exemplars": exs[-16:]}


def reset():
    """Tests: drop every registered journal and retained exemplar."""
    global _enabled
    with _GLOCK:
        del _JOURNALS[:]
        _EXEMPLARS.clear()
    _enabled = None
