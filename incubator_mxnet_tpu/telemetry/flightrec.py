"""Flight recorder: always-on black-box forensics (ISSUE 5 tentpole
part 1).

PR 4's telemetry is pull-based: a dashboard someone is watching.  When
a run DIES — NaN rollback, preemption, a serving dispatcher backstop,
an uncaught exception on a feed thread — nothing durable survives to
explain it.  This module is the black box: a lock-guarded bounded ring
of structured events that every subsystem appends to unconditionally
(step records, span completions, counter-delta samples, checkpoint /
rollback / fault / preemption markers, feed stalls, serving
queue-depth samples, HBM watermarks), plus an atomic JSON dump that
turns the ring + the counter ledger + the executable cost table
(costs.py) + the config-knob snapshot into ONE self-contained forensic
file a dead run leaves behind.

Cost model — the recorder is ON BY DEFAULT, so it must be nearly free:
`record()` is one enabled-check, one tuple build and one deque append
under a lock; no string formatting, no serialization, nothing until
dump time.  `MXNET_BLACKBOX=0` reduces every hook to a single bool
read.

Dump triggers (all end in `dump_blackbox()`):

- NaN-rollback and preemption in `ResilientTrainer`
- a mesh shrink in `parallel/elastic.py` (a replica died: the dump
  names it and carries the health timeline that condemned it)
- the serving dispatcher's error backstop (`serving/engine.py`)
- uncaught exceptions: `sys.excepthook` + `threading.excepthook`
  (a raising feed/dispatcher worker leaves a dump, not silence)
- `SIGUSR2` — a live-run snapshot without stopping anything
- an explicit `telemetry.dump_blackbox()`

`install_crash_hooks()` is idempotent and chains the previous hooks;
`ResilientTrainer`, `InferenceEngine` and `telemetry.start()` install
them on construction.  `python -m incubator_mxnet_tpu.tools.blackbox
<dump>` summarizes a dump (timeline tail, counters, cost table, a
one-line suspected-cause heuristic).
"""
from __future__ import annotations

import itertools
import json
import os
import signal
import sys
import threading
import time
from collections import deque

from .. import config as _cfg
from ..monitor import events

__all__ = ["enabled", "enable", "record", "record_at", "record_mesh",
           "ring_snapshot",
           "clear", "configure", "hbm_sample", "hbm_peaks",
           "sample_counters", "dump_blackbox", "crash_dump",
           "install_crash_hooks", "uninstall_crash_hooks",
           "last_dump_path", "set_fleet_provider", "fleet_block"]

SCHEMA = "mxtpu-blackbox/1"

_LOCK = threading.Lock()
_RING = None                    # deque of (ts, tid, kind, name, data)
_SEQ = itertools.count(1)       # CPython-atomic; dump filename ordinal
_HBM_PEAK = {}                  # device label -> peak bytes_in_use seen
_LAST_COUNTS = {}               # sample_counters baseline
_LAST = {"path": None}          # newest dump path (tests / CLI)
_CRASH_SEEN = {}                # reason -> last crash_dump wall time
#: min seconds between crash dumps for the SAME reason — a persistent
#: dispatcher error loops every ~10ms, and each dump is a full file;
#: without a throttle a degraded host fills its disk with forensics
CRASH_DUMP_MIN_GAP_S = 10.0

# None = follow the MXNET_BLACKBOX knob; enable() installs an explicit
# process-local override (the spans.py pattern)
_enabled = None

# fleet-view provider (ISSUE 11): telemetry/fleet.py registers a
# zero-arg callable returning the merged per-replica telemetry block;
# every dump embeds its result so a forensic file answers "which
# replica was slow" without a live process to ask
_FLEET = {"provider": None}


def set_fleet_provider(fn):
    """Register the callable whose result becomes the `fleet` block of
    every black-box dump (None unregisters).  Best-effort at dump
    time: a raising provider yields no block, never a failed dump."""
    _FLEET["provider"] = fn


def fleet_block():
    """The registered fleet provider's current block (None when no
    provider is set, the provider raised, or its supervisor is gone)."""
    fn = _FLEET["provider"]
    if fn is None:
        return None
    try:
        return fn()
    except Exception:               # noqa: BLE001 — the fleet view is
        return None                 # forensic garnish, never a blocker


def enabled() -> bool:
    """Whether the flight recorder is on (default: yes — it exists for
    the runs nobody instrumented in advance)."""
    if _enabled is not None:
        return _enabled
    return bool(_cfg.get("MXNET_BLACKBOX"))


def enable(flag=True):
    """Flip the recorder on/off (None = revert to the MXNET_BLACKBOX
    knob); returns the previous effective state."""
    global _enabled
    prev = enabled()
    _enabled = None if flag is None else bool(flag)
    return prev


def _ring():
    global _RING
    r = _RING
    if r is None:
        with _LOCK:
            if _RING is None:
                _RING = deque(maxlen=max(
                    16, int(_cfg.get("MXNET_BLACKBOX_RING"))))
            r = _RING
    return r


def configure(maxlen=None):
    """(Re)size the ring (drops retained events).  Tests use this; the
    default comes from MXNET_BLACKBOX_RING at first use."""
    global _RING
    with _LOCK:
        _RING = deque(maxlen=max(16, int(
            maxlen if maxlen is not None
            else _cfg.get("MXNET_BLACKBOX_RING"))))


def record(kind: str, name: str, **data):
    """Append one structured event to the ring.  The HOT path: one
    bool read disabled (checked HERE, before the clock read and the
    delegate call — the MXNET_BLACKBOX=0 contract); enabled, one
    tuple + one locked deque append — no formatting, no serialization
    until dump time."""
    if not enabled():
        return
    record_at(time.time(), kind, name, **data)


def record_at(ts: float, kind: str, name: str, **data):
    """`record()` with an explicit wall-clock stamp: a FOREIGN span
    (telemetry.emit_foreign) describes an interval that ended in
    another process BEFORE the message delivering it arrived — a
    prefetched decode batch can sit in the queue for hundreds of ms,
    and stamping delivery time would shift the slice right by the
    whole queue wait in the dump's chrome view."""
    if not enabled():
        return
    ev = (float(ts), threading.get_ident(), kind, name, data or None)
    _ring()                         # ensure it exists (locks itself)
    with _LOCK:
        # re-read under the lock: a concurrent configure() swaps the
        # ring, and appending to the discarded deque loses the event
        _RING.append(ev)


def record_mesh(phase: str, **data):
    """Mesh-transition marker (the elastic trainer's forensic trail):
    one ring event under kind ``mesh`` — ``replica_down`` /
    ``replica_slow`` / ``shrink`` / ``grow`` / ``generation`` — with
    the replica ids, device labels and step in `data`.  A mesh-shrink
    black-box dump is read by exactly these events: the dump NAMES the
    lost replica because this marker landed in the ring before
    `crash_dump("mesh.shrink")` snapshotted it."""
    record("mesh", phase, **data)


def clear():
    with _LOCK:
        if _RING is not None:
            _RING.clear()
        _LAST_COUNTS.clear()
        _HBM_PEAK.clear()
        _CRASH_SEEN.clear()
        _LAST["path"] = None


def ring_snapshot(last=None):
    """The retained events, oldest first, as dicts (`last` keeps only
    the newest N)."""
    with _LOCK:
        evs = list(_RING) if _RING is not None else []
    if last is not None:
        evs = evs[-int(last):]
    out = []
    for ts, tid, kind, name, data in evs:
        d = {"ts": ts, "tid": tid % 100000, "kind": kind, "name": name}
        if data:
            d.update(data)
        out.append(d)
    return out


# -- HBM watermarks ----------------------------------------------------
def hbm_sample(tag="sample", force=False):
    """Sample per-device HBM via `storage.memory_events` (which posts
    the `mem.*` series on monitor.events), update the per-device peak
    watermarks, and append one ring event per device.  Backends whose
    PJRT `memory_stats` returns None (CPU jax) used to silently
    no-op here; they now fall back
    to the `jax.live_arrays()` per-device byte sum
    (`storage.live_arrays_events`), each event tagged
    ``source="live_arrays"`` so a dump never mistakes the committed-
    buffer sum for an allocator report.  Gated on `enabled()` (the
    MXNET_BLACKBOX=0 contract is a single bool read per hook);
    `force=True` is the dump path, which samples even when an explicit
    dump was requested on a disarmed recorder."""
    if not (enabled() or force):
        return []
    try:
        from ..storage import live_arrays_events, memory_events
        stats = memory_events()
        if not stats:
            stats = live_arrays_events()
    except Exception:               # noqa: BLE001 — forensics must
        return []                   # never take the run down
    for s in stats:
        dev = s["device"]
        with _LOCK:
            peak = max(_HBM_PEAK.get(dev, 0),
                       s.get("peak_bytes", 0), s["bytes_in_use"])
            _HBM_PEAK[dev] = peak
        record("hbm", dev, tag=tag, bytes_in_use=s["bytes_in_use"],
               peak_bytes=peak, bytes_limit=s.get("bytes_limit", 0),
               **({"source": s["source"]} if "source" in s else {}))
    return stats


def hbm_peaks() -> dict:
    """{device: peak bytes_in_use observed by hbm_sample}."""
    with _LOCK:
        return dict(_HBM_PEAK)


# -- counter-delta samples ---------------------------------------------
def sample_counters(prefixes=None):
    """Record the nonzero counter DELTAS since the last sample as one
    ring event (the periodic exporter calls this every tick, so the
    timeline shows counter flow between dumps, not just the final
    totals).  Returns the delta dict.  Baseline updates are locked —
    the exporter worker and a checkpointing training thread sample
    concurrently, and a racy read-modify-write would double-count or
    drop deltas in the forensic timeline."""
    if not enabled():
        return {}
    snap = events.snapshot()
    if prefixes:
        snap = {k: v for k, v in snap.items()
                if any(k.startswith(p) for p in prefixes)}
    delta = {}
    with _LOCK:
        for k, v in snap.items():
            d = v - _LAST_COUNTS.get(k, 0)
            if d:
                delta[k] = d
            _LAST_COUNTS[k] = v
    if delta:                       # record() takes _LOCK itself —
        record("counters", "delta", **delta)    # append outside it
    return delta


# -- dump --------------------------------------------------------------
def _exc_block(exc):
    if exc is None:
        return None
    import traceback
    try:
        tb = "".join(traceback.format_exception(
            type(exc), exc, getattr(exc, "__traceback__", None)))
    except Exception:               # noqa: BLE001
        tb = ""
    return {"type": type(exc).__name__,
            "message": str(exc)[:500],
            "traceback": tb[-8000:]}


def _config_snapshot():
    out = {}
    for name in _cfg.list_vars():
        try:
            v = _cfg.get(name)
            out[name] = v if isinstance(
                v, (bool, int, float, str, type(None))) else str(v)
        except Exception:           # noqa: BLE001
            out[name] = "<unreadable>"
    return out


def _chrome_view(evs):
    """The event timeline as chrome://tracing JSON: span events render
    as complete ('X') slices, everything else as instants.  An event
    carrying an explicit `pid` (a foreign span emitted on behalf of a
    decode worker — telemetry.emit_foreign) keeps that pid, so the
    trace shows the worker's interval in its own process row."""
    out = []
    for e in evs:
        base = {"name": "%s:%s" % (e["kind"], e["name"]),
                "cat": e["kind"], "pid": e.get("pid") or os.getpid(),
                "tid": e["tid"]}
        dur = e.get("dur_us")
        if dur is not None:
            base.update(ph="X", ts=(e["ts"] * 1e6) - dur, dur=dur)
        else:
            base.update(ph="i", ts=e["ts"] * 1e6, s="t")
        args = {k: v for k, v in e.items()
                if k not in ("ts", "tid", "kind", "name")}
        if args:
            base["args"] = args
        out.append(base)
    return out


def _slug(s):
    return "".join(c if c.isalnum() or c in "-_." else "-"
                   for c in str(s))[:48] or "dump"


def _resolve_path(path, reason):
    if path:
        path = str(path)
        if not os.path.isdir(path):
            return path             # explicit file
        d = path
    else:
        # default to scratch, never the checkout: crash hooks armed
        # OUTSIDE bench/conftest (which set MXNET_BLACKBOX_DIR) used
        # to drop excepthook dumps into whatever directory the process
        # happened to be launched from — typically the repo root
        import tempfile
        d = _cfg.get("MXNET_BLACKBOX_DIR") or tempfile.gettempdir()
        os.makedirs(d, exist_ok=True)
    name = "blackbox-%s-p%d-%03d-%s.json" % (
        time.strftime("%Y%m%dT%H%M%S"), os.getpid(), next(_SEQ),
        _slug(reason))
    return os.path.join(d, name)


def dump_blackbox(path=None, reason="manual", exc=None, last=None):
    """Write the black box: config-knob snapshot, counter ledger +
    percentiles, executable cost table, HBM watermarks, the last-N
    event timeline, and a chrome-trace view of it — one atomic JSON
    file (tmp + os.replace).  `path` may be a file, a directory, or
    None (MXNET_BLACKBOX_DIR, else the system temp dir; auto-named).
    Returns the written path."""
    # order matters: snapshot the ledger FIRST, then sample (the
    # sample's own events land in the timeline of the NEXT dump, and
    # cost resolution must not skew the counters this dump reports)
    counters = events.snapshot()
    pcts = events.latency_snapshot()
    # tenant/lane splits (ISSUE 8): the labeled rings ride along so an
    # overload dump can say WHOSE p99 blew out, not just that one did
    labeled = {"counters": events.labeled_snapshot(),
               "percentiles": events.labeled_latency_snapshot()}
    hbm_sample(tag="dump", force=True)
    from . import costs as _costs
    try:
        cost_block = _costs.snapshot()
    except Exception:               # noqa: BLE001 — cost attribution
        cost_block = {"rows": [], "totals": {}}  # is best-effort
    fleet = fleet_block()
    # the SLO rule/alert state (ISSUE 12): a dump triggered BY an
    # alert (reason "slo:<rule>") carries the firing evidence; any
    # other dump still answers "was anything firing when this died"
    try:
        from . import slo as _slo
        slo_block = _slo.block() or None
    except Exception:               # noqa: BLE001 — forensic garnish
        slo_block = None
    # the control-plane state (ISSUE 16): guarded on the module being
    # ALREADY imported — a training-only dump must not pull the whole
    # serving stack in just to say "no supervisors"
    ctl_block = None
    try:
        ctl_mod = sys.modules.get(
            "incubator_mxnet_tpu.serving.controlplane")
        if ctl_mod is not None:
            ctl_block = ctl_mod.status_block() or None
    except Exception:               # noqa: BLE001
        ctl_block = None
    # the compile-loop decisions (ISSUE 18): same already-imported
    # guard — a run that never tuned must not pull the compile
    # subsystem in just to say "no decisions"
    tune_block = None
    try:
        tune_mod = sys.modules.get(
            "incubator_mxnet_tpu.compile.autotune")
        if tune_mod is not None:
            tune_block = tune_mod.block() or None
    except Exception:               # noqa: BLE001
        tune_block = None
    # the request journals + promoted exemplars (ISSUE 19): same
    # already-imported guard — a dump from a process that never ran an
    # engine must not import the tracing layer to say "no requests"
    rt_block = None
    try:
        rt_mod = sys.modules.get(
            "incubator_mxnet_tpu.telemetry.reqtrace")
        if rt_mod is not None:
            rt_block = rt_mod.block() or None
    except Exception:               # noqa: BLE001
        rt_block = None
    # the memory observatory (ISSUE 20): same already-imported guard;
    # the dump takes one sample first (when armed) so the block shows
    # the corpse's residency, not a stale tick — the OOM path already
    # forced its own sample before reaching here
    mw_block = None
    try:
        mw_mod = sys.modules.get(
            "incubator_mxnet_tpu.telemetry.memwatch")
        if mw_mod is not None:
            mw_mod.sample(tag="dump")
            mw_block = mw_mod.block() or None
    except Exception:               # noqa: BLE001
        mw_block = None
    evs = ring_snapshot(last=last)
    doc = {
        "schema": SCHEMA,
        "ts": time.time(),
        "pid": os.getpid(),
        "reason": str(reason),
        "exception": _exc_block(exc),
        "config": _config_snapshot(),
        "counters": counters,
        "percentiles": pcts,
        "labeled": labeled,
        "costs": cost_block,
        "fleet": fleet,
        "slo": slo_block,
        "controlplane": ctl_block,
        "autotune": tune_block,
        "reqtrace": rt_block,
        "memwatch": mw_block,
        "hbm": {"peaks": hbm_peaks()},
        "events": evs,
        "trace": {"traceEvents": _chrome_view(evs),
                  "displayTimeUnit": "ms"},
    }
    path = _resolve_path(path, reason)
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(), threading.get_ident())
    with open(tmp, "w") as f:
        json.dump(doc, f, default=str)
    os.replace(tmp, path)
    _LAST["path"] = path
    events.incr("blackbox.dumps")
    record("dump", str(reason), path=path)
    return path


def last_dump_path():
    """The newest dump this process wrote (None before the first)."""
    return _LAST["path"]


def crash_dump(reason, exc=None):
    """`dump_blackbox` for crash paths: never raises (a failing dump
    in an excepthook / signal handler / dispatcher backstop must not
    mask the original failure), and throttled per reason
    (CRASH_DUMP_MIN_GAP_S) — a persistently-failing dispatcher loop
    must not fill the disk with one dump per poll.  Returns the path,
    or None (disabled / throttled / failed)."""
    if not enabled():
        return None
    now = time.monotonic()
    with _LOCK:
        last = _CRASH_SEEN.get(reason)
        if last is not None and now - last < CRASH_DUMP_MIN_GAP_S:
            return None
        _CRASH_SEEN[reason] = now
    try:
        return dump_blackbox(reason=reason, exc=exc)
    except Exception:               # noqa: BLE001
        return None


# -- crash hooks -------------------------------------------------------
_HOOKS = {"installed": False, "prev_sys": None, "prev_thread": None,
          "prev_sig": None, "sig_installed": False}


def install_crash_hooks(sigusr2=True):
    """Install the black-box triggers: `sys.excepthook` +
    `threading.excepthook` (CHAINED — the previous hooks still run
    after the dump) and, on the main thread, a SIGUSR2 handler (which
    REPLACES any previous one; `uninstall_crash_hooks` restores it).
    Idempotent, and each trigger arms independently: a first call off
    the main thread installs the excepthooks only, and a later
    main-thread call still arms SIGUSR2.  No-op (returns False) when
    the recorder is disabled."""
    if not enabled():
        return False
    did = False
    if not _HOOKS["installed"]:
        prev_sys = sys.excepthook
        prev_thread = threading.excepthook

        def _sys_hook(tp, val, tb):
            if not (tp is SystemExit or tp is KeyboardInterrupt):
                record("fault", "uncaught", where="main",
                       type=getattr(tp, "__name__", str(tp)))
                crash_dump("excepthook", val)
            (prev_sys or sys.__excepthook__)(tp, val, tb)

        def _thread_hook(args):
            if args.exc_type is not SystemExit:
                record("fault", "uncaught",
                       where=getattr(args.thread, "name", "?"),
                       type=getattr(args.exc_type, "__name__", "?"))
                crash_dump("threading.excepthook", args.exc_value)
            prev_thread(args)

        sys.excepthook = _sys_hook
        threading.excepthook = _thread_hook
        _HOOKS.update(prev_sys=prev_sys, prev_thread=prev_thread,
                      installed=True)
        did = True
    if sigusr2 and not _HOOKS["sig_installed"] \
            and hasattr(signal, "SIGUSR2"):
        def _usr2_work():
            record("marker", "sigusr2")
            crash_dump("sigusr2")

        def _on_usr2(signum, frame):
            # the handler interrupts the main thread BETWEEN bytecodes
            # — it may hold the ring lock mid-record(), so taking it
            # here would self-deadlock; hand the dump to a thread
            threading.Thread(target=_usr2_work, daemon=True,
                             name="BlackboxUSR2").start()
        try:
            _HOOKS["prev_sig"] = signal.signal(signal.SIGUSR2, _on_usr2)
            _HOOKS["sig_installed"] = True
            did = True
        except (ValueError, OSError):   # not the main thread: a later
            _HOOKS["prev_sig"] = None   # main-thread call retries
    return did


def uninstall_crash_hooks():
    """Restore the chained hooks (tests; idempotent)."""
    if not _HOOKS["installed"]:
        return
    sys.excepthook = _HOOKS["prev_sys"] or sys.__excepthook__
    threading.excepthook = _HOOKS["prev_thread"] or \
        threading.__excepthook__
    if _HOOKS["sig_installed"]:
        try:
            signal.signal(signal.SIGUSR2,
                          _HOOKS["prev_sig"] or signal.SIG_DFL)
        except (ValueError, OSError):
            pass
        _HOOKS["sig_installed"] = False
    _HOOKS.update(installed=False, prev_sys=None, prev_thread=None,
                  prev_sig=None)
