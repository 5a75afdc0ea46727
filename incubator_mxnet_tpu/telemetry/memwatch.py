"""Sampled per-device memory observatory (ISSUE 20 tentpole).

PR 19 gave the fleet request-level *latency* truth; memory was still
flying blind: the registry admits tenants on a committed-bytes ledger
built from XLA `memory_analysis` estimates, the controlplane halves
its shrink window on "HBM pressure" computed from those same
estimates, and an allocator OOM produced a bare RESOURCE_EXHAUSTED
with no record of who was actually resident.  This module closes the
loop between COMMITTED (what the ledgers promised) and MEASURED (what
the allocator actually holds):

- **Sampling.**  `sample()` reads PJRT ``memory_stats`` per device
  (`storage.memory_events`) where the backend reports it, and falls
  back to a `jax.live_arrays()` per-device byte sum — tagged
  ``source="live_arrays"`` — on hosts whose ``memory_stats`` returns
  None (CPU jax).  Samples land in a bounded ring
  (MXNET_MEMWATCH_RING) and update per-phase peak watermarks
  (warmup / steady / deploy); a watermark that RISES writes a durable
  ``memwatch`` history row (telemetry/history.py — the PR 12 shard
  discipline, so run N+1 reads run N's envelope by run id).
- **Attribution.**  `attribution()` joins measured device bytes
  against every committed consumer it can see: the live
  `ModelRegistry` ledgers (per-entry footprints, basis, KV slot
  pools via ``kv_cache_bytes``, AOT ``memory_analysis`` rows via
  `costs.footprint_bytes`), tracked trainers (parameter placement +
  ZeRO `BucketPlan.describe()`), and any injected `register_source`
  rows (what the tests hand-build).  Each device's measured bytes are
  apportioned to its tenants proportionally to their commitments;
  bytes no tenant committed show up as an explicit
  ``(unattributed)`` row instead of vanishing.
- **Drift + OOM forensics.**  `slo.MemDriftRule` judges the
  attribution each exporter tick and fires when measured contradicts
  committed by >MXNET_MEMWATCH_DRIFT_FACTOR either direction,
  carrying the top-N consumers table and re-reconciling the ledger
  row (`reconcile_tenant`).  Allocation-failure paths (engine build,
  serving/generation warmup, both trainers) call `guard_oom(site,
  exc)`: a RESOURCE_EXHAUSTED exception takes a forced sample and a
  proactive black-box dump whose ``memwatch`` block holds
  committed-vs-measured per tenant, the watermarks and the recent
  deploy/scale/register events — rendered by ``python -m
  incubator_mxnet_tpu.tools.blackbox memautopsy``.

Hot-path contract: ``MXNET_MEMWATCH=0`` (or `enable(False)`) makes
`sample()` a single bool read; enabled, sampling happens ONLY at
exporter-tick cadence, dump time, and warmup/deploy phase transitions
— never per request or step.  `tools/check_overhead.py --what mem`
holds the serving loop with memwatch on vs off to <2%.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time
import weakref
from collections import deque

from .. import config as _cfg
from ..monitor import events
from . import flightrec as _bb

__all__ = ["enabled", "enable", "sample", "samples", "last_sample",
           "fresh_sample", "fresh_device_bytes", "watermarks",
           "set_phase", "current_phase", "phase", "register_source",
           "unregister_source", "track_trainer", "committed_rows",
           "attribution", "top_consumers", "reconcile_tenant",
           "is_oom", "oom_dump", "guard_oom", "block", "reset",
           "device_key", "canon_device"]

#: the phase ladder the watermarks are kept per: deploys and warmups
#: spike transient working sets the steady-state envelope must not
#: absorb (an eviction advisor sized off a warmup spike would evict
#: half the fleet)
PHASES = ("warmup", "steady", "deploy")

#: substrings that mark an allocator out-of-memory failure.  PJRT
#: surfaces XlaRuntimeError with a RESOURCE_EXHAUSTED status; numpy /
#: host paths raise MemoryError ("Unable to allocate ...")
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "out of memory", "Out of memory",
                "Unable to allocate", "MemoryError")

# None = follow the MXNET_MEMWATCH knob; enable() installs an explicit
# process-local override (the flightrec/reqtrace pattern — what the
# overhead gate's on/off trial flips)
_enabled = None

_LOCK = threading.Lock()
_RING = None                    # deque of sample dicts
_WATERMARKS = {}                # phase -> {device: peak used bytes}
_LAST = {"sample": None}        # newest sample (monotonic "mono" key)
_PHASE = ["steady"]             # current phase (list = mutable cell)
_SAMPLER = [None]               # injected probe for tests
_SOURCES = {}                   # name -> callable -> rows | None
_TRAINERS = weakref.WeakSet()   # tracked trainers (ZeRO attribution)


def enabled() -> bool:
    """Whether the observatory is armed for this process."""
    if _enabled is not None:
        return _enabled
    return bool(_cfg.get("MXNET_MEMWATCH"))


def enable(flag=True):
    """Flip sampling on/off (None = revert to the MXNET_MEMWATCH
    knob); returns the previous effective state."""
    global _enabled
    prev = enabled()
    _enabled = None if flag is None else bool(flag)
    return prev


def set_sampler(fn):
    """Install a probe override for deterministic tests: ``fn()``
    returns the per-device dict `sample()` would otherwise measure
    (``{device: {"used_bytes", "peak_bytes", "limit_bytes",
    "source"}}``).  ``None`` restores the real probe.  Returns the
    previous override."""
    prev = _SAMPLER[0]
    _SAMPLER[0] = fn
    return prev


# -- device naming -----------------------------------------------------
def device_key(dev) -> str:
    """Canonical ``platform:id`` key for a jax device or a Context."""
    dev = getattr(dev, "jax_device", dev)
    return "%s:%d" % (getattr(dev, "platform",
                              getattr(dev, "device_type", "dev")),
                      getattr(dev, "id",
                              getattr(dev, "device_id", 0)))


def canon_device(name) -> str:
    """Normalize a device label to the ``platform:id`` key —
    `Context.__repr__` prints ``cpu(0)``, PJRT prints ``cpu:0``."""
    s = str(name)
    if s.endswith(")") and "(" in s:
        head, _, tail = s.partition("(")
        return "%s:%s" % (head, tail[:-1])
    return s


# -- sampling ----------------------------------------------------------
def _probe():
    """One real measurement pass: PJRT stats where reported,
    live-array sums (`storage.live_arrays_events`) for the statless
    devices."""
    import jax
    devs = {}
    try:
        from ..storage import memory_events
        stats = memory_events()
    except Exception:               # noqa: BLE001 — forensics must
        stats = []                  # never take the run down
    for s in stats:
        devs[s["device"]] = {
            "used_bytes": int(s["bytes_in_use"]),
            "peak_bytes": int(s.get("peak_bytes", 0)),
            "limit_bytes": int(s.get("bytes_limit", 0)),
            "source": "memory_stats"}
    try:
        missing = [d for d in jax.devices()
                   if device_key(d) not in devs]
    except Exception:               # noqa: BLE001
        missing = []
    if missing:
        try:
            from ..storage import live_arrays_events
            live = {s["device"]: s
                    for s in live_arrays_events(devices=missing)}
        except Exception:           # noqa: BLE001
            live = {}
        for d in missing:
            k = device_key(d)
            used = int(live.get(k, {}).get("bytes_in_use", 0))
            devs[k] = {"used_bytes": used, "peak_bytes": used,
                       "limit_bytes": 0, "source": "live_arrays"}
    return devs


def _ring():
    global _RING
    if _RING is None:
        with _LOCK:
            if _RING is None:
                _RING = deque(
                    maxlen=max(1, int(_cfg.get("MXNET_MEMWATCH_RING"))))
    return _RING


def sample(tag="sample", force=False, throttle=True):
    """Take one observatory sample: per-device used/peak/limit bytes
    with their ``source``, stamped with the current phase.  Updates
    the per-phase watermarks (a rising watermark writes a durable
    ``memwatch`` history row) and appends to the bounded ring.
    Returns the sample dict, or None when disabled (one bool read —
    the whole MXNET_MEMWATCH=0 cost).

    Unforced periodic calls are THROTTLED: within
    MXNET_MEMWATCH_MIN_S of the previous sample the call returns that
    sample unchanged, without re-probing or re-recording — any caller
    may poll at its own cadence and the observatory still bounds its
    own probe cost.  ``force=True`` (the OOM/dump/bench path) and the
    phase-transition samples (``throttle=False``) always probe."""
    if not (enabled() or force):
        return None
    if throttle and not force:
        min_s = float(_cfg.get("MXNET_MEMWATCH_MIN_S"))
        with _LOCK:
            last = _LAST["sample"]
        if last is not None and min_s > 0 \
                and time.monotonic() - last.get("mono", 0) < min_s:
            return last
    probe = _SAMPLER[0] or _probe
    try:
        devs = probe() or {}
    except Exception:               # noqa: BLE001 — the observatory
        return None                 # must never take the run down
    now = time.time()
    ph = _PHASE[0]
    s = {"ts": now, "mono": time.monotonic(), "phase": ph,
         "tag": str(tag), "devices": devs,
         "total_bytes": sum(d.get("used_bytes", 0)
                            for d in devs.values())}
    rose = []
    ring = _ring()
    with _LOCK:
        marks = _WATERMARKS.setdefault(ph, {})
        for dev, d in devs.items():
            used = int(d.get("used_bytes", 0))
            if used > marks.get(dev, 0):
                marks[dev] = used
                rose.append((dev, used, d.get("source", "?")))
        ring.append(s)
        _LAST["sample"] = s
    events.incr("memwatch.samples")
    for dev, used, src in rose:
        try:
            from . import history as _hist
            _hist.record("memwatch", "watermark", float(used),
                         labels={"device": dev, "phase": ph,
                                 "source": str(src)})
        except Exception:           # noqa: BLE001 — durability is
            pass                    # best-effort
    return s


def samples():
    """The retained samples, oldest first."""
    with _LOCK:
        return list(_RING) if _RING is not None else []


def last_sample():
    """The newest sample (None before the first)."""
    with _LOCK:
        return _LAST["sample"]


def fresh_sample(max_age_s=None):
    """The newest sample if it is younger than ``max_age_s``
    (MXNET_MEMWATCH_FRESH_S), else None — the freshness contract the
    controlplane pressure upgrade and the drift rule judge under."""
    s = last_sample()
    if s is None:
        return None
    if max_age_s is None:
        max_age_s = float(_cfg.get("MXNET_MEMWATCH_FRESH_S"))
    if time.monotonic() - s.get("mono", 0.0) > max_age_s:
        return None
    return s


def fresh_device_bytes(max_age_s=None):
    """{device: measured used bytes} from a fresh sample, else None."""
    s = fresh_sample(max_age_s)
    if s is None:
        return None
    return {dev: int(d.get("used_bytes", 0))
            for dev, d in s["devices"].items()}


def watermarks() -> dict:
    """{phase: {device: peak used bytes}} observed so far."""
    with _LOCK:
        return {ph: dict(m) for ph, m in _WATERMARKS.items()}


# -- phases ------------------------------------------------------------
def set_phase(name):
    """Set the current phase (``warmup`` / ``steady`` / ``deploy``);
    returns the previous one."""
    prev = _PHASE[0]
    _PHASE[0] = str(name)
    return prev


def current_phase() -> str:
    return _PHASE[0]


@contextlib.contextmanager
def phase(name):
    """Scope a phase transition: watermarks taken inside attribute to
    ``name``, and one sample is taken on EXIT (the transition itself
    is the cadence — a deploy's residency spike is observed exactly
    when it exists, without touching any per-request path)."""
    prev = set_phase(name)
    try:
        yield
    finally:
        try:
            # transitions are rare and authoritative — never throttled
            sample(tag="phase:%s" % name, throttle=False)
        except Exception:           # noqa: BLE001
            pass
        set_phase(prev)


# -- attribution -------------------------------------------------------
def register_source(name, fn):
    """Register a committed-bytes source: ``fn()`` returns rows
    ``{"tenant", "device", "committed_bytes", ...}`` (or None to
    auto-unregister).  Tests hand-build ledgers through this; the
    registry/trainer joins are built in."""
    with _LOCK:
        _SOURCES[str(name)] = fn


def unregister_source(name):
    with _LOCK:
        _SOURCES.pop(str(name), None)


def track_trainer(trainer):
    """Weakly track a trainer for attribution (its parameter
    placement + ZeRO bucket plan become committed rows).  Called from
    `ShardedTrainer.__init__`; safe to call many times."""
    _TRAINERS.add(trainer)


def _registry_rows():
    """Committed rows from every live `ModelRegistry`: one row per
    (model, device) at the ledger footprint, carrying the admission
    basis, the KV slot-pool split (generation engines) and the AOT
    memory-analysis view (`costs.footprint_bytes`) as detail."""
    reg_mod = sys.modules.get("incubator_mxnet_tpu.serving.registry")
    if reg_mod is None:
        return []
    from . import costs as _costs
    rows = []
    for reg in reg_mod.live_registries():
        try:
            with reg._lock:
                entries = [e for e in reg._models.values()
                           if e is not None]
                ctxs = list(reg._ctxs)
        except Exception:           # noqa: BLE001
            continue
        for e in entries:
            aot = 0
            try:
                aot = max(_costs.footprint_bytes(fam, kind="serve")
                          for fam in e.cost_labels)
            except Exception:       # noqa: BLE001
                pass
            kv = None
            kv_fn = getattr(e.engine, "kv_cache_bytes", None)
            if callable(kv_fn):
                try:
                    kv = kv_fn()
                except Exception:   # noqa: BLE001
                    kv = None
            for i in e.devices:
                row = {"tenant": e.name,
                       "device": device_key(ctxs[i]),
                       "committed_bytes": int(e.footprint),
                       "kind": "serve", "basis": e.basis,
                       "origin": "registry"}
                if aot:
                    row["aot_bytes"] = int(aot)
                if kv:
                    row["kv_bytes"] = int(kv.get("total", 0))
                    row["kv_slots"] = int(kv.get("slots", 0))
                rows.append(row)
    return rows


def _trainer_rows():
    """Committed rows from the tracked trainers: parameter bytes BY
    PLACEMENT (each addressable shard counts on the device that holds
    it — ZeRO>=2 shards show 1/N per device, replicated params show
    the full copy everywhere), with the `BucketPlan.describe()`
    envelope as detail."""
    rows = []
    for tr in list(_TRAINERS):
        per_dev = {}
        try:
            import jax
            for a in jax.tree_util.tree_leaves(tr.params):
                try:
                    for sh in a.addressable_shards:
                        k = device_key(sh.device)
                        per_dev[k] = per_dev.get(k, 0) \
                            + int(sh.data.nbytes)
                except Exception:   # noqa: BLE001
                    continue
        except Exception:           # noqa: BLE001
            continue
        plan = getattr(tr, "_zero_plan", None)
        detail = None
        if plan is not None:
            try:
                detail = plan.describe()
            except Exception:       # noqa: BLE001
                detail = None
        name = "train:%s" % (
            getattr(getattr(tr, "net", None), "prefix", "")
            or "sharded").strip("_")
        for dev, b in per_dev.items():
            row = {"tenant": name, "device": dev,
                   "committed_bytes": int(b), "kind": "train",
                   "basis": "placement", "origin": "trainer"}
            if detail:
                row["zero_plan"] = {
                    k: detail[k] for k in ("bucket_cap_mb",
                                           "solo_bytes",
                                           "concat_bytes")
                    if k in detail}
            rows.append(row)
    return rows


def committed_rows():
    """Every committed-bytes row the observatory can see: injected
    sources first (auto-unregistered when they return None), then the
    built-in registry and trainer joins."""
    with _LOCK:
        srcs = list(_SOURCES.items())
    rows = []
    dead = []
    for name, fn in srcs:
        try:
            r = fn()
        except Exception:           # noqa: BLE001
            continue
        if r is None:
            dead.append(name)
            continue
        for x in r:
            rows.append(dict(x, origin=x.get("origin", name)))
    for name in dead:
        unregister_source(name)
    rows.extend(_registry_rows())
    rows.extend(_trainer_rows())
    return rows


def attribution(smp=None, top=None, rows=None):
    """Join a sample against the committed rows: each device's
    measured bytes are apportioned to its tenants proportionally to
    their commitments (``measured_bytes``), with ``drift`` =
    measured/committed; measured bytes no tenant committed become an
    explicit ``(unattributed)`` row.  Sorted biggest consumer first;
    ``top`` caps the list (MXNET_MEMWATCH_TOP when the callers that
    render tables pass it).  Returns [] before the first sample."""
    smp = smp if smp is not None else last_sample()
    if not smp:
        return []
    rows = committed_rows() if rows is None else list(rows)
    by_dev = {}
    for r in rows:
        by_dev.setdefault(canon_device(r.get("device")), []).append(r)
    out = []
    for dev, d in sorted(smp.get("devices", {}).items()):
        measured = int(d.get("used_bytes", 0))
        src = d.get("source", "?")
        drows = by_dev.get(dev, [])
        committed = sum(int(r.get("committed_bytes", 0))
                        for r in drows)
        for r in drows:
            c = int(r.get("committed_bytes", 0))
            share = (measured * c // committed) if committed > 0 \
                else 0
            out.append(dict(
                r, device=dev, measured_bytes=int(share),
                drift=(round(share / c, 4) if c > 0 else None),
                device_used_bytes=measured, source=src))
        if not drows and measured > 0:
            out.append({"tenant": "(unattributed)", "device": dev,
                        "committed_bytes": 0,
                        "measured_bytes": measured, "drift": None,
                        "device_used_bytes": measured,
                        "kind": "?", "origin": "memwatch",
                        "source": src})
    out.sort(key=lambda r: -r.get("measured_bytes", 0))
    if top is not None:
        out = out[:max(1, int(top))]
    return out


def top_consumers(n=None, smp=None, rows=None):
    """{tenant@device: measured bytes} for the top-N attribution rows
    — the table a firing mem-drift alert and the memautopsy verdict
    carry."""
    if n is None:
        n = int(_cfg.get("MXNET_MEMWATCH_TOP"))
    return {"%s@%s" % (r["tenant"], r["device"]):
            int(r.get("measured_bytes", 0))
            for r in attribution(smp=smp, top=n, rows=rows)}


def reconcile_tenant(tenant) -> bool:
    """Re-reconcile a drifting tenant's ledger row on every live
    registry hosting it (`ModelRegistry.reconcile` — measured AOT
    rows replace the projection).  Returns True if any registry
    recognized the tenant."""
    reg_mod = sys.modules.get("incubator_mxnet_tpu.serving.registry")
    if reg_mod is None:
        return False
    hit = False
    for reg in reg_mod.live_registries():
        try:
            with reg._lock:
                known = tenant in reg._models \
                    and reg._models[tenant] is not None
            if known:
                reg.reconcile(tenant)
                hit = True
        except Exception:           # noqa: BLE001 — reconciliation is
            continue                # an alert side-effect, best-effort
    return hit


# -- OOM forensics -----------------------------------------------------
def is_oom(exc) -> bool:
    """Whether an exception is an allocator out-of-memory failure
    (PJRT RESOURCE_EXHAUSTED, host MemoryError, numpy's 'Unable to
    allocate')."""
    if exc is None:
        return False
    if isinstance(exc, MemoryError):
        return True
    text = "%s: %s" % (type(exc).__name__, exc)
    return any(m in text for m in _OOM_MARKERS)


def oom_dump(site, exc=None):
    """The proactive OOM black box: one forced sample (the corpse's
    residency, live-arrays fallback and all), an ``oom`` ring event
    naming the site, then a crash dump whose reason carries the
    ``memwatch:oom:<site>`` family `blackbox.suspected_cause` and the
    ``memautopsy`` subcommand key on.  Never raises; returns the dump
    path (None = disabled/throttled)."""
    try:
        sample(tag="oom", force=True)
    except Exception:               # noqa: BLE001
        pass
    events.incr("memwatch.oom")
    events.incr("memwatch.oom", labels={"site": str(site)})
    _bb.record("memwatch", "oom", site=str(site),
               error=type(exc).__name__ if exc is not None else None)
    return _bb.crash_dump("memwatch:oom:%s" % site, exc)


def guard_oom(site, exc) -> bool:
    """The one-line catch-site helper: `oom_dump` iff `is_oom(exc)`.
    Returns whether the exception was an OOM (callers re-raise
    either way)."""
    if not is_oom(exc):
        return False
    oom_dump(site, exc)
    return True


# -- surfaces ----------------------------------------------------------
def _recent_lifecycle_events(last=16):
    """The newest deploy/scale/register flight-recorder events — the
    'what just changed residency' trail the OOM block carries."""
    names = ("registered", "unregistered", "registered_version",
             "footprint_reconciled", "footprint_reconcile_large",
             "admission_rejected", "scale_up", "scale_down",
             "deploy", "promote", "rollback", "hbm_pressure")
    out = [e for e in _bb.ring_snapshot()
           if e.get("kind") in ("serve", "controlplane")
           and e.get("name") in names]
    return out[-int(last):]


def block() -> dict:
    """The ``memwatch`` block for dumps, /metrics.json and teletop:
    newest sample, per-phase watermarks, the attribution join and the
    recent lifecycle events.  {} before the first sample (so the
    optional-block surfaces skip it cleanly)."""
    s = last_sample()
    if s is None:
        return {}
    top = int(_cfg.get("MXNET_MEMWATCH_TOP"))
    return {"phase": current_phase(),
            "sample": {k: v for k, v in s.items() if k != "mono"},
            "fresh": fresh_sample() is not None,
            "watermarks": watermarks(),
            "attribution": attribution(top=max(top, 8)),
            "events": _recent_lifecycle_events()}


def reset():
    """Drop every sample, watermark, injected source, tracked trainer
    and override — test isolation."""
    global _enabled, _RING
    with _LOCK:
        _RING = None        # re-sized from MXNET_MEMWATCH_RING on the
        _WATERMARKS.clear()  # next sample
        _LAST["sample"] = None
        _SOURCES.clear()
        _TRAINERS.clear()   # a cycle-held trainer from a previous
        # test would otherwise keep contributing placement rows to
        # the attribution join until the gc happens to run
    _PHASE[0] = "steady"
    _SAMPLER[0] = None
    _enabled = None
