"""Executable-level persistent compile cache.

Built for an earlier setup on which JAX's persistent compilation cache
did not engage, so every fresh process paid the full XLA+Mosaic compile
for each fused train-step executable.  On this installation JAX's own
cache works (`compile_cache.py`, chip_smoke.py's cold/warm pair);
whether this module stays is ROADMAP D5.  The reference's answer to
repeated-compile cost is cuDNN's autotune cache; this is one level up:
the COMPILED PJRT executable itself, serialized to disk.

Mechanism (`aot_jit`): wrap a pure function like `jax.jit` does.  On
each new input signature, `lower()` (trace + StableHLO only — seconds,
no backend compile), hash the StableHLO text together with the jax
version and device kind, and either `deserialize_and_load` a stored
executable (sub-second) or `compile()` + `serialize()` + store.  The
pickle-resistant vjp/Partial out-trees are NOT pickled — they are
rebuilt locally from `lowered.out_info`, which is why this works where
pickling a `(blob, in_tree, out_tree)` triple fails (jaxpr debug info
holds unpicklable Traceback objects).

Enabled when `MXNET_AOT_CACHE_DIR` is set (nothing sets it by default);
without it `aot_jit` IS `jax.jit` — zero overhead, zero behavior change.
Donation/aliasing is baked into the lowering, so donated-buffer
semantics survive the round trip (exercised on the real chip by the
bench BERT config).
"""
from __future__ import annotations

import hashlib
import os

import jax

from . import config as _cfg
from .monitor import events
from .telemetry import costs as _costs
from .telemetry import flightrec as _bb
from .telemetry import spans as _tele

__all__ = ["aot_jit", "cache_dir", "trim_cache"]

# Disk-load circuit breaker (ISSUE 14 satellite).  The BENCH_serve
# smoking gun (aot.stale: 7 = aot.miss: 7, every reason
# deserialize_error) is a backend whose deserialize path fails
# DETERMINISTICALLY — each executable then pays a doomed read+
# deserialize before recompiling, every run, and the warm path never
# engages.  Two defenses, both per-process:
#   1. `_LOAD_BREAKER_FAILS` consecutive deserialize_error stales trip
#      the breaker: remaining executables skip the load attempt
#      entirely (aot.load_skipped) — one classified verdict
#      (aot.load_disabled + ring event + warning) instead of N failed
#      loads.  Any successful load resets the streak.
#   2. After the FIRST store, the just-written blob is read back and
#      deserialized once (self-verify): a backend that cannot load its
#      own serializations is caught in the run that WROTE the cache,
#      not discovered as a stale storm in the next one.
_LOAD_FAILS = [0]           # consecutive deserialize_error count
_LOAD_FAIL_DIR = [None]     # cache dir the streak was observed in —
                            # a dir change (tests point at fresh tmp
                            # dirs) is a different cache, not more
                            # evidence against this backend
_LOADS_DISABLED = [None]    # reason string once tripped
_SELF_VERIFIED = [False]    # one post-store verify per process
_LOAD_BREAKER_FAILS = 2


def _disable_loads(reason, detail=""):
    if _LOADS_DISABLED[0] is not None:
        return
    _LOADS_DISABLED[0] = str(reason)
    events.incr("aot.load_disabled")
    _bb.record("aot", "load_disabled", reason=str(reason),
               detail=str(detail)[:200])
    import warnings
    warnings.warn(
        "aot_cache: disk-load path disabled for this process (%s%s) "
        "— executables still compile and re-serialize, but "
        "deserialization on this backend fails deterministically; "
        "loads will be skipped instead of failing one by one"
        % (reason, (": " + str(detail)[:120]) if detail else ""))


def cache_dir():
    return _cfg.get("MXNET_AOT_CACHE_DIR") or ""


def trim_cache(max_entries=None):
    """Keep-K LRU over the on-disk blobs: evict oldest-mtime `.pjrtx`
    entries beyond `max_entries` (default `MXNET_AOT_CACHE_MAX`; 0 =
    unbounded).  Cache hits refresh mtime, so recently-served
    executables survive — the bound long-lived serving hosts need
    (every new model/bucket/shape otherwise grows the dir forever).
    Blobs listed in the pre-warm manifest (ISSUE 18) are the declared
    cross-process working set: they evict LAST — every unlisted blob
    goes first, and a manifest replay refreshes their mtimes (hit
    semantics), so pre-warmed executables survive churn from one-off
    signatures.  Best-effort and race-tolerant (concurrent processes
    may evict the same entry); returns the number of entries removed."""
    if max_entries is None:
        max_entries = int(_cfg.get("MXNET_AOT_CACHE_MAX"))
    d = cache_dir()
    if not d or max_entries <= 0:
        return 0
    protected = set()
    try:
        from .compile import prewarm as _pw
        protected = _pw.listed_blobs(d)
    except Exception:           # noqa: BLE001 — the manifest is
        pass                    # forensic garnish, never a blocker
    try:
        entries = []
        for name in os.listdir(d):
            if not name.endswith(".pjrtx"):
                continue
            try:
                entries.append((name in protected,
                                os.path.getmtime(os.path.join(d, name)),
                                name))
            except OSError:
                continue        # concurrently evicted/renamed
        entries.sort()          # unlisted first, then oldest mtime
        removed = 0
        for _, _, name in entries[:max(0, len(entries) - max_entries)]:
            try:
                os.remove(os.path.join(d, name))
                removed += 1
            except OSError:
                pass
        return removed
    except OSError:
        return 0


def _note_prewarm(label, kind, path):
    """File this (label, blob) pair in the pre-warm manifest (ISSUE
    18) after a successful compile-or-load — the cross-process memory
    `compile/prewarm.replay()` and a fresh serving warmup read."""
    try:
        from .compile import prewarm as _pw
        _pw.note(label, os.path.basename(path), exe_kind=kind)
    except Exception:               # noqa: BLE001 — best-effort
        pass


def _stale_reason(exc) -> str:
    """Classify WHY a cached executable blob failed to load (ISSUE 11
    satellite): `aot.stale` alone says a recompile happened, not what
    to fix — BENCH_serve's `aot.stale: 7, aot.miss: 7` smoking gun was
    undiagnosable.  Four buckets, matched on the failure text:

    - ``version``            — executable format / runtime build
      rotation ("cached executable is ... format vX, this build is
      vY"); fix = let the cache re-fill, or pin the runtime.
    - ``backend_mismatch``   — blob compiled for a different platform /
      device kind / topology than it is being loaded onto; fix = the
      cache key (or the deployment) is mixing backends.
    - ``key_mismatch``       — in/out tree or signature mismatch
      between the blob and this call; fix = the lowering changed under
      the same key.
    - ``deserialize_error``  — anything else (truncated/corrupt blob,
      read error).
    """
    msg = ("%s: %s" % (type(exc).__name__, exc)).lower()
    if "version" in msg or "format v" in msg:
        return "version"
    if any(w in msg for w in ("platform", "backend", "device",
                              "topology", "shard")):
        return "backend_mismatch"
    if any(w in msg for w in ("tree", "structure", "signature",
                              "argument", "unflatten")):
        return "key_mismatch"
    return "deserialize_error"


def _key_for(lowered, dev):
    # dev is the device the executable is compiled for and pinned to
    # (_args_device) — NOT jax.devices()[0], which can be a different
    # kind/platform in a heterogeneous process (stale-key risk)
    raw = "|".join([
        lowered.as_text(),
        jax.__version__,
        getattr(dev, "device_kind", ""),
        dev.platform,
        # executable format is runtime-build-locked — the version in
        # the key turns a runtime rotation into clean misses
        str(getattr(getattr(dev, "client", None), "platform_version",
                    "")),
        # device topology: an executable built on a 1-device process
        # fails shard-count checks when loaded under a virtual 8-device
        # mesh (same platform/kind, different assignment)
        str(jax.device_count()),
        str(jax.process_count()),
    ])
    return hashlib.sha256(raw.encode()).hexdigest()


class _AotJitted:
    """Callable with jax.jit semantics + executable disk persistence.
    One compiled executable per input aval signature."""

    def __init__(self, fn, donate_argnums=(), label=None, kind="aot",
                 expect_donated=None, role=None):
        self._label = label or getattr(fn, "__name__", "fn")
        # the same executable name as MeteredJit gives without a cache
        # directory: jit__traced_<label or role>
        self._jit = jax.jit(_costs.traced_as(fn, self._label, role),
                            donate_argnums=donate_argnums)
        self._compiled = {}
        self._kind = kind
        self._cost_keys = {}        # sig -> costs registry row key
        # donation audit (ISSUE 10 satellite): same warn-once contract
        # as MeteredJit — an AOT-cached step that stopped donating its
        # state should say so by name
        _costs._audit_donation(self._label, donate_argnums,
                               expect_donated)

    def _sig(self, args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        # device is part of the signature: the loaded executable is
        # pinned to the argument device, so same-shaped calls on a
        # different device must resolve their own executable (jax.jit
        # keys on placement the same way)
        dev = self._args_device(args)
        # weak_type is part of the signature: jax.jit recompiles on a
        # weak-type-only difference (python-scalar promotion vs a
        # committed array), so sharing one executable across it would
        # let dtype promotion diverge from the fallback path
        return (treedef, getattr(dev, "id", 0),
                tuple((tuple(getattr(a, "shape", ())),
                       str(getattr(a, "dtype", type(a))),
                       bool(getattr(a, "weak_type", False)))
                      for a in leaves))

    @staticmethod
    def _args_device(args):
        """The device the program will execute on (= first argument
        leaf's device; falls back to the default device)."""
        for leaf in jax.tree_util.tree_leaves(args):
            devs = getattr(leaf, "devices", None)
            if callable(devs):
                try:
                    return next(iter(devs()))
                except Exception:
                    pass
        return jax.devices()[0]

    @staticmethod
    def _deserialize(blob, in_tree, out_tree, dev):
        """deserialize_and_load, pinned to the ARGUMENT device — the
        loader's default binds the blob to EVERY visible device, which
        fails shard checks under a virtual multi-device mesh."""
        from jax.experimental.serialize_executable import (
            deserialize_and_load)
        return deserialize_and_load(blob, in_tree, out_tree,
                                    execution_devices=[dev])

    def _note_cost(self, sig, lowered, compiled, compile_s,
                   loaded=False):
        """File this executable's row in the cost registry (ISSUE 5):
        flops/bytes from cost_analysis, arg/out/donated bytes from
        memory_analysis — both None-tolerant."""
        try:
            self._cost_keys[sig] = _costs.note_executable(
                self._kind, "%s[%d]" % (self._label,
                                        len(self._cost_keys)),
                lowered=lowered, compiled=compiled,
                compile_s=compile_s, loaded=loaded)
        except Exception:           # noqa: BLE001 — attribution is
            pass                    # best-effort, never fatal

    def _get_compiled(self, args, sig=None):
        from jax.experimental.serialize_executable import serialize
        import jax.tree_util as tu
        import time as _t
        dbg = os.environ.get("MXNET_AOT_CACHE_DEBUG")
        t0 = _t.perf_counter()
        with _tele.span("aot.lower"):
            lowered = self._jit.lower(*args)
        t1 = _t.perf_counter()
        events.observe_time("aot.lower_us", t1 - t0)
        dev = self._args_device(args)
        # the execution device is part of the key: a blob loaded onto a
        # different device than it was compiled for fails at CALL time,
        # outside this method's fallback
        path = os.path.join(
            cache_dir(),
            _key_for(lowered, dev) + ".d%d.pjrtx" % getattr(dev, "id", 0))
        t2 = _t.perf_counter()
        if os.path.exists(path) and _LOADS_DISABLED[0] is not None:
            # breaker open: this backend's deserialize fails
            # deterministically — skip the doomed read+load instead of
            # adding another stale to the storm
            events.incr("aot.load_skipped")
            if dbg:
                print("[aot] LOAD-SKIP (%s) %s"
                      % (_LOADS_DISABLED[0], os.path.basename(path)))
        elif os.path.exists(path):
            try:
                with _tele.span("aot.load"):
                    with open(path, "rb") as f:
                        blob = f.read()
                    in_tree = tu.tree_structure((tuple(args), {}))
                    out_tree = tu.tree_structure(lowered.out_info)
                    # single-device programs only (plain jit)
                    out = self._deserialize(blob, in_tree, out_tree,
                                            dev)
                try:            # LRU: a hit refreshes eviction order
                    os.utime(path)
                except OSError:
                    pass
                _LOAD_FAILS[0] = 0      # a working load path resets
                events.incr("aot.hit")  # the breaker streak
                events.observe_time("aot.load_us",
                                    _t.perf_counter() - t2)
                self._note_cost(sig, lowered, out,
                                _t.perf_counter() - t2, loaded=True)
                _note_prewarm(self._label, self._kind, path)
                if dbg:
                    print("[aot] HIT lower=%.1fs key=%.1fs load=%.1fs"
                          % (t1 - t0, t2 - t1, _t.perf_counter() - t2))
                return out
            except Exception as stale_exc:  # noqa: BLE001
                # corrupt/stale blob: fall through to compile and
                # overwrite the entry — but say WHY, as a labeled
                # counter + ring event (the aggregate alone made
                # BENCH_serve's stale=miss=7 undiagnosable)
                reason = _stale_reason(stale_exc)
                events.incr("aot.stale")
                events.incr("aot.stale", labels={"reason": reason})
                _bb.record("aot", "stale", reason=reason,
                           label=self._label,
                           error=("%s: %s" % (
                               type(stale_exc).__name__,
                               stale_exc))[:160],
                           blob=os.path.basename(path))
                if reason == "deserialize_error":
                    # version/backend/key mismatches are honest one-off
                    # staleness; repeated DESERIALIZE failures against
                    # ONE cache dir are a broken load path — trip the
                    # breaker (a dir change restarts the evidence)
                    if _LOAD_FAIL_DIR[0] != cache_dir():
                        _LOAD_FAIL_DIR[0] = cache_dir()
                        _LOAD_FAILS[0] = 0
                    _LOAD_FAILS[0] += 1
                    if _LOAD_FAILS[0] >= _LOAD_BREAKER_FAILS:
                        _disable_loads(
                            "deserialize_error x%d" % _LOAD_FAILS[0],
                            detail="%s: %s" % (
                                type(stale_exc).__name__, stale_exc))
                if dbg:
                    print("[aot] STALE (%s) %s"
                          % (reason, os.path.basename(path)))
        t3 = _t.perf_counter()      # fresh stamp: a failed stale-blob
        with _tele.span("aot.compile"):  # load above must not inflate
            compiled = lowered.compile()  # the compile-cost tail
        events.incr("aot.miss")
        events.observe_time("aot.compile_us", _t.perf_counter() - t3)
        self._note_cost(sig, lowered, compiled,
                        _t.perf_counter() - t3)
        if dbg:
            print("[aot] MISS lower=%.1fs key=%.1fs compile=%.1fs"
                  % (t1 - t0, t2 - t1, _t.perf_counter() - t3))
        try:
            blob, _, _ = serialize(compiled)
            tmp = path + ".tmp.%d" % os.getpid()
            os.makedirs(cache_dir(), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)       # atomic: concurrent procs race safely
            _note_prewarm(self._label, self._kind, path)
            trim_cache()                # keep-K bound (MXNET_AOT_CACHE_MAX)
            if not _SELF_VERIFIED[0]:
                # one round trip per process: prove THIS backend can
                # load its own serializations in the run that writes
                # the cache, instead of discovering a stale storm on
                # the warm run (the deserialize_error:6 smoking gun)
                _SELF_VERIFIED[0] = True
                try:
                    in_tree = tu.tree_structure((tuple(args), {}))
                    out_tree = tu.tree_structure(lowered.out_info)
                    self._deserialize(blob, in_tree, out_tree, dev)
                    events.incr("aot.selfcheck_ok")
                except Exception as ver_exc:    # noqa: BLE001
                    events.incr("aot.selfcheck_failed")
                    _disable_loads("self_verify",
                                   detail="%s: %s" % (
                                       type(ver_exc).__name__,
                                       ver_exc))
        except Exception:
            pass                        # cache write is best-effort
        return compiled

    def __call__(self, *args):
        sig = self._sig(args)
        comp = self._compiled.get(sig)
        if comp is None:
            try:
                with _tele.phase("compile.call", self._label):
                    comp = self._get_compiled(args, sig)
            except Exception as e:      # any AOT failure → plain jit
                import warnings
                warnings.warn(
                    "aot_cache disabled for this executable (%s: %s) "
                    "— falling back to plain jit (full recompile per "
                    "process)" % (type(e).__name__, str(e)[:120]))
                comp = False
            self._compiled[sig] = comp
        if _bb.enabled():
            ck = self._cost_keys.get(sig)
            if ck is not None:
                _costs.invoke(ck)
        if comp is False:
            return self._jit(*args)
        return comp(*args)

    def lower(self, *args, **kw):       # passthrough for introspection
        return self._jit.lower(*args, **kw)


def aot_jit(fn, donate_argnums=(), label=None, kind="aot",
            expect_donated=None, role=None):
    """`jax.jit(fn, donate_argnums=...)` with executable persistence
    under `MXNET_AOT_CACHE_DIR` (no-op passthrough when unset).

    `label` additionally registers the executable in the cost registry
    (`telemetry.costs`) under `kind`/`label`: with the cache dir set,
    cost/memory analysis is extracted from the compiled executable
    already in hand; without it, the plain jit is wrapped in a
    `MeteredJit` (invocation counts + lazily-resolved cost analysis).
    Unlabeled calls keep the original zero-overhead contract.
    `expect_donated` arms the donation audit (warn once, by label,
    when a donatable argnum is not in `donate_argnums`).  A labeled
    executable is named `jit__traced_<slug>` in both modes, the slug
    made of `role` where the call site fixes one, else of `label`
    (`costs.traced_as`); its first call with a new signature leaves a
    `compile.call` row (ident = label) in the phase log."""
    if not cache_dir():
        if label is not None:
            return _costs.metered_jit(fn, donate_argnums=donate_argnums,
                                      kind=kind, label=label,
                                      expect_donated=expect_donated,
                                      role=role)
        _costs._audit_donation(label or getattr(fn, "__name__", "fn"),
                               donate_argnums, expect_donated)
        return jax.jit(fn, donate_argnums=donate_argnums)
    return _AotJitted(fn, donate_argnums=donate_argnums, label=label,
                      kind=kind, expect_donated=expect_donated, role=role)
