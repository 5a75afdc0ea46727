"""Per-executable FLOPs/HBM cost attribution (ISSUE 5 tentpole
part 2).

Telemetry so far says how long things took; nothing says what the
hardware was ASKED to do.  XLA exposes exactly that per executable —
`cost_analysis()` (flops, bytes accessed) and `memory_analysis()`
(argument/output/temp/alias bytes) — and the repo already touches the
surface per-op (ndarray.py:77) but never aggregates it.  This registry
is the aggregation point: every jitted executable the framework builds
(the fused imperative train step in gluon/block.py + optimizer.py,
ShardedTrainer/ResilientTrainer steps, the serving bucket executables)
registers one row per input signature, and every call bumps the row's
invocation count — so a blackbox dump or a `/metrics` scrape can say
"this run spent N invocations × M GFLOPs on `resilient.gstep`, and the
serving buckets held K bytes of HBM".

Two registration paths:

- `note_executable(...)` — for a caller that holds a `Lowered` and/or
  `Compiled` already: analysis (memory analysis included) is extracted
  eagerly.
- `metered_jit(fn, ...)` — how every labelled executable of the
  package is built (Gluon's CachedOp and fused train step, the trainer
  steps, the serving and generation engines).  New signatures are
  detected by a trace-time hook (a jit cache hit never runs the python
  body — the `train.traces` pattern), which captures the tracer avals
  and files a PENDING row; `table()`/`totals()` resolve pending rows
  by lowering against the stored avals — off the hot path, and
  (because jit shares its trace cache with `.lower()`) usually without
  re-tracing.  The steady-state call pays two int compares and one
  locked counter bump, never a pytree flatten.

Both guards: `cost_analysis()`/`memory_analysis()` returning None or
raising degrades to a row with the
walls and invocation counts but zeroed cost fields — never a crash.
The per-call hot path is gated on `flightrec.enabled()`:
MXNET_BLACKBOX=0 makes `MeteredJit.__call__` a bool read + the inner
jit call + one list read.

The device's time by model part (docs/observability.md, "Model parts"):
`part(name)` is the one scope the models and ops enter while they are
traced, and `op_parts(role)` reads a compiled executable's text for the
part of each of its instructions.  A device trace bears instruction
names and no scope, so the two are joined outside the program.
"""
from __future__ import annotations

import re
import threading
import time
import weakref

from . import spans as _spans

__all__ = ["note_executable", "note_collective", "invoke", "table",
           "totals", "snapshot", "reset", "metered_jit", "MeteredJit",
           "footprint_bytes", "suggest_bucket_mb", "lowerings",
           "PARTS", "part", "op_parts"]

_LOCK = threading.Lock()
_ROWS = {}                      # key -> dict row
_NEXT = [1]


def _cost_dict(obj):
    """`obj.cost_analysis()` as a plain dict — tolerant of None, a
    per-device list, a missing method, or a raising backend."""
    fn = getattr(obj, "cost_analysis", None)
    if fn is None:
        return {}
    try:
        c = fn()
    except Exception:               # noqa: BLE001 — a backend without
        return {}                   # cost analysis: attribution degrades
    if isinstance(c, (list, tuple)):
        c = c[0] if c else None
    return dict(c) if c else {}


def _mem_dict(compiled):
    """`compiled.memory_analysis()` fields as a plain dict (same
    tolerance as `_cost_dict`)."""
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return {}
    try:
        m = fn()
    except Exception:               # noqa: BLE001
        return {}
    if m is None:
        return {}
    out = {}
    for field, key in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "donated_bytes"),
                       ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(m, field, None)
        if v is not None:
            try:
                out[key] = int(v)
            except (TypeError, ValueError):
                pass
    return out


def _apply_analysis(row, cost, mem):
    c = _cost_dict(cost) if cost is not None else {}
    row["flops"] = float(c.get("flops", 0.0) or 0.0)
    row["bytes_accessed"] = float(c.get("bytes accessed", 0.0) or 0.0)
    row["analyzed"] = bool(c)
    if mem is not None:
        row.update(_mem_dict(mem))


def note_executable(kind, label, lowered=None, compiled=None,
                    compile_s=None, loaded=False, nsig=None):
    """Register one executable's cost row (eager path — analysis
    objects are in hand).  Prefers `compiled` for cost/memory analysis,
    falls back to `lowered` for cost (a deserialized executable may not
    re-expose cost_analysis).  Returns the row key for `invoke()`."""
    row = {"kind": str(kind), "label": str(label),
           "flops": 0.0, "bytes_accessed": 0.0,
           "compile_wall_s": float(compile_s) if compile_s else 0.0,
           "loaded": bool(loaded), "invocations": 0,
           "analyzed": False, "pending": None}
    c = _cost_dict(compiled)
    # prefer the compiled executable's analysis; a deserialized blob
    # may not re-expose it, so fall back to the lowering's (one
    # cost_analysis pass either way)
    if c:
        row["flops"] = float(c.get("flops", 0.0) or 0.0)
        row["bytes_accessed"] = float(c.get("bytes accessed", 0.0)
                                      or 0.0)
        row["analyzed"] = True
        row.update(_mem_dict(compiled))
    else:
        _apply_analysis(row, lowered, compiled)
    if nsig:
        row["sig"] = str(nsig)
    with _LOCK:
        key = _NEXT[0]
        _NEXT[0] += 1
        _ROWS[key] = row
    return key


def note_collective(label, op, wire_bytes, n_shards, dtype="float32"):
    """Register one bucket-collective's cost row (ISSUE 10 satellite):
    the ZeRO-2/3 reduce-scatter / all-gather buckets are not separate
    executables (they live inside the fused train step), so XLA's
    per-executable analysis cannot attribute their bytes-on-wire per
    bucket.  This row carries the bucket's wire bytes explicitly
    (``bytes_accessed`` = bytes each shard contributes to the ring),
    kind="collective", so teletop and the bench JSON can rank buckets
    the same way they rank executables.  ``invoke(key)`` per step keeps
    cumulative wire totals honest.  Returns the row key."""
    row = {"kind": "collective", "label": str(label),
           "flops": 0.0, "bytes_accessed": float(wire_bytes),
           "compile_wall_s": 0.0, "loaded": False, "invocations": 0,
           "analyzed": True, "pending": None,
           "sig": "%s[%d shards, %s]" % (op, int(n_shards), dtype)}
    with _LOCK:
        key = _NEXT[0]
        _NEXT[0] += 1
        _ROWS[key] = row
    return key


_HEURISTIC_WARNED = set()


def suggest_bucket_mb(param_bytes, n_shards, label_prefix=None,
                      default_mb=4.0, deciding=False):
    """Bucket-size cap steering (ISSUE 10 tentpole b): pick the
    MXNET_ZERO_BUCKET_MB default from measured per-executable bytes.

    When a train-step row for ``label_prefix`` already exists with a
    resolved bytes-accessed figure (a previous build of this trainer —
    e.g. the elastic rebuild path, where the registry has watched the
    step run), the cap targets ~1/32 of the executable's measured
    per-step traffic: enough buckets to interleave with backward,
    each well under the backend's large-collective cliff.  Without a
    row, the same 1/32 rule applies to the param bytes themselves.
    Clamped to [1, 16] MB; an explicit MXNET_ZERO_BUCKET_MB (> 0)
    always wins at the call site.

    ISSUE 18 deprecation shim: the compile autotuner
    (compile/autotune.py) is the default steering now, and this
    one-shot heuristic survives as its COLD-HISTORY fallback.
    ``deciding=True`` is the autotuner saying "no measured evidence
    existed — this heuristic's answer is the deciding input": that
    warns once per label (so tuned-vs-heuristic provenance is visible
    in the blackbox via the `autotune/heuristic_fallback` ring event)
    without penalizing advisory callers."""
    if deciding:
        key = str(label_prefix or "<unlabeled>")
        if key not in _HEURISTIC_WARNED:
            _HEURISTIC_WARNED.add(key)
            from . import flightrec as _bb
            _bb.record("autotune", "heuristic_fallback", label=key)
            import warnings
            warnings.warn(
                "costs.suggest_bucket_mb is the DECIDING input for "
                "executable %r: the autotune history holds no measured "
                "probe/cost rows for it yet — the one-shot heuristic "
                "steers this build; run with MXNET_HISTORY_DIR set so "
                "the next run tunes from measurements" % key)
    basis = float(param_bytes)
    if label_prefix:
        with _LOCK:
            rows = [dict(r) for r in _ROWS.values()]
        for r in rows:
            if _in_family(r, label_prefix) \
                    and r.get("bytes_accessed", 0) > 0 \
                    and r.get("pending") is None:
                basis = max(basis, float(r["bytes_accessed"]))
                break
    if basis <= 0:
        return float(default_mb)
    return float(min(16.0, max(1.0, basis / 32.0 / 1e6)))


def _note_pending(kind, label, resolver, compile_s=None):
    """Register a row whose analysis is resolved lazily by `resolver()`
    (returns a Lowered, or None) at table/totals time."""
    row = {"kind": str(kind), "label": str(label),
           "flops": 0.0, "bytes_accessed": 0.0,
           "compile_wall_s": float(compile_s) if compile_s else 0.0,
           "loaded": False, "invocations": 0,
           "analyzed": False, "pending": resolver, "lower": resolver}
    with _LOCK:
        key = _NEXT[0]
        _NEXT[0] += 1
        _ROWS[key] = row
    return key


def invoke(key, n=1):
    """Bump a row's cumulative invocation count (one lock; the per-step
    cost of attribution)."""
    with _LOCK:
        row = _ROWS.get(key)
        if row is not None:
            row["invocations"] += int(n)


def set_compile_wall(key, seconds):
    with _LOCK:
        row = _ROWS.get(key)
        if row is not None:
            row["compile_wall_s"] = float(seconds)


def _resolve(row):
    # pending swap under the lock: two concurrent table() callers (the
    # exporter worker and a crash dump) must not run one resolver twice
    with _LOCK:
        resolver, row["pending"] = row["pending"], None
    if resolver is None:
        return
    try:
        lowered = resolver()
    except Exception:               # noqa: BLE001 — resolution is
        lowered = None              # best-effort forensics
    if lowered is not None:
        _apply_analysis(row, lowered, None)


def table():
    """The cost table: one dict per registered executable, pending
    analyses resolved, sorted by cumulative FLOPs (flops × calls)
    descending."""
    with _LOCK:
        items = list(_ROWS.items())
    out = []
    for key, row in items:
        if row.get("pending") is not None:
            _resolve(row)
        r = {k: v for k, v in row.items()
             if k not in ("pending", "lower")}
        r["key"] = key
        r["cum_flops"] = r["flops"] * max(1, r["invocations"])
        r["cum_bytes"] = r["bytes_accessed"] * max(1, r["invocations"])
        out.append(r)
    out.sort(key=lambda r: r["cum_flops"], reverse=True)
    return out


def totals():
    """Aggregates for embedding in one JSON line (bench.py): executable
    and invocation counts, total/cumulative flops + bytes accessed, and
    the HBM peak watermark (flightrec's `hbm_sample` high-water)."""
    rows = table()
    from . import flightrec as _bb
    peaks = _bb.hbm_peaks()
    return {"executables": len(rows),
            "invocations": sum(r["invocations"] for r in rows),
            "flops": sum(r["flops"] for r in rows),
            "bytes_accessed": sum(r["bytes_accessed"] for r in rows),
            "cum_flops": sum(r["cum_flops"] for r in rows),
            "cum_bytes": sum(r["cum_bytes"] for r in rows),
            "compile_wall_s": round(sum(r["compile_wall_s"]
                                        for r in rows), 3),
            "hbm_peak_bytes": max(peaks.values()) if peaks else 0}


def footprint_bytes(label_prefix, kind=None):
    """MEASURED per-device HBM footprint of one executable family
    (ISSUE 8 admission control): the max over registered rows of one
    label family (optionally filtered by `kind`) of argument + output
    + temp bytes from XLA's memory_analysis.  Buckets of one serving
    model share parameters, so the max row — the largest bucket — IS
    the family's working set.  Rows are labeled `<family>[<idx>]`
    (`MeteredJit` appends the signature ordinal), so the match is exact
    up to the '[' delimiter — plain startswith would let model
    'ranker' read model 'ranker2's footprint.  Returns 0 when no
    matching row carries memory fields (`MeteredJit` rows resolve
    cost_analysis only, ROADMAP R5; admission then falls back to
    projection)."""
    best = 0
    for r in table():
        if kind is not None and r.get("kind") != kind:
            continue
        if not _in_family(r, label_prefix):
            continue
        b = (r.get("argument_bytes", 0) + r.get("output_bytes", 0)
             + r.get("temp_bytes", 0))
        best = max(best, int(b))
    return best


def _in_family(row, label_prefix):
    """Rows are labeled `<family>[<idx>]`: exact up to the '[' — plain
    startswith would let 'ranker' match 'ranker2'."""
    label = str(row.get("label", ""))
    return label == label_prefix or label.startswith(label_prefix + "[")


def lowerings(label_prefix):
    """The `jax.stages.Lowered` of every live metered-jit executable of
    one label family (one per traced signature) — for an audit that
    must read the compiled program itself, e.g. chip_smoke.py asking
    whether the Mosaic kernels are really in the fused train step:
    ``lowered.compile().as_text()``.  The avals carry no sharding, so
    a mesh step is audited through `ShardedTrainer.lower_step`
    instead.  Rows filed eagerly (`note_executable`) keep no lowering
    and are skipped."""
    with _LOCK:
        resolvers = [r["lower"] for r in _ROWS.values()
                     if r.get("lower") and _in_family(r, label_prefix)]
    return [low for low in (fn() for fn in resolvers) if low is not None]


def drop_rows(label_prefix, kind=None):
    """Remove the registered rows of one label family (the
    `footprint_bytes` matching rule: exact, or `<prefix>[...]`).  The
    ModelRegistry drops a model's rows on unregister so a later
    re-registration under the same name cannot read the previous
    incarnation's footprint; stale `invoke()`s against dropped keys
    are no-ops.  Returns the number of rows removed."""
    with _LOCK:
        gone = [k for k, r in _ROWS.items()
                if (kind is None or r.get("kind") == kind)
                and _in_family(r, label_prefix)]
        for k in gone:
            del _ROWS[k]
    return len(gone)


def snapshot():
    """{"rows": table(), "totals": totals()} — the dump/export block."""
    return {"rows": table(), "totals": totals()}


def reset():
    with _LOCK:
        _ROWS.clear()
        _RETIRED.clear()


def traced_as(fn, label, role=None):
    """`fn` behind a function named `_traced_<slug>`, so that the
    executable `jax.jit` makes of it reads `jit__traced_<slug>` in HLO
    module names and profiler traces: an executable is found by what
    it is for.  The slug is `role` if the call site fixes one (the
    generation engine's do not follow the user's cost label), else the
    label with every run of other characters turned into `_`."""
    slug = re.sub(r"[^0-9A-Za-z]+", "_", str(role or label)).strip("_")

    def _traced(*a):
        return fn(*a)

    _traced.__name__ = _traced.__qualname__ = "_traced_" + slug[:40]
    return _traced


# -- the device's time by model part --------------------------------------

# the vocabulary of `part`; docs/observability.md draws each boundary
PARTS = ("embed", "head", "proj", "attn", "index", "state", "cache",
         "experts", "ffn", "window")

_PART_RE = re.compile(r"mx\.([a-z_]+)")
_INSTR_RE = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def part(name):
    """The scope of one model part: `jax.named_scope("mx." + name)`, for
    a `name` of `PARTS`.  Entered where the work is written, in the
    models and at the top of the ops' entry points, so it runs only
    while a function is traced: a compiled step never enters it.  It is
    metadata: the lowered program without debug info is the same with
    and without it.  The innermost scope names an op (`op_parts`)."""
    if name not in PARTS:
        raise ValueError("%r is no model part: %s" % (name, ", ".join(PARTS)))
    import jax
    return jax.named_scope("mx." + name)


_LIVE = weakref.WeakSet()       # every MeteredJit that is alive
_RETIRED = {}                   # traced name -> the programs of the
                                # newest dead executable built with a role


def _parse_parts(text):
    """{instruction name: part or None} of a compiled module's text: the
    innermost `mx.<part>` of the instruction's `op_name`.  An instruction
    whose metadata XLA dropped has none (on the v5e: the scatter fusions,
    `kind=kCustom`, whose roots lose it too)."""
    parts = {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            op = _OP_NAME_RE.search(line)
            scopes = _PART_RE.findall(op.group(1)) if op else ()
            parts[m.group(1)] = scopes[-1] if scopes else None
    return parts


def op_parts(role):
    """The program's map from compiled op to model part: one entry for
    every traced signature of every executable whose traced name
    (`jit__traced_gen_prefill`) contains `role`,

        {"name": traced name, "stale": bool,
         "instructions": {HLO instruction name: part or None}}

    over every computation of the compiled module (the ops of while and
    conditional bodies are events of their own in a device trace, under
    these names).  An instruction's part is the innermost `mx.<part>`
    scope of its `op_name` (of its root's, for a fusion); None outside
    every scope.

    Built when asked and kept after: the signature's traced program is
    lowered and compiled again (the persistent compile cache answers;
    the first call of a signature noted where its arguments lay, so the
    lowering is the call's own), and its text read once.  Nothing
    happens at warm-up or on a call, and the flight recorder need not
    be on.  It answers for the executables that are alive and for the
    newest dead one of each role-named executable
    (`metered_jit(role=)`), so a reader may ask once the engine or the
    trainer is shut and gone.

    `stale`: the text names no part at all.  That is an executable
    loaded from a compile cache filled before the program had scopes
    (the cache's key leaves metadata out,
    `jax_compilation_cache_include_metadata_in_key`), not a model
    without parts: take no share from it."""
    with _LOCK:
        groups = [(mj._name, mj._programs) for mj in list(_LIVE)]
        groups += list(_RETIRED.items())
    out = []
    for name, programs in groups:
        if role not in name:
            continue
        for program in programs:
            if program[1] is None:
                try:
                    text = program[0].lower().compile().as_text()
                except Exception as e:      # noqa: BLE001 — forensics
                    from . import flightrec as _bb
                    _bb.record("costs", "op_parts_failed", name=name,
                               error=repr(e)[:200])
                    continue
                instructions = _parse_parts(text)
                program[1] = {"name": name, "instructions": instructions,
                              "stale": not any(instructions.values())}
            out.append(program[1])
    return out


_DONATION_WARNED = set()


def _audit_donation(label, donate_argnums, expect_donated):
    """Donation audit (ISSUE 10 satellite): a trainer step that fails
    to donate its state doubles the persistent HBM bill and breaks the
    in-place-update contract silently.  ``expect_donated`` names the
    argnums the CALLER says hold donatable state; any of them missing
    from ``donate_argnums`` warns ONCE per executable label (the label
    is the thing an operator can grep the cost table / blackbox for)."""
    if not expect_donated:
        return
    missing = sorted(set(int(i) for i in expect_donated)
                     - set(int(i) for i in donate_argnums))
    if not missing or label in _DONATION_WARNED:
        return
    _DONATION_WARNED.add(label)
    import warnings
    warnings.warn(
        "executable %r: argument(s) %s hold donatable state but are "
        "not donated (donate_argnums=%s) — the update will copy "
        "instead of aliasing, doubling this state's memory footprint"
        % (label, missing, tuple(donate_argnums)))


class MeteredJit:
    """`jax.jit` + cost-row registration + invocation counting.

    Hot-path contract (the check_overhead.py gate): NO per-call
    signature computation.  New input signatures are detected by a
    trace-time side effect inside the wrapped function (the
    `train.traces` pattern — a jit cache hit never runs the python
    body): the tracer avals are captured THERE, at trace cost, and the
    steady-state call pays one bool read, two int compares and one
    locked counter bump.  Recorder off: one bool read, the inner jit,
    one list read.

    The first call of a signature also keeps its traced program
    (`jax.stages.Traced`: the jaxpr and where the call's arguments lay;
    neither the function nor an array), from which `op_parts` compiles
    the call's own lowering again.  An executable built with a `role`
    leaves these behind when it dies."""

    def __init__(self, fn, donate_argnums=(), kind="jit", label=None,
                 expect_donated=None, role=None):
        import jax
        self._kind = kind
        self._label = label or getattr(fn, "__name__", "fn")
        _audit_donation(self._label, donate_argnums, expect_donated)
        self._role = role
        self._keys = []             # registry row key per traced sig
        self._pending = []          # avals captured at trace time
        self._programs = []         # [Traced, its op_parts entry or None]
        # suppresses the hook during lazy cost resolution (its lower()
        # may re-trace).  THREAD-local: a resolver running on the
        # exporter thread must not swallow a genuinely new signature
        # the training thread traces concurrently
        self._tls = threading.local()

        def hooked(*a):
            # trace-time only: a jit cache hit never runs this.  The
            # avals are filed once `fn` has traced: a trace that raises
            # leaves nothing for the next call to register
            out = fn(*a)
            if not getattr(self._tls, "resolving", False):
                self._pending.append(jax.tree_util.tree_map(
                    lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype),
                    a))
            return out

        traced = traced_as(hooked, self._label, role)
        self._name = "jit_" + traced.__name__
        self._jit = jax.jit(traced, donate_argnums=donate_argnums)
        _LIVE.add(self)

    def __del__(self):
        # the newest dead executable of a role still answers `op_parts`
        # (a dict store: no work for the collector's thread)
        try:
            if self._role and self._programs:
                _RETIRED[self._name] = self._programs
        except AttributeError:          # __init__ did not finish
            pass

    def _trace(self, avals, args):
        """The signature `avals` as the call `args` traced it: each
        argument's aval with the sharding it was committed to and its
        weak type, so that the lowering is the call's own, letter for
        letter, and the compile cache knows it.  Where `args` are
        another thread's (two traced at once), the plain avals."""
        import jax

        def placed(s, x):
            if tuple(getattr(x, "shape", ())) != tuple(s.shape):
                raise ValueError("another call's arguments")
            return jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=x.sharding if getattr(x, "committed", False)
                else None,
                weak_type=bool(getattr(
                    x, "weak_type",
                    isinstance(x, (bool, int, float, complex)))))

        try:
            avals = jax.tree_util.tree_map(placed, avals, args)
        except (ValueError, TypeError):
            pass
        self._tls.resolving = True
        try:
            return self._jit.trace(*avals)
        finally:
            self._tls.resolving = False

    def _register_pending(self, args, wall_s):
        """Turn trace-time aval captures into kept programs and, with
        the recorder on (`wall_s`), pending cost rows (the
        lowering/analysis happens at table()/dump time — jit shares
        its trace cache with .lower(), so resolution usually re-traces
        nothing).  `wall_s` (this call's wall, which included the
        trace+compile) is the compile-wall proxy."""
        jref = weakref.ref(self._jit)
        me = weakref.ref(self)
        while self._pending:
            avals = self._pending.pop(0)
            try:
                self._programs.append([self._trace(avals, args), None])
            except Exception:           # noqa: BLE001 — `op_parts`
                pass                    # then lacks this signature
            if wall_s is None:
                continue

            def resolver(avals=avals):
                j, s = jref(), me()
                if j is None:
                    return None
                if s is not None:
                    s._tls.resolving = True
                try:
                    return j.lower(*avals)
                finally:
                    if s is not None:
                        s._tls.resolving = False

            key = _note_pending(
                self._kind, "%s[%d]" % (self._label, len(self._keys)),
                resolver, compile_s=wall_s)
            self._keys.append(key)

    def __call__(self, *args):
        from . import flightrec as _bb
        if not _bb.enabled():
            out = self._jit(*args)
            if self._pending:           # this call traced a signature
                self._register_pending(args, None)
            return out
        t0 = time.monotonic()
        out = self._jit(*args)
        if self._pending:
            # this call traced a new signature: register it, with the
            # call's wall (≈ trace + compile + one execution) as the
            # honest compile-wall proxy, and say in the phase log
            # which step it was that recompiled
            t1 = time.monotonic()
            _spans.phase_at("compile.call", t0, t1, self._label)
            self._register_pending(args, t1 - t0)
        if self._keys:
            # cache-hit calls attribute to the newest row — knowing the
            # exact signature would cost a per-call pytree flatten,
            # which is precisely what the overhead gate forbids; totals
            # stay exact, per-row splits are approximate under
            # alternating shapes
            invoke(self._keys[-1])
        return out

    def lower(self, *args, **kw):       # introspection passthrough
        return self._jit.lower(*args, **kw)


def metered_jit(fn, donate_argnums=(), kind="jit", label=None,
                expect_donated=None, role=None):
    """`jax.jit(fn, donate_argnums=...)` with a cost-registry row per
    input signature and cumulative invocation counts.
    ``expect_donated`` arms the donation audit: argnums named there but
    absent from ``donate_argnums`` warn once with the executable
    label.  The executable is named `jit__traced_<label or role>`
    (`traced_as`)."""
    return MeteredJit(fn, donate_argnums=donate_argnums, kind=kind,
                      label=label, expect_donated=expect_donated,
                      role=role)
