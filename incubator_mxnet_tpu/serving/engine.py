"""Inference serving engine: shape-bucketed dynamic batching over
pre-compiled executables (ISSUE 3 tentpole).

The ROADMAP north star is "heavy traffic from millions of users", and
the serving-side analogue of the training recompilation problem is the
RECOMPILATION CLIFF: eager `block(x)` compiles one executable per input
batch size, so organic traffic (every batch size from 1 to N) triggers
a fresh trace+compile — seconds of tail latency per new shape (the
hazard TVM arxiv 1802.04799 and the XLA fusion analysis arxiv 2301.13062 both
center on).  The engine closes the executable set instead:

1. **Shape buckets.**  Requests are coalesced by a background
   dispatcher into power-of-two batch buckets (`MXNET_SERVE_BUCKETS`,
   default 1,2,4,...,`MXNET_SERVE_MAX_BATCH`) and padded up to the
   bucket size, so the set of compiled executables is CLOSED and
   known in advance.
2. **Warm-up.**  `warmup()` compiles every (device, bucket)
   executable before traffic (`telemetry.costs.metered_jit`); where
   `compile_cache.enable()` has turned JAX's persistent compilation
   cache on, a restarted serving host loads them from it.
   `serve.traces` counts executable traces; it stays FLAT
   after warmup under mixed-size traffic — the zero-recompile
   contract `bench.py serve` asserts.
3. **Concurrency.**  Callers `submit()` single examples (or
   `submit_batch()` small batches) and get `concurrent.futures`
   futures; a dispatcher thread coalesces, and with multiple replica
   devices each device gets its own single-thread worker so buckets
   execute concurrently across replicas (in-flight bounded at the
   replica count).  The request queue
   is BOUNDED (`MXNET_SERVE_QUEUE_CAP`): submits beyond it fail fast
   with `QueueFull` (backpressure, not unbounded memory).  Each
   request may carry a deadline; a request that expires waiting is
   resolved with `DeadlineExceeded` and never wastes device time.
4. **Robustness (PR 1 patterns).**  `drain()`/`close()` complete
   in-flight work and join the dispatcher within a timeout; every
   outstanding future is resolved.  `handle_sigterm=True` installs the
   flag-only preemption handler (resilience.py pattern): on SIGTERM
   the engine stops intake, finishes the queue, and retires.  Fault
   sites `serve.enqueue` / `serve.infer` (fault.py) inject rejection
   and transient executable failure; infer faults are retried on the
   standard `retry_transient` budget.
5. **Observability.**  `serve.*` counters on `monitor.events`
   (`queue_us`, `infer_us`, `e2e_us`, `batch_fill`, `pad_waste`,
   `rejected`, `batches`, `requests`, `traces`, ...) plus per-request
   latency samples for `events.percentiles("serve.e2e_us")` — tails,
   not means, are the serving SLO.
6. **Overload hardening (ISSUE 8).**  Requests carry a priority
   `lane` (`MXNET_SERVE_LANES`, highest first) and optionally a
   `tenant`.  The dispatcher drains lanes in strict priority order,
   earliest-deadline-first within one.  Under sustained overload the
   engine SHEDS instead of queueing toward uniform collapse: a lane
   past its quota share of the queue (`MXNET_SERVE_LANE_QUOTAS`), a
   tenant past `MXNET_SERVE_TENANT_QUOTA`, or a request whose
   deadline is already unmeetable gets the typed `Shed` /
   `DeadlineExceeded` error synchronously (`serve.shed`, labeled by
   lane/tenant/reason), and over-deadline work found at dispatch time
   is reaped without device time.  `serve.e2e_us`/`serve.requests`
   additionally split by lane and tenant through the labeled
   percentile rings (`events.labeled_latency_snapshot("serve.")`),
   so /metrics and black-box dumps answer WHOSE p99 blew out.

Multi-device replica dispatch: pass `devices=[ctx, ...]` (or build via
`ShardedTrainer.serve()` / `parallel.mesh.replica_contexts`) and the
dispatcher round-robins buckets across per-device parameter replicas.
The round-robin is HEALTH-AWARE (the serving twin of the elastic
training mesh, ISSUE 7): `MXNET_SERVE_REPLICA_FAILS` consecutive
terminal dispatch failures on one replica mark it unhealthy
(`serve.replica_unhealthy` counter + a flight-recorder event naming
the device) and traffic routes around it; after
`MXNET_SERVE_REPLICA_COOLDOWN_S` ONE probe batch is routed back —
success re-admits it (`serve.replica_recovered`), failure restarts the
cooldown.  With every replica unhealthy the engine fails OPEN (soonest
cooldown first): degraded service beats refused service.

The uint8 wire contract matches PR 2's training path: with
`HybridBlock.set_input_transform(normalize_transform(...))` installed,
clients submit raw uint8 pixels, the engine ships them as-is (4x fewer
wire bytes) and the normalize+cast is traced INTO each bucket
executable.
"""
from __future__ import annotations

import heapq
import itertools
import queue
from collections import deque
import signal
import threading
import time
import weakref
from concurrent.futures import Future

import numpy as _np

from .. import config as _cfg
from .. import fault
from ..base import MXNetError
from ..context import Context, current_context
from ..monitor import events
from ..telemetry import costs as _costs
from ..telemetry import flightrec as _bb
from ..telemetry import reqtrace as _reqtrace
from ..telemetry import spans as _tele

__all__ = ["InferenceEngine", "QueueFull", "DeadlineExceeded",
           "EngineClosed", "Shed", "serve_counters"]


class QueueFull(MXNetError):
    """The bounded request queue is at capacity — backpressure: the
    caller should retry later or shed load upstream."""


class DeadlineExceeded(MXNetError):
    """The request's deadline expired before it reached the device."""


class EngineClosed(MXNetError):
    """submit() after drain()/close() (or during SIGTERM drain)."""


class Shed(MXNetError):
    """The request was refused by overload policy — its lane is over
    quota, its tenant is over quota, or its deadline was already
    unmeetable (ISSUE 8).  Unlike `QueueFull` (transient backpressure:
    retry soon), a shed means the engine is deliberately degrading
    low-priority intake to protect higher lanes — back off or
    re-submit on a higher lane."""


def serve_counters():
    """Snapshot of the `serve.*` counters (µs totals / counts)."""
    return events.snapshot("serve.")


class _Request:
    __slots__ = ("data", "n", "future", "t_enq", "deadline", "single",
                 "tele", "lane", "tenant", "rec")

    def __init__(self, data, n, future, deadline, single, lane=None,
                 tenant=None):
        self.data = data
        self.n = n
        self.future = future
        self.t_enq = time.monotonic()
        self.deadline = None if deadline is None \
            else self.t_enq + float(deadline)
        self.single = single
        self.lane = lane
        self.tenant = tenant
        # the submitter's span context (telemetry): the dispatcher's
        # serve.dispatch/serve.infer spans parent onto it, so a
        # request's submit→dispatch→infer chain shares one trace id
        # across the three threads it crosses
        self.tele = _tele.current()
        # lifecycle journal record (ISSUE 19): phase stamps land on it
        # as the request crosses queue→coalesce→dispatch→infer→join;
        # None when journaling is off (stamps guard on it)
        self.rec = None


class _OverQuota(Exception):
    """Internal: a put would push its lane past quota (the engine
    translates it into the public typed `Shed`)."""

    def __init__(self, lane, depth, cap):
        super().__init__(lane, depth, cap)
        self.lane, self.depth, self.cap = lane, depth, cap


class _LaneQueue:
    """Priority-lane request queue with `queue.Queue`'s accounting
    surface (the subset the engine uses: put_nowait/get/get_nowait/
    task_done/qsize/maxsize/unfinished_tasks/all_tasks_done), so the
    drain()/close() exactly-once contract carries over unchanged.

    SHARED INFRASTRUCTURE: `serving.generation.GenerationEngine`
    (ISSUE 14) admits decode-slot joins through this same queue (and
    `_parse_lanes`/`_parse_lane_quotas`/`_OverQuota`) — one admission
    policy, one set of typed errors, two engines.  Changes here have
    two consumers.

    Ordering (ISSUE 8): strict priority ACROSS lanes (the dispatcher
    never serves a lower lane while a higher one has work) and
    earliest-deadline-first WITHIN a lane (no-deadline requests keep
    FIFO order after every deadlined one — a request that asked for a
    latency bound outranks one that didn't).  Each lane may carry an
    occupancy cap (its quota share of `maxsize`): a put beyond it
    raises `_OverQuota` so over-quota low-priority work is SHED at
    submit time instead of queueing the whole engine toward uniform
    deadline collapse."""

    def __init__(self, maxsize, lanes, lane_caps):
        self.maxsize = int(maxsize)
        self._lanes = tuple(lanes)
        self._caps = dict(lane_caps)        # lane -> cap (None = none)
        self._heaps = {ln: [] for ln in self._lanes}
        self._seq = itertools.count()       # FIFO tiebreak within EDF
        self._mutex = threading.Lock()
        self._not_empty = threading.Condition(self._mutex)
        self.all_tasks_done = threading.Condition(self._mutex)
        self.unfinished_tasks = 0
        self._size = 0

    def put_nowait(self, req):
        with self._mutex:
            # lane quota BEFORE global fullness: the engine's
            # displacement path relies on queue.Full implying the
            # request's own lane still has quota headroom (so the
            # post-eviction re-put cannot fail)
            h = self._heaps[req.lane]
            cap = self._caps.get(req.lane)
            if cap is not None and len(h) >= cap:
                raise _OverQuota(req.lane, len(h), cap)
            if self._size >= self.maxsize:
                raise queue.Full
            key = (req.deadline if req.deadline is not None
                   else float("inf"), next(self._seq))
            heapq.heappush(h, (key, req))
            self._size += 1
            self.unfinished_tasks += 1
            self._not_empty.notify()

    def _pop_locked(self):
        for lane in self._lanes:            # highest priority first
            h = self._heaps[lane]
            if h:
                _, req = heapq.heappop(h)
                self._size -= 1
                return req
        raise queue.Empty

    def get_nowait(self):
        with self._mutex:
            return self._pop_locked()

    def evict_lowest(self, below):
        """Remove and return the LAST-to-run request (latest deadline,
        newest arrival) of the lowest-priority non-empty lane strictly
        below `below`, or None when every lower lane is empty.  The
        engine uses this to DISPLACE low work when a higher-lane
        submit meets a full queue — without it, lower-lane backlog
        could hold every slot and the top lane would see QueueFull
        under exactly the overload the lanes exist for.  The victim
        stays counted in unfinished_tasks: the caller sheds it through
        the normal resolve path (task_done fires there)."""
        try:
            start = self._lanes.index(below) + 1
        except ValueError:
            return None
        with self._mutex:
            for lane in reversed(self._lanes[start:]):
                h = self._heaps[lane]
                if h:
                    item = max(h)       # latest deadline, newest seq
                    h.remove(item)
                    heapq.heapify(h)
                    self._size -= 1
                    return item[1]
        return None

    def get(self, timeout=None):
        # single-consumer contract (the dispatcher): one wait then one
        # pop attempt; a timeout/spurious wakeup surfaces queue.Empty,
        # which every call site already loops on
        with self._not_empty:
            if not self._size:
                self._not_empty.wait(timeout)
            return self._pop_locked()

    def task_done(self):
        with self.all_tasks_done:
            n = self.unfinished_tasks - 1
            if n < 0:
                raise ValueError("task_done() called too many times")
            self.unfinished_tasks = n
            if n == 0:
                self.all_tasks_done.notify_all()

    def qsize(self):
        with self._mutex:
            return self._size

    def lane_depths(self):
        with self._mutex:
            return {ln: len(h) for ln, h in self._heaps.items()}


def _parse_lanes(spec):
    if spec and isinstance(spec, (list, tuple)):
        names = [str(s).strip() for s in spec if str(s).strip()]
    else:
        names = [s.strip() for s in str(spec or "").split(",")
                 if s.strip()]
    out = []
    for n in names:                         # dedupe, order-preserving
        if n not in out:
            out.append(n)
    if not out:
        raise ValueError("serve lanes spec is empty: %r" % (spec,))
    return tuple(out)


def _parse_lane_quotas(spec, lanes, cap):
    """lane -> occupancy cap (requests) from the quota-fraction spec;
    the top lane defaults to the full queue (None = no lane cap), and
    an explicit fraction >= 1 also means no extra bound.  Fraction
    parsing (incl. the auto ladder) is shared with the SLO layer's
    default shed budgets — config.serve_lane_quota_fractions — so
    what the engine enforces and what the alerts budget cannot
    drift."""
    fracs = _cfg.serve_lane_quota_fractions(spec, len(lanes))
    caps = {}
    for lane, f in zip(lanes, fracs):
        caps[lane] = None if f >= 1.0 else max(1, int(f * cap))
    return caps


def _parse_buckets(spec, max_batch):
    if spec and isinstance(spec, (list, tuple, set, frozenset)):
        bs = sorted({int(s) for s in spec})
    elif spec:
        bs = sorted({int(s) for s in str(spec).split(",") if s.strip()})
    else:
        bs, b = [], 1
        while b < max_batch:
            bs.append(b)
            b *= 2
        bs.append(int(max_batch))
        bs = sorted(set(bs))
    if not bs or bs[0] < 1:
        raise ValueError("serve buckets must be positive ints, got %r"
                         % (spec,))
    return tuple(bs)


class InferenceEngine:
    """Concurrent inference over a Block with bucketed dynamic batching.

    block: a (Hybrid)Block with initialized parameters.  Its
        `set_input_transform` (if any) is traced into every bucket
        executable — the uint8-on-wire path.
    ctx / devices: one Context, or a list for replica round-robin
        (default: the current context).
    buckets / max_batch / max_wait_us / queue_cap: see the
        MXNET_SERVE_* knobs in config.py (arguments override).
    example_shape / wire_dtype: per-example shape (no batch dim) and
        the dtype clients put on the wire; needed by `warmup()` before
        the first request has been seen.

    Lifecycle: construct → `warmup()` → submit traffic → `drain()` /
    `close()`.  The dispatcher thread starts lazily on first submit.
    """

    def __init__(self, block, ctx=None, devices=None, buckets=None,
                 max_batch=None, max_wait_us=None, queue_cap=None,
                 example_shape=None, wire_dtype=None,
                 handle_sigterm=False, lanes=None, lane_quotas=None,
                 tenant_quota=None, cost_label=None, version=None):
        from ..parallel.functional import functionalize
        if devices is None:
            devices = [ctx or current_context()]
        elif ctx is not None:
            raise ValueError("pass ctx= or devices=, not both")
        if not devices:
            raise ValueError("need at least one serving device")
        self._block = block
        self._ctxs = [d if isinstance(d, Context) else Context(*d)
                      for d in devices]
        max_batch = int(max_batch if max_batch is not None
                        else _cfg.get("MXNET_SERVE_MAX_BATCH"))
        self._buckets = _parse_buckets(
            buckets if buckets is not None
            else _cfg.get("MXNET_SERVE_BUCKETS"), max_batch)
        self._max_wait = (int(max_wait_us if max_wait_us is not None
                              else _cfg.get("MXNET_SERVE_MAX_WAIT_US"))
                          / 1e6)
        cap = max(1, int(queue_cap if queue_cap is not None
                         else _cfg.get("MXNET_SERVE_QUEUE_CAP")))
        # priority lanes (ISSUE 8): strict priority across, EDF within;
        # submits default to the TOP lane so single-lane callers keep
        # the pre-lane behavior (quota 1.0 on the top lane = the plain
        # bounded queue)
        self._lanes = _parse_lanes(
            lanes if lanes is not None else _cfg.get("MXNET_SERVE_LANES"))
        self._lane_caps = _parse_lane_quotas(
            lane_quotas if lane_quotas is not None
            else _cfg.get("MXNET_SERVE_LANE_QUOTAS"),
            self._lanes, cap)
        self._q = _LaneQueue(cap, self._lanes, self._lane_caps)
        self._tenant_quota = int(
            tenant_quota if tenant_quota is not None
            else _cfg.get("MXNET_SERVE_TENANT_QUOTA"))
        self._tenant_q = {}         # tenant -> currently-queued count
        self._cost_label = str(cost_label or "serve.infer")
        # version tag (ISSUE 16): labels the serve.requests/e2e_us/
        # shed splits so canary traffic is attributable; None = no
        # labeled children (single-version engines add no labelsets)
        self._version = str(version) if version is not None else None
        # per-request lifecycle journal (ISSUE 19): bounded ring +
        # tail-exemplar promotion; the model tag is the cost label's
        # model part (serve.infer:<model>) so exemplars join the cost
        # registry's attribution
        self._journal = _reqtrace.journal(
            "serve",
            self._cost_label.split(":", 1)[1]
            if ":" in self._cost_label else self._cost_label,
            version=self._version)
        # model.bad_version taint: >0 stalls every batch by this many
        # seconds and sign-flips outputs (deterministic degradation)
        self._degrade_s = 0.0
        self._example_shape = (tuple(example_shape)
                               if example_shape is not None else None)
        self._wire_dtype = (str(_np.dtype(wire_dtype))
                            if wire_dtype is not None else None)

        self._pure = functionalize(block, training=False)
        self._infer = self._make_infer()
        self._param_src = None      # block whose params serve (set by
                                    # refresh_params_from on promote)
        self._param_remap = None    # promoted-name -> serving-name
                                    # (auto-prefix drift)
        self._dev_params = None     # list of {name: jax.Array} per ctx
        try:
            self.refresh_params()
        except Exception:
            # deferred-shape params (model_zoo nets before a first
            # forward): resolved lazily from the first concrete batch
            # in _run (shape inference needs an input signature)
            self._dev_params = None

        self._lock = threading.Lock()       # submit/lifecycle state
        self._exec_lock = threading.Lock()  # trace/execute (warmup vs
                                            # dispatcher share the block)
        # RELATIVE deadlines recently observed per lane (bounded
        # rolling windows): the SLO targets (ISSUE 12) — what callers
        # actually asked of a lane is the honest p99 bound, not a
        # knob someone forgot to set.  A WINDOW, not an all-time min:
        # one misconfigured client's 1ms outlier must age out, not
        # poison the lane's derived p99 rule until process restart
        self._lane_deadline_s = {}  # lane -> deque of recent deadlines
        self._thread = None
        self._carry = None          # request pulled but not yet batched
        self._svc_ewma = {}         # bucket -> EWMA batch service s
                                    # (deadline feasibility at dispatch)
        self._rr = 0
        self._n_batches = 0
        self._dev_batches = [0] * len(self._ctxs)
        self._n_inflight = 0
        # replica health (round-robin routes around a failing device)
        self._max_fails = int(_cfg.get("MXNET_SERVE_REPLICA_FAILS"))
        self._cooldown = float(
            _cfg.get("MXNET_SERVE_REPLICA_COOLDOWN_S"))
        self._fail_streak = [0] * len(self._ctxs)
        self._unhealthy_until = [0.0] * len(self._ctxs)  # 0 = healthy
        if len(self._ctxs) > 1:
            # replica overlap: one single-thread worker per device so
            # device k+1 executes while device k is still busy; the
            # semaphore bounds total in-flight batches at the replica
            # count (a pool backlog would reintroduce the unbounded
            # memory the bounded queue exists to prevent)
            from concurrent.futures import ThreadPoolExecutor
            self._pools = [ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ServeReplica%d" % i)
                for i in range(len(self._ctxs))]
            self._inflight = threading.Semaphore(len(self._ctxs))
        else:
            self._pools = None
            self._inflight = None
        self._draining = False
        self._stop = False
        self._closed = False
        self._warm = False
        self._prev_sigterm = None
        if handle_sigterm:
            self._install_sigterm()
        # a serving host is exactly the process black-box dumps exist
        # for: arm the uncaught-exception/SIGUSR2 triggers (idempotent)
        _bb.install_crash_hooks()

    # -- executable construction ---------------------------------------
    def _make_infer(self):
        from ..ndarray.ndarray import NDArray
        pure = self._pure
        block = self._block

        def infer(params, x):
            # trace-time side effect ONLY: a jit-cache hit never runs
            # this python body, so the counter is the recompile meter
            # the zero-recompile-after-warmup contract is asserted on
            events.incr("serve.traces")
            nd_in = (NDArray(x),)
            tr = getattr(block, "_apply_input_transform", None)
            if tr is not None:
                # same seam as training (PR 2): uint8 wire → on-device
                # normalize/cast, fused into this bucket's executable
                nd_in = tr(nd_in)
            out, _states = pure(params, *nd_in)
            return out

        # each (device, bucket) signature becomes one cost-registry row
        # under the engine's cost label (default serve.infer; the
        # ModelRegistry passes serve.infer:<model> so admission can
        # find THIS model's measured footprint) — the per-bucket
        # FLOPs/HBM attribution the blackbox dump reports
        return _costs.metered_jit(infer, label=self._cost_label,
                                  kind="serve", role="serve_infer")

    def refresh_params(self):
        """(Re-)replicate the block's current parameters onto every
        serving device (call after the block was retrained/updated).
        After a `refresh_params_from` promote, the promoted block is
        the parameter source — a later refresh must keep serving the
        promoted weights, not silently revert to the original's."""
        import jax
        from ..parallel.functional import extract_params
        base = extract_params(self._param_src if self._param_src
                              is not None else self._block)
        if self._param_remap:
            base = {self._param_remap.get(n, n): v
                    for n, v in base.items()}
        self._dev_params = [
            {n: jax.device_put(v, c.jax_device)
             for n, v in base.items()}
            for c in self._ctxs]

    def refresh_params_from(self, block, version=None):
        """Promote-by-weight-swap (ISSUE 16): serve `block`'s
        parameters through THIS engine's already-warmed executables.
        The parameter trees must match — same names, same shapes; or
        (gluon auto-prefixing gives separately-built copies of the
        SAME architecture fresh ``dense<N>_*`` names) same
        registration order of shapes, in which case params map
        positionally onto the serving names.  The executables were
        traced against the original signature, so an architecturally
        different version needs a fresh engine, not a swap.
        Optionally re-tags the engine's version label."""
        from ..parallel.functional import extract_params
        new = extract_params(block)
        cur = extract_params(self._param_src if self._param_src
                             is not None else self._block)
        remap = None
        if set(new) != set(cur):
            # collect_params order is registration order: identical
            # architectures enumerate identically even when the name
            # prefixes drifted
            if len(new) != len(cur):
                raise ValueError(
                    "parameter tree mismatch: promote needs an "
                    "identical tree (%d params vs %d serving) — "
                    "architecturally different versions need a fresh "
                    "engine" % (len(new), len(cur)))
            remap = dict(zip(new, cur))
            cur_by_new = {n: cur[remap[n]] for n in new}
        else:
            cur_by_new = cur
        for n in new:
            if tuple(new[n].shape) != tuple(cur_by_new[n].shape):
                raise ValueError(
                    "parameter %r shape %r != serving shape %r — the "
                    "warmed executables serve ONE signature"
                    % (n, tuple(new[n].shape),
                       tuple(cur_by_new[n].shape)))
        self._param_src = block
        self._param_remap = remap
        self.refresh_params()
        if version is not None:
            self._version = str(version)
            self._journal.version = self._version
        events.incr("serve.param_swaps")

    def set_version(self, version):
        """Re-tag the version label on this engine's serve.* splits
        (promotes re-point the primary's label at the new version)."""
        self._version = str(version) if version is not None else None
        self._journal.version = self._version

    def degrade(self, stall_s):
        """Taint this engine (model.bad_version fault site): every
        batch stalls `stall_s` seconds and outputs are sign-flipped —
        deterministic degradation the canary SLO rules must catch.
        Test/chaos hook; 0 restores healthy behavior."""
        self._degrade_s = max(0.0, float(stall_s))

    # -- signal / preemption (PR 1 pattern) ----------------------------
    def _install_sigterm(self):
        ref = weakref.ref(self)         # the process-global handler
        state = {}                      # must not pin the engine (same
                                        # GC contract as the dispatcher)

        def _on_sigterm(signum, frame):
            eng = ref()
            if eng is not None:
                # flag only (signal-safe): the dispatcher notices,
                # stops intake, completes queued work, and retires
                eng._draining = True
                events.incr("serve.preempted")
                return
            # engine collected without close(): restore the previous
            # handler and re-deliver, so the process keeps honoring
            # preemption instead of silently swallowing SIGTERM
            try:
                signal.signal(signal.SIGTERM,
                              state.get("prev") or signal.SIG_DFL)
                signal.raise_signal(signal.SIGTERM)
            except Exception:           # noqa: BLE001
                pass
        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM,
                                               _on_sigterm)
            state["prev"] = self._prev_sigterm
        except ValueError:          # not the main thread
            self._prev_sigterm = None

    def uninstall_sigterm(self):
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except ValueError:
                pass
            self._prev_sigterm = None

    def request_shutdown(self):
        """Programmatic SIGTERM equivalent: stop intake, finish queued
        work in the background (pair with `close()` to join)."""
        self._draining = True
        events.incr("serve.preempted")

    # -- submission ----------------------------------------------------
    def _host_array(self, x):
        from ..ndarray.ndarray import NDArray
        if isinstance(x, NDArray):
            return x.asnumpy()
        return _np.asarray(x)

    def _check_example(self, shape, dtype):
        # shape AND wire dtype are the executable signature: accepting
        # a wrong-dtype request would silently trace a NEW executable
        # (the recompilation cliff this engine exists to close) and a
        # mixed-dtype coalesced batch would promote via np.concatenate.
        # Locked: two racing first-ever submits must agree on ONE
        # signature (the loser gets the error, not the dispatcher).
        dtype = str(_np.dtype(dtype))
        with self._lock:
            if self._example_shape is None:
                self._example_shape = tuple(shape)
                self._wire_dtype = dtype
                return
            if tuple(shape) != self._example_shape:
                raise ValueError(
                    "request example shape %r != engine example shape "
                    "%r (one executable set serves ONE signature; "
                    "build a second engine for a second signature)"
                    % (tuple(shape), self._example_shape))
            if self._wire_dtype is None:
                self._wire_dtype = dtype
            elif dtype != self._wire_dtype:
                raise ValueError(
                    "request wire dtype %s != engine wire dtype %s "
                    "(dtype is part of the warmed executable "
                    "signature; convert client-side)"
                    % (dtype, self._wire_dtype))

    def submit(self, x, deadline=None, lane=None, tenant=None):
        """Enqueue ONE example (no batch dim).  Returns a Future whose
        result is the model output for this example (batch dim
        stripped), an NDArray on the executing device.  `deadline` is
        seconds from now; expiry resolves the future with
        DeadlineExceeded.  `lane` picks the priority lane (default:
        the top lane); `tenant` tags the request for per-tenant quotas
        and the labeled serve.* splits.  Raises QueueFull / Shed /
        EngineClosed synchronously."""
        arr = self._host_array(x)
        return self._submit(arr[None], deadline, single=True,
                            lane=lane, tenant=tenant)

    def submit_batch(self, x, deadline=None, lane=None, tenant=None):
        """Enqueue a small batch (leading batch dim, size ≤ the largest
        bucket).  The batch is dispatched as one unit (never split), so
        it shares one future."""
        arr = self._host_array(x)
        if arr.ndim < 1 or arr.shape[0] < 1:
            raise ValueError("submit_batch needs a leading batch dim")
        if arr.shape[0] > self._buckets[-1]:
            raise ValueError(
                "batch of %d exceeds the largest bucket (%d); chunk it "
                "client-side (the bucket set is closed by design)"
                % (arr.shape[0], self._buckets[-1]))
        return self._submit(arr, deadline, single=False,
                            lane=lane, tenant=tenant)

    def _shed_mark(self, lane, tenant, reason, deadline=False):
        """The shed counter block — ONE definition for every shed path
        (quota sheds, born-expired, dispatch-time expiry,
        displacement), so the aggregate + lane/reason + tenant splits
        cannot drift apart."""
        events.incr("serve.rejected")
        if deadline:
            events.incr("serve.deadline_expired")
        events.incr("serve.shed")
        events.incr("serve.shed", labels={"lane": lane or "-",
                                          "reason": reason})
        if tenant is not None:
            events.incr("serve.shed", labels={"tenant": tenant})
        if self._version is not None:
            # per-version split (ISSUE 16): canary attribution — the
            # version-labeled shed burn is what the supervisor's
            # rollback rules read
            events.incr("serve.shed", labels={"version": self._version})

    def _shed(self, lane, tenant, reason, msg):
        self._shed_mark(lane, tenant, reason)
        raise Shed(msg)

    def _submit(self, arr, deadline, single, lane=None, tenant=None):
        if fault.should_fire("serve.enqueue"):
            events.incr("serve.rejected")
            raise QueueFull("injected enqueue fault (serve.enqueue)")
        self._check_example(arr.shape[1:], arr.dtype)
        lane = self._lanes[0] if lane is None else str(lane)
        if lane not in self._lane_caps:
            raise ValueError("unknown lane %r (engine lanes: %s)"
                             % (lane, ",".join(self._lanes)))
        tenant = str(tenant) if tenant is not None else None
        fut = Future()
        req = _Request(arr, arr.shape[0], fut, deadline, single,
                       lane=lane, tenant=tenant)
        req.rec = self._journal.start(req.t_enq, lane, tenant)
        if req.rec is not None:
            req.rec.n = req.n
        if req.deadline is not None and req.deadline <= req.t_enq:
            # born expired: queueing it could only burn queue slots on
            # work that is already lost — shed, deadline-typed
            self._shed_mark(lane, tenant, "deadline", deadline=True)
            exc = DeadlineExceeded("deadline is not in the future")
            self._journal.retire(req.rec, exc=exc)
            raise exc
        # closed-check + enqueue are ATOMIC against close()'s final
        # flush (which sets _closed then drains the queue under the
        # same lock): a put that wins the race lands BEFORE the flush
        # and is resolved by it — no future is ever stranded.  The
        # tenant-quota hold increments under the SAME lock, and
        # _retire's decrement is the single release point — counts
        # can't leak or double-release across the shed/expiry paths.
        try:
            self._submit_locked(req, deadline, lane, tenant)
        except MXNetError as e:
            # synchronous refusals (quota sheds / QueueFull / closed)
            # never reach _finish — this is their journal retire point
            # (terminal records always promote; the whole wall lands
            # in the queue phase, the budget phase of a refusal)
            rec, req.rec = req.rec, None
            self._journal.retire(rec, exc=e)
            raise
        self._ensure_dispatcher()
        return fut

    def _submit_locked(self, req, deadline, lane, tenant):
        with self._lock:
            if self._closed or self._draining:
                events.incr("serve.rejected")
                raise EngineClosed("engine is draining/closed")
            if tenant is not None and self._tenant_quota > 0 and \
                    self._tenant_q.get(tenant, 0) >= self._tenant_quota:
                self._shed(lane, tenant, "tenant_quota",
                           "tenant %r over quota (%d queued, cap %d); "
                           "back off or raise MXNET_SERVE_TENANT_QUOTA"
                           % (tenant, self._tenant_q.get(tenant, 0),
                              self._tenant_quota))
            victim = None
            try:
                self._q.put_nowait(req)
            except _OverQuota as oq:
                self._shed(lane, tenant, "lane_quota",
                           "lane %r over quota (%d queued, cap %d); "
                           "excess low-priority work is shed under "
                           "overload — see MXNET_SERVE_LANE_QUOTAS"
                           % (oq.lane, oq.depth, oq.cap))
            except queue.Full:
                # priority displacement: a higher-lane submit meeting
                # a full queue evicts the newest lowest-lane request
                # (which is shed, typed) instead of being rejected —
                # otherwise lower-lane backlog whose quotas sum past
                # 1.0 would hold every slot and the TOP lane would see
                # QueueFull under exactly the overload lanes exist for
                victim = self._q.evict_lowest(below=lane)
                if victim is None:
                    events.incr("serve.rejected")
                    raise QueueFull(
                        "serve queue at capacity (%d requests); retry "
                        "later or raise MXNET_SERVE_QUEUE_CAP"
                        % self._q.maxsize)
                # the eviction freed a slot and this lane was under
                # its own quota (the first put raised Full, not
                # _OverQuota), so the re-put cannot fail
                self._q.put_nowait(req)
            if tenant is not None:
                self._tenant_q[tenant] = \
                    self._tenant_q.get(tenant, 0) + 1
            if deadline is not None:
                # ACCEPTED requests only (shed paths raised above):
                # a quota-shed client's deadline never became work
                # this lane owed.  Same lock as the enqueue — one
                # deque append per deadlined submit
                dq = self._lane_deadline_s.get(lane)
                if dq is None:
                    dq = self._lane_deadline_s[lane] = \
                        deque(maxlen=256)
                dq.append(float(deadline))
        if victim is not None:          # outside the lock: _finish →
            self._shed_mark(victim.lane, victim.tenant, "displaced")
            self._finish(victim, exc=Shed(  # _retire re-takes it
                "displaced by %r-lane traffic under overload "
                "(queue full); back off or escalate lanes" % lane))

    def _ensure_dispatcher(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=InferenceEngine._dispatch_loop,
                    args=(weakref.ref(self),), daemon=True,
                    name="ServeDispatcher")
                self._thread.start()

    # -- dispatcher ----------------------------------------------------
    @staticmethod
    def _dispatch_loop(ref):
        """Holds the engine only through a WEAKREF between iterations
        (the DeviceFeed._run pattern): an engine dropped without
        close() becomes unreachable, the GC fires __del__ (stop
        flags), and this thread retires at its next poll — a
        bound-method target would pin the engine (and its per-device
        parameter replicas) for process lifetime on exactly the
        long-lived hosts that rebuild engines per model refresh."""
        while True:
            eng = ref()
            if eng is None:
                return
            try:
                reqs = eng._collect()
                if reqs is None:
                    return
                if reqs:                # [] = idle poll: release the
                    eng._execute(reqs)  # strong ref and re-resolve
            except Exception as e:      # noqa: BLE001 — the dispatcher
                # must survive ANYTHING (a dead dispatcher strands every
                # queued future); _execute resolves its own requests, so
                # whatever escaped here had none in hand
                import logging
                logging.getLogger(__name__).exception(
                    "serve dispatcher error (recovered)")
                events.incr("serve.dispatcher_errors")
                # the backstop firing means the engine survived
                # something it shouldn't have seen — leave the forensic
                # file while the evidence (ring + counters) is fresh
                _bb.record("fault", "serve.dispatcher",
                           error=type(e).__name__)
                _bb.crash_dump("serve.dispatcher", e)
                time.sleep(0.01)
            finally:
                del eng

    def _retire(self, req):
        """Return an accepted request's queue slot (task_done) and
        release its tenant-quota hold — the single decrement point,
        reached exactly once per accepted request (via _finish or the
        cancel path), so tenant counts cannot leak across shed storms
        or drain."""
        if req.tenant is not None:
            with self._lock:
                n = self._tenant_q.get(req.tenant, 0) - 1
                if n > 0:
                    self._tenant_q[req.tenant] = n
                else:
                    self._tenant_q.pop(req.tenant, None)
        self._q.task_done()

    def _finish(self, req, result=None, exc=None):
        """Resolve a request's future (result or exception) and retire
        its queue slot — tolerant of caller-side cancel()/double
        resolution (a cancelled future raises InvalidStateError on
        set_*; that must never kill the dispatcher or skew task_done
        accounting)."""
        try:
            if exc is not None:
                req.future.set_exception(exc)
            else:
                req.future.set_result(result)
        except Exception:               # noqa: BLE001 — cancelled/done
            events.incr("serve.cancelled")
        self._retire(req)
        # the single journal-retire point for every ACCEPTED request
        # (refusals retire in _submit, cancels in _execute): phase
        # math + tail-promotion happen here, off the submit path
        rec, req.rec = req.rec, None
        if rec is not None:
            self._journal.retire(rec, exc=exc)

    def _collect(self):
        """Coalesce queued requests into one bucket's worth: pull
        greedily while the queue is non-empty, wait up to max_wait for
        fill once it runs dry, stop at the largest bucket.  Returns the
        request list, or None when the dispatcher should retire."""
        max_b = self._buckets[-1]
        reqs, total = [], 0
        edl = None              # earliest deadline among collected reqs
        with self._lock:        # carry handoff races close()'s flush
            carry, self._carry = self._carry, None
        if carry is not None:
            reqs.append(carry)
            total = carry.n
            edl = carry.deadline
        t_first = time.monotonic() if reqs else None
        while total < max_b:
            if self._stop:
                break
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                if reqs:
                    now = time.monotonic()
                    rem = self._max_wait - (now - t_first)
                    if edl is not None:
                        # a collected request is about to expire: stop
                        # filling and dispatch (or reap) it promptly
                        # instead of padding the wait to max_wait
                        rem = min(rem, edl - now)
                    if rem <= 0:
                        break
                    try:
                        item = self._q.get(timeout=min(rem, 0.05))
                    except queue.Empty:
                        continue
                else:
                    if self._draining:
                        return None     # intake stopped + queue empty
                    try:                # idle poll (watches stop flags)
                        item = self._q.get(timeout=0.05)
                    except queue.Empty:
                        # surface to the outer loop so the dispatcher's
                        # strong engine ref lapses between idle polls
                        # (abandonment/GC liveness)
                        return []
            if item.rec is not None:    # end of queue-wait: the
                item.rec.t_collect = time.monotonic()   # coalesce
            if item.deadline is not None and \
                    time.monotonic() > item.deadline:   # phase starts
                self._expire(item)
                continue
            if total + item.n > max_b:
                with self._lock:
                    self._carry = item  # next batch starts with it
                break
            reqs.append(item)
            total += item.n
            if item.deadline is not None:
                edl = item.deadline if edl is None \
                    else min(edl, item.deadline)
            if t_first is None:
                t_first = time.monotonic()
        return reqs if reqs else None

    def _expire(self, req):
        # over-deadline work found at dispatch time is SHED (typed
        # error, never device time) — under overload this is what keeps
        # a backed-up lane from dragging every deadline down with it
        self._shed_mark(req.lane, req.tenant, "deadline",
                        deadline=True)
        self._finish(req, exc=DeadlineExceeded(
            "request expired after %.3fs in queue"
            % (time.monotonic() - req.t_enq)))

    def _bucket_for(self, n):
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    #: headroom multiplier on the EWMA service estimate in the
    #: dispatch-time feasibility check: the estimate is a mean, the
    #: deadline is a bound — without margin, requests dispatched at the
    #: feasibility edge land just past their deadline whenever the
    #: actual service time comes in above the mean
    _SVC_MARGIN = 1.25

    def _svc_estimate(self, bucket):
        """EWMA batch service seconds for `bucket`.  When this bucket
        hasn't run yet, scale the NEAREST known bucket's EWMA by the
        size ratio — judging a size-1 batch by the 32-wide bucket's
        wall would spuriously shed small requests that had time to
        spare.  0 cold: feasibility shedding only engages once real
        service times exist."""
        with self._lock:
            est = self._svc_ewma.get(bucket)
            if est is None and self._svc_ewma:
                near = min(self._svc_ewma,
                           key=lambda b: abs(b - bucket))
                est = self._svc_ewma[near] * (bucket / float(near))
            return (est or 0.0) * self._SVC_MARGIN

    def _execute(self, reqs):
        # deadline-AWARE dispatch (ISSUE 8): a request is shed not only
        # when its deadline already passed, but when it CANNOT make it
        # — now + the estimated batch service time (per-bucket EWMA)
        # past the deadline means dispatching it would burn device time
        # to deliver a result the caller has already written off.
        # Two passes: reap the already-expired FIRST, then judge
        # feasibility against the service time of the batch that will
        # ACTUALLY run — 31 stale requests must not doom the 1 fresh
        # one by inflating the bucket estimate
        now = time.monotonic()
        fresh = []
        for r in reqs:
            if r.rec is not None:       # coalesce done, batch formed
                r.rec.t_exec = now
            if r.deadline is not None and now > r.deadline:
                self._expire(r)
            else:
                fresh.append(r)
        live = []
        est = self._svc_estimate(
            self._bucket_for(sum(r.n for r in fresh))) if fresh else 0.0
        for r in fresh:
            if r.deadline is not None and now + est > r.deadline:
                self._expire(r)
            elif not r.future.set_running_or_notify_cancel():
                # caller cancelled while queued: drop before burning
                # device time; the future is already CANCELLED
                events.incr("serve.cancelled")
                self._retire(r)
                rec, r.rec = r.rec, None
                self._journal.retire(rec, status="cancelled",
                                     reason="cancelled while queued")
            else:
                live.append(r)          # RUNNING: cancel() is now inert
        if not live:
            return
        total = sum(r.n for r in live)
        bucket = self._bucket_for(total)
        # queue-depth sample per dispatched batch: the black-box
        # timeline shows backlog growth leading up to a death, which
        # counters (totals) cannot reconstruct.  Stamped at the batch's
        # earliest ADMISSION, not at dispatch (ISSUE 19 satellite, the
        # emit_foreign end-stamp family): the depth belongs where the
        # oldest victim started waiting, so the dump timeline shows the
        # backlog GROWING before the slow exemplar instead of the
        # sample landing after the queue already drained
        _bb.record_at(_tele.wall_of(min(r.t_enq for r in live)),
                      "serve", "queue", depth=self._q.qsize(),
                      bucket=bucket, n=total)
        dev_i = self._pick_replica()
        if self._pools is None:
            self._run_and_fan(live, total, bucket, dev_i)
            return
        # replica overlap: hand the batch to device dev_i's worker so
        # the dispatcher can coalesce the NEXT bucket while this one
        # executes; the semaphore bounds in-flight batches at the
        # replica count (the queue cap alone can't — pool backlogs
        # would be the unbounded memory the bounded queue exists to
        # prevent)
        self._inflight.acquire()
        with self._lock:
            self._n_inflight += 1
        try:
            self._pools[dev_i].submit(self._run_and_fan, live, total,
                                      bucket, dev_i)
        except RuntimeError:            # pool shut down by a racing
            self._inflight.release()    # close(): these futures are in
            with self._lock:            # neither queue nor carry, so
                self._n_inflight -= 1   # the flush can't see them —
            for r in live:              # resolve here, never strand
                self._finish(r, exc=EngineClosed(
                    "engine closed before dispatch"))

    # -- replica health ------------------------------------------------
    def _pick_replica(self):
        """Health-aware round-robin: skip replicas inside their
        unhealthy cooldown; a replica whose cooldown expired gets ONE
        probe batch (its window re-arms immediately, so a second batch
        does not pile onto an unproven device before the probe's
        verdict).  All-unhealthy fails OPEN to the soonest-recovering
        replica — degraded service beats refused service."""
        n = len(self._ctxs)
        if n == 1:
            self._rr += 1
            return 0
        now = time.monotonic()
        with self._lock:
            for _ in range(n):
                i = self._rr % n
                self._rr += 1
                until = self._unhealthy_until[i]
                if until == 0.0:
                    return i
                if now >= until:
                    # probe: one batch back onto the cooled-down
                    # replica; success re-admits it (_replica_ok),
                    # failure restarts the cooldown (_replica_failed)
                    self._unhealthy_until[i] = now + self._cooldown
                    events.incr("serve.replica_probes")
                    return i
            i = min(range(n), key=lambda k: self._unhealthy_until[k])
        events.incr("serve.all_replicas_unhealthy")
        return i

    def _replica_failed(self, dev_i, exc):
        """A terminal dispatch failure (the retry budget is already
        spent by the time this is called) on replica `dev_i`."""
        newly = False
        with self._lock:
            self._fail_streak[dev_i] += 1
            streak = self._fail_streak[dev_i]
            if streak >= self._max_fails or \
                    self._unhealthy_until[dev_i] > 0.0:
                newly = self._unhealthy_until[dev_i] == 0.0
                self._unhealthy_until[dev_i] = \
                    time.monotonic() + self._cooldown
        if newly:
            events.incr("serve.replica_unhealthy")
            _bb.record("serve", "replica_unhealthy",
                       replica=int(dev_i),
                       device=repr(self._ctxs[dev_i]),
                       consecutive_fails=int(streak),
                       error=type(exc).__name__,
                       cooldown_s=self._cooldown)
            import logging
            logging.getLogger(__name__).warning(
                "serving replica %d (%r) marked unhealthy after %d "
                "consecutive failures (%s); routing around it for "
                "%.1fs", dev_i, self._ctxs[dev_i], streak,
                type(exc).__name__, self._cooldown)

    def _replica_ok(self, dev_i):
        """A successful dispatch: the streak resets, and an unhealthy
        replica (this was its probe) is re-admitted."""
        recovered = False
        with self._lock:
            self._fail_streak[dev_i] = 0
            if self._unhealthy_until[dev_i] > 0.0:
                self._unhealthy_until[dev_i] = 0.0
                recovered = True
        if recovered:
            events.incr("serve.replica_recovered")
            _bb.record("serve", "replica_recovered",
                       replica=int(dev_i),
                       device=repr(self._ctxs[dev_i]))

    def _run_and_fan(self, live, total, bucket, dev_i):
        """Pad→execute→fan-out for one coalesced batch — inline on a
        single-device engine, on the device's worker thread with
        replicas.  EVERY exit resolves every live future (the
        drain/close contract rides on task_done accounting)."""
        from ..parallel.resilience import retry_transient
        t0 = time.monotonic()
        for r in live:
            events.observe_time("serve.queue_us", t0 - r.t_enq)
            if r.rec is not None:       # dispatch handoff complete;
                r.rec.t_infer0 = t0     # device time starts here
                r.rec.bucket = bucket
        # the dispatch span parents onto the first request's submit-side
        # context, so the cross-thread submit→dispatch→infer chain
        # shares one trace; nested serve.infer inherits automatically
        dispatch_span = _tele.span("serve.dispatch",
                                   parent=live[0].tele)
        try:
            dispatch_span.start()
            try:
                batch = live[0].data if len(live) == 1 else \
                    _np.concatenate([r.data for r in live], axis=0)
                if bucket > total:
                    pad = _np.zeros(
                        (bucket - total,) + batch.shape[1:],
                        batch.dtype)
                    batch = _np.concatenate([batch, pad], axis=0)
                with _tele.span("serve.infer"):
                    out = retry_transient(
                        lambda: self._run(dev_i, batch),
                        what="serve.infer(bucket=%d)" % bucket,
                        event="serve.retries")
            except Exception as e:      # noqa: BLE001 — fan the failure
                events.incr("serve.failed")
                self._replica_failed(dev_i, e)
                for r in live:          # out to every caller's future
                    self._finish(r, exc=e)
                return
            self._replica_ok(dev_i)
            t1 = time.monotonic()
            dt_svc = t1 - t0
            for r in live:
                if r.rec is not None:   # device done; join/D2H next
                    r.rec.t_infer1 = t1
            with self._lock:    # feed the deadline-feasibility EWMA
                prev = self._svc_ewma.get(bucket)
                self._svc_ewma[bucket] = dt_svc if prev is None \
                    else 0.3 * dt_svc + 0.7 * prev
            events.observe_time("serve.infer_us", dt_svc)
            events.incr("serve.batches")
            events.incr("serve.batch_fill", total)
            events.incr("serve.pad_waste", bucket - total)
            events.incr("serve.requests", len(live))
            with self._lock:
                self._n_batches += 1
                self._dev_batches[dev_i] += 1
            try:
                self._fan_out(live, out, dev_i)
            except Exception as e:      # noqa: BLE001 — e.g. an output
                # leaf without a leading batch dim: the infer succeeded
                # but slicing failed; the futures must still resolve
                events.incr("serve.failed")
                for r in live:
                    if not r.future.done():
                        self._finish(r, exc=e)
        finally:
            dispatch_span.stop()
            if self._pools is not None:
                self._inflight.release()
                with self._lock:
                    self._n_inflight -= 1

    def _materialize_params(self, batch_np):
        """Resolve deferred parameter shapes from a concrete batch
        (model_zoo nets defer channel dims until a first forward),
        then replicate.  Mirrors HybridBlock.__call__'s pre-pass:
        abstract infer_shape first, one paused eager forward as the
        fallback for forwards eval_shape can't abstract."""
        from ..ndarray.ndarray import NDArray
        import jax
        blk = self._block
        x = NDArray(jax.device_put(batch_np[:1],
                                   self._ctxs[0].jax_device),
                    ctx=self._ctxs[0])
        tr = getattr(blk, "_apply_input_transform", None)
        pre = tr((x,)) if tr is not None else (x,)
        try:
            blk.infer_shape(*pre)
            for p in blk.collect_params().values():
                if p._deferred_init:
                    p._finish_deferred_init()
        except Exception:
            from .. import autograd as _ag
            from ..gluon.block import Block
            with _ag.pause():
                Block.__call__(blk, *pre)
        self.refresh_params()

    def _run(self, dev_i, batch_np):
        import jax
        fault.maybe_raise("serve.infer", step=self._n_batches)
        # benign per-batch stall (latency chaos / the controlplane
        # bench's sleep-dominated service): unlike serve.infer this
        # slows the batch instead of failing it, so capacity scales
        # with REPLICAS even on a single-core virtual-device host
        fault.maybe_slow("serve.slow", step=self._n_batches)
        if self._warm and self._dev_params is not None:
            # warmed steady state: every (device, bucket) executable
            # exists and the signature is locked, so replica workers
            # execute lock-free (jit cache hits are thread-safe) —
            # this is what lets device k+1 overlap device k
            x = jax.device_put(batch_np,
                               self._ctxs[dev_i].jax_device)
            out = self._infer(self._dev_params[dev_i], x)
            jax.block_until_ready(out)
            return self._degraded(out)
        with self._exec_lock:           # traces/materialization
            if self._dev_params is None:
                self._materialize_params(batch_np)
            x = jax.device_put(batch_np, self._ctxs[dev_i].jax_device)
            out = self._infer(self._dev_params[dev_i], x)
            jax.block_until_ready(out)
        return self._degraded(out)

    def _degraded(self, out):
        """model.bad_version taint (see `degrade`): stall + sign-flip
        — deterministic badness on latency AND correctness, so both a
        p99 rule and an output-parity check catch it."""
        if not self._degrade_s:
            return out
        import jax
        time.sleep(self._degrade_s)
        return jax.tree_util.tree_map(lambda a: -a, out)

    def _fan_out(self, reqs, out, dev_i):
        import jax
        from ..ndarray.ndarray import NDArray
        ctx = self._ctxs[dev_i]
        off = 0
        for r in reqs:
            lo, hi, single = off, off + r.n, r.single
            res = jax.tree_util.tree_map(
                lambda a: NDArray(a[lo] if single else a[lo:hi],
                                  ctx=ctx), out)
            off = hi
            if r.rec is not None:       # slice done; what remains is
                r.rec.t_fin = time.monotonic()  # future resolution
            self._finish(r, result=res)
            dt = time.monotonic() - r.t_enq
            events.observe_time("serve.e2e_us", dt)
            # tenant/lane splits of the same series (ISSUE 8): the
            # aggregate above stays authoritative, the labeled rings
            # answer "p99 for lane X / tenant Y" in /metrics + dumps
            us = int(dt * 1e6)
            # REQUEST-denominated, matching the unlabeled aggregate
            # (dispatcher: len(live)) and serve.shed (1 per shed) —
            # the SLO shed burn rules ratio shed/(requests+shed), and
            # example-denominated children would dilute that ratio by
            # the batch size for submit_batch traffic
            if r.lane is not None:
                events.observe("serve.e2e_us", us,
                               labels={"lane": r.lane})
                events.incr("serve.requests",
                            labels={"lane": r.lane})
            if r.tenant is not None:
                events.observe("serve.e2e_us", us,
                               labels={"tenant": r.tenant})
                events.incr("serve.requests",
                            labels={"tenant": r.tenant})
            if self._version is not None:
                # version split (ISSUE 16): one labelset per live
                # version (bounded by the MAX_LABELSETS fold) — the
                # percentile ring the canary p99 rule judges
                events.observe("serve.e2e_us", us,
                               labels={"version": self._version})
                events.incr("serve.requests",
                            labels={"version": self._version})

    # -- warmup --------------------------------------------------------
    def warmup(self, example_shape=None, wire_dtype=None):
        """Compile EVERY (device, bucket) executable before traffic,
        so no organic request ever pays a compile.  Needs the example
        signature — from the constructor, a prior request, or the
        arguments here.  Returns a summary dict; after it,
        `serve.traces` stays flat under any mix of request sizes ≤ the
        largest bucket."""
        if self._example_shape is None and example_shape is None:
            raise ValueError(
                "warmup() of %r before any request needs example_shape= "
                "(and wire_dtype=) — the executable signature"
                % self._cost_label)
        # route through the SAME signature gate as submits: a warmup
        # conflicting with an already-locked shape/dtype must raise,
        # not silently re-point the executable set away from traffic
        self._check_example(
            tuple(example_shape) if example_shape is not None
            else self._example_shape,
            wire_dtype or self._wire_dtype or "float32")
        dtype = _np.dtype(self._wire_dtype)
        t0 = time.monotonic()
        per_bucket = {}
        try:
            # the deterministic OOM drill: the serve.oom fault site
            # raises a RESOURCE_EXHAUSTED-shaped failure here, through
            # the same catch the real allocator failure takes
            fault.maybe_raise(
                "serve.oom", 0, msg="RESOURCE_EXHAUSTED: out of "
                "memory while warming %r (injected)" % self._cost_label)
            for i in range(len(self._ctxs)):
                for b in self._buckets:
                    x = _np.zeros((b,) + self._example_shape, dtype)
                    tb = time.monotonic()
                    self._run(i, x)
                    per_bucket[b] = round(time.monotonic() - tb, 4)
        except Exception as e:
            # an allocator OOM while materializing the bucket ladder:
            # dump committed-vs-measured BEFORE unwinding releases the
            # buffers that prove who was resident (ISSUE 20)
            from ..telemetry import memwatch as _mw
            _mw.guard_oom("serve.warmup", e)
            raise
        self._warm = True
        events.incr("serve.warmups")
        # probe row OUTSIDE bench (ISSUE 19 satellite / ROADMAP item 2
        # follow-on): the warmup's own measured wall trains the
        # autotuner's measured tier for the serve-bucket ladder, so
        # production serving hosts contribute evidence — until now
        # only bench wrote probes and serving only consumed
        try:
            from ..compile import autotune as _autotune
            if per_bucket:
                _autotune.note_probe(
                    "serve_buckets", self._cost_label,
                    ",".join(str(b) for b in self._buckets),
                    sum(per_bucket.values()) * 1e6,
                    source="serve.warmup", devices=len(self._ctxs))
        except Exception:           # noqa: BLE001 — evidence is
            pass                    # advisory, never blocks warmup
        return {"buckets": list(self._buckets),
                "devices": len(self._ctxs),
                "wall_s": round(time.monotonic() - t0, 3),
                "bucket_wall_s": per_bucket,
                "traces": events.get("serve.traces")}

    # -- lifecycle -----------------------------------------------------
    def drain(self, timeout=30.0):
        """Stop intake (submits raise EngineClosed) and wait until every
        already-accepted request is resolved.  Returns True when the
        queue fully drained within `timeout`."""
        self._draining = True
        deadline = time.monotonic() + float(timeout)
        with self._q.all_tasks_done:
            while self._q.unfinished_tasks:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                if (self._thread is None or
                        not self._thread.is_alive()) and \
                        not self._n_inflight:
                    break               # nothing will drain it
                self._q.all_tasks_done.wait(min(rem, 0.1))
        return self._q.unfinished_tasks == 0

    def close(self, timeout=30.0):
        """drain() + retire the dispatcher (joined within `timeout`) +
        resolve any still-outstanding future (EngineClosed) so no
        caller blocks forever.  Idempotent.  Returns True when the
        dispatcher thread is fully joined."""
        t_end = time.monotonic() + float(timeout)
        self.drain(timeout)
        self._stop = True
        t = self._thread
        joined = True
        if t is not None and t.is_alive():
            t.join(max(0.1, t_end - time.monotonic()))
            joined = not t.is_alive()
        if self._pools is not None:     # in-flight replica batches
            for p in self._pools:       # complete (and resolve) first
                p.shutdown(wait=True)
        # anything the dispatcher never got to (drain timeout, dead
        # dispatcher, a submit that raced the shutdown): resolve, don't
        # strand.  _closed flips and the queue flushes under the SAME
        # lock _submit enqueues under, so every accepted request is
        # either flushed here or was visible to the dispatcher; the
        # carry handoff is locked against a still-alive dispatcher for
        # the same exactly-once reason.
        leftovers = []
        with self._lock:
            self._closed = True
            if self._carry is not None:
                leftovers.append(self._carry)
                self._carry = None
            while True:
                try:
                    leftovers.append(self._q.get_nowait())
                except queue.Empty:
                    break
        for r in leftovers:
            self._finish(r, exc=EngineClosed(
                "engine closed before dispatch"))
        self.uninstall_sigterm()
        return joined

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        # flags only — never join a thread from a finalizer; the
        # daemon dispatcher retires at its next poll (replica pool
        # workers exit when their executors are collected with us)
        self._draining = True
        self._stop = True
        self._closed = True
        try:                            # best-effort handler restore
            self.uninstall_sigterm()    # (no-op unless installed; may
        except Exception:               # fail off the main thread —
            pass                        # the handler then chains prev)

    # -- introspection -------------------------------------------------
    def slo_targets(self):
        """{lane: tightest relative deadline seconds among the last
        256 ACCEPTED deadlined requests} — the per-lane SLO targets
        telemetry/slo.py derives its default p99-vs-deadline rules
        from (empty until deadlined traffic has been seen; an
        outlier-tight deadline ages out of the window instead of
        pinning the target forever)."""
        with self._lock:
            return {lane: min(dq)
                    for lane, dq in self._lane_deadline_s.items()
                    if dq}

    def slo_lane_quotas(self):
        """{lane: occupancy quota FRACTION this engine actually
        enforces}, reconstructed from the live caps — so the SLO
        layer's default shed budgets honor programmatic ``lanes=`` /
        ``lane_quotas=`` engines, not just the env knobs."""
        cap = float(self._q.maxsize)
        return {lane: (1.0 if c is None else c / cap)
                for lane, c in self._lane_caps.items()}

    def stats(self):
        """Engine + process-wide `serve.*` counter snapshot, including
        latency percentiles (p50/p90/p99) for the observed series."""
        now = time.monotonic()
        with self._lock:
            tenants = dict(self._tenant_q)
        return {"counters": serve_counters(),
                "latency": events.latency_snapshot("serve."),
                "labeled": events.labeled_latency_snapshot("serve."),
                "buckets": list(self._buckets),
                "devices": [repr(c) for c in self._ctxs],
                "device_batches": list(self._dev_batches),
                "replica_health": [
                    "unhealthy" if u > now else
                    ("probing" if u > 0.0 else "healthy")
                    for u in self._unhealthy_until],
                "queue_depth": self._q.qsize(),
                "lanes": {"order": list(self._lanes),
                          "depths": self._q.lane_depths(),
                          "caps": dict(self._lane_caps)},
                "tenants_queued": tenants,
                "version": self._version,
                "degraded": bool(self._degrade_s),
                "warm": self._warm}
