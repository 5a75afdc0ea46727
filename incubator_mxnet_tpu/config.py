"""Unified typed config/flag registry (ref: SURVEY §5.6 — the reference
reads MXNET_* env vars ad-hoc via dmlc::GetEnv across the codebase and
documents them in docs/faq/env_var.md; this module is the single typed
catalogue of every knob this framework honors).

Usage:

    from incubator_mxnet_tpu import config
    config.get("MXNET_ENGINE_TYPE")       # typed read (env > default)
    config.describe()                     # the env_var.md analogue
    config.set("MXNET_USE_PALLAS", "0")   # process-local override

Values resolve in order: process-local override (`set`) → environment →
registered default.  Use sites read through `config.get` at call time,
so env changes made before first use are honored (matching dmlc::GetEnv
semantics)."""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

__all__ = ["register", "get", "set", "unset", "list_vars", "describe"]

_LOCK = threading.Lock()
_REGISTRY: Dict[str, "_Var"] = {}
_OVERRIDES: Dict[str, str] = {}


class _Var:
    __slots__ = ("name", "type", "default", "doc", "choices")

    def __init__(self, name, type_, default, doc, choices=None):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc
        self.choices = choices


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _to_bool(s):
    v = str(s).lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError("not a boolean: %r" % (s,))


def register(name: str, type_: Callable = str, default: Any = None,
             doc: str = "", choices=None):
    """Register a knob. Re-registration with identical signature is a
    no-op; conflicting re-registration raises."""
    with _LOCK:
        old = _REGISTRY.get(name)
        if old is not None:
            if (old.type, old.default, old.choices) != \
                    (type_, default, choices):
                raise ValueError("config %s re-registered with a "
                                 "different signature" % name)
            return
        _REGISTRY[name] = _Var(name, type_, default, doc, choices)


def _parse(var, raw):
    conv = _to_bool if var.type is bool else var.type
    val = conv(raw)
    if var.choices is not None and val not in var.choices:
        raise ValueError("config %s: %r not in %r"
                         % (var.name, val, var.choices))
    return val


_warned = set()


def get(name: str, default: Any = None):
    """Typed read: override > environment > registered default > the
    `default` argument. Unregistered names read the raw environment.

    A malformed ENVIRONMENT value warns once and falls back to the
    default — a stray env var must never make `import` crash (matching
    dmlc::GetEnv's tolerance). `set()` overrides were validated eagerly,
    so they always parse here."""
    var = _REGISTRY.get(name)
    raw = _OVERRIDES.get(name, os.environ.get(name))
    if var is None:
        return raw if raw is not None else default
    if raw is None:
        # registered default wins over the argument (per the contract);
        # the argument only backstops a registration without a default
        return var.default if var.default is not None else default
    try:
        return _parse(var, raw)
    except (TypeError, ValueError) as e:
        fallback = var.default if var.default is not None else default
        if name not in _warned:
            _warned.add(name)
            import warnings
            warnings.warn("ignoring invalid %s=%r (%s); using default %r"
                          % (name, raw, e, fallback))
        return fallback


def set(name: str, value) -> None:     # noqa: A001 — parity naming
    """Process-local override (wins over the environment). Validated
    eagerly for registered names — a bad explicit override is a bug at
    the call site, unlike a stray env var."""
    var = _REGISTRY.get(name)
    if var is not None:
        _parse(var, str(value))
    _OVERRIDES[name] = str(value)


def unset(name: str) -> None:
    _OVERRIDES.pop(name, None)


def list_vars():
    return sorted(_REGISTRY)


def describe() -> str:
    """Render the registry as the env_var.md-style table."""
    lines = ["%-36s %-8s %-14s %s" % ("Variable", "Type", "Default",
                                      "Description"),
             "-" * 100]
    for name in sorted(_REGISTRY):
        v = _REGISTRY[name]
        cur = get(name)
        mark = "" if cur == v.default else "   [now: %r]" % (cur,)
        lines.append("%-36s %-8s %-14r %s%s"
                     % (name, v.type.__name__, v.default,
                        v.doc, mark))
    return "\n".join(lines)


def serve_lane_quota_fractions(spec, n_lanes):
    """Per-lane queue-occupancy quota FRACTIONS from a
    MXNET_SERVE_LANE_QUOTAS-style spec (a list/tuple of floats or a
    comma string; empty = the auto ladder 1.0, .75, .5, … floored at
    .25; a short list repeats its last value).  ONE definition, lives
    here because this module is the jax-free ground both consumers
    share: serving/engine.py turns the fractions into request caps it
    ENFORCES, telemetry/slo.py turns them into the shed error budgets
    it ALERTS on — parsed in two places they would silently drift."""
    if spec and isinstance(spec, (list, tuple)):
        fracs = [float(s) for s in spec]
    elif spec:
        fracs = [float(s) for s in str(spec).split(",") if s.strip()]
    else:
        fracs = [max(0.25, 1.0 - 0.25 * i) for i in range(n_lanes)]
    if not fracs or any(f <= 0 for f in fracs):
        raise ValueError("lane quotas must be positive fractions, "
                         "got %r" % (spec,))
    while len(fracs) < int(n_lanes):
        fracs.append(fracs[-1])             # short list: last repeats
    return fracs[:int(n_lanes)]


# ---------------------------------------------------------------------------
# the catalogue — every knob the framework honors, in one place
# ---------------------------------------------------------------------------

register("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
         "Engine mode; 'NaiveEngine' blocks after every op (race "
         "debugging, ref §5.2)",
         choices=("ThreadedEnginePerDevice", "ThreadedEngine",
                  "NaiveEngine"))
register("MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN", int, 15,
         "Op count threshold above which the engine emits a bulk-segment "
         "profiler mark (XLA fuses regardless)")
register("MXNET_CACHEDOP_FUSION", str, "1",
         "Cross-program fusion of the imperative step: 0=off (every "
         "cached-op/backward/update dispatches separately, round-2 "
         "behaviour), 1=on (net+loss one executable, backward+optimizer "
         "one executable)", choices=("0", "1"))
register("MXNET_USE_PALLAS", str, "1",
         "Pallas kernel dispatch: 0=never, 1=auto (by score-matrix "
         "bytes), 2=always", choices=("0", "1", "2"))
register("MXNET_PALLAS_INTERPRET", bool, False,
         "Run Pallas kernels in interpret mode (CPU debugging)")
register("MXNET_FLASH_BLOCK_Q", int, 0,
         "Flash-attention Q block size (0 = auto)")
register("MXNET_FLASH_BLOCK_K", int, 0,
         "Flash-attention K block size (0 = auto)")
register("MXNET_FLASH_AUTO_BYTES", float, 4e9,
         "Score-matrix bytes above which attention auto-switches to the "
         "flash kernel")
register("MXNET_FLASH_BWD_PALLAS", str, "1",
         "flash-attention backward: 1=Pallas dq/dkv kernels (block "
         "recompute from lse residuals, no TxT HBM slab), 0=fused-XLA "
         "scan fallback")
register("MXNET_FLASH_BWD_BYTES", float, 5e8,
         "Bytes threshold for the recompute-free flash backward")
register("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1 << 20,
         "Array size above which kvstore push/pull prefers sharded "
         "reduce (parity knob; XLA collectives auto-tune)")
register("MXNET_GPU_MEM_POOL_TYPE", str, "Naive",
         "Accepted for parity; memory pooling is the PJRT/XLA "
         "allocator's job on TPU (BFC arena) — value is recorded but "
         "has no effect",
         choices=("Naive", "Round", "Unpooled"))
register("MXNET_GPU_MEM_POOL_RESERVE", int, 5,
         "Accepted for parity; see MXNET_GPU_MEM_POOL_TYPE")
register("MXNET_ENFORCE_DETERMINISM", bool, False,
         "Request deterministic XLA lowering (sets "
         "--xla_gpu_deterministic_ops-equivalent behavior where "
         "available; threefry RNG is always deterministic)")
register("MXNET_SAFE_ACCUMULATION", bool, True,
         "Accumulate norms/softmax in float32 when inputs are "
         "half-precision (always on in XLA lowerings here)")
register("MXNET_FAULT_PLAN", str, "",
         "Deterministic fault-injection plan (fault.py): ';'-separated "
         "'site@step' / 'site#call' entries with optional xN repeat and "
         "~S stall seconds, e.g. 'grad_nan@3;preempt@7;io.read#2'. "
         "Empty = no faults. Armed via fault.reset_from_config()")
register("MXNET_CKPT_INTERVAL", int, 100,
         "ResilientTrainer: steps between periodic atomic checkpoints")
register("MXNET_CKPT_KEEP", int, 3,
         "ResilientTrainer: checkpoints retained (keep-last-K garbage "
         "collection; older step_* directories are removed after a "
         "successful write)")
register("MXNET_CKPT_VERIFY", bool, True,
         "Verify every checkpoint against its integrity manifest "
         "(per-file + per-leaf CRCs, integrity.py) before restoring "
         "it.  A mismatch raises a typed CheckpointCorrupt naming the "
         "bad leaf and resume() salvages the newest VERIFIABLE "
         "checkpoint from keep-K instead of dying.  0 skips "
         "verification (a flipped bit loads silently)")
register("MXNET_IO_CORRUPT_BUDGET", int, 16,
         "Corrupt RecordIO records tolerated (quarantined: skipped, "
         "counted on io.decode.records_corrupt, ring-evented and "
         "appended to the io-quarantine JSONL) per epoch per "
         "reader/service before the epoch fails loudly with "
         "CorruptRecordBudgetExceeded.  Negative = unlimited "
         "quarantine; 0 = zero tolerance (first corrupt record fails "
         "the epoch)")
register("MXNET_SDC_AUDIT_STEPS", int, 0,
         "Cross-replica SDC audit cadence: every N steps, hash every "
         "replicated param/optimizer-state shard per replica and "
         "compare across the mesh (integrity.audit_replicas).  A "
         "divergent replica is silent data corruption: black-box dump "
         "naming replica+leaf, then checkpoint rollback "
         "(ResilientTrainer) or replica eviction (ElasticTrainer). "
         "0 = off (the audit reads every replicated leaf back to "
         "host, so the cadence is a cost knob)")
register("MXNET_BAD_STEP_ROLLBACK", int, 3,
         "ResilientTrainer: consecutive skipped (non-finite/spiking) "
         "steps before rolling back to the last checkpoint; 0 disables "
         "rollback (skip-only)")
register("MXNET_LOSS_SPIKE_FACTOR", float, 0.0,
         "ResilientTrainer: skip the update when loss exceeds this "
         "multiple of its running mean (0 = non-finite detection only)")
register("MXNET_RETRY_MAX", int, 3,
         "Resilience retry budget for transient collective/I-O failures "
         "(exponential backoff between attempts)")
register("MXNET_RETRY_BACKOFF", float, 0.05,
         "Initial backoff seconds for resilience retries (doubles per "
         "attempt, jittered — see MXNET_RETRY_BACKOFF_MS)")
register("MXNET_RETRY_BACKOFF_MS", float, 0.0,
         "Initial retry backoff in MILLISECONDS; when > 0 it overrides "
         "MXNET_RETRY_BACKOFF.  Each attempt doubles the window and "
         "sleeps a uniform-jittered interval in [window/2, window] so "
         "a fleet of workers hitting the same storage/collective blip "
         "does not retry in lockstep (thundering herd)")
register("MXNET_KVSTORE_BARRIER_TIMEOUT", float, 300.0,
         "DistKVStore barrier timeout in seconds: a worker stuck at a "
         "barrier raises a clear rank-tagged error instead of hanging "
         "the job forever (0 = wait indefinitely)")
register("MXNET_IO_WORKER_RESTARTS", int, 2,
         "DecodeService: dead decode-worker auto-respawns allowed per "
         "service (pool-wide).  A respawned worker resumes its "
         "(wid, epoch) shard slice at the first undelivered batch — "
         "per-record RNG derivation keeps the stream bit-identical to "
         "an uninterrupted run.  Respawns are counted on "
         "io.decode.worker_restarts; past the budget a dead worker is "
         "a hard mid-epoch error (the pre-elastic behaviour).  0 "
         "disables respawn")
register("MXNET_IO_WORKERS", int, 0,
         "Multi-process decode service (io.decode_service): worker "
         "PROCESSES behind ImageRecordIter(workers=) and the bench io/"
         "e2e configs — GIL-free decode over sharded RecordIO readers "
         "into a shared-memory slab ring. 0 = disabled (the legacy "
         "threaded/native pipeline)")
register("MXNET_IO_RING_SLOTS", int, 0,
         "Decode-service shared-memory ring size in batch slabs "
         "(shared by all workers; each slab is one full batch). "
         "0 = auto (2*workers + 2)")
register("MXNET_IO_MP_START", str, "fork",
         "Decode-service process start method. 'fork' is the fast "
         "default (workers are jax-free by design, so forking a "
         "jax-initialized parent is safe); 'spawn' pays a fresh "
         "interpreter + package import per worker",
         choices=("fork", "spawn", "forkserver"))
register("MXNET_FEED_DEPTH", int, 2,
         "DeviceFeed (io.device_feed) prefetch depth: batches in flight "
         "between the background transfer thread and the consumer "
         "(2 = double buffer)")
register("MXNET_FEED_ASYNC", bool, True,
         "DeviceFeed background transfer thread; 0 = synchronous "
         "read+device_put in the consumer (debugging; same counters)")
register("MXNET_FEED_WIRE_DTYPE", str, "uint8",
         "Wire dtype for the image e2e feed path (bench.py): 'uint8' "
         "ships raw augmented pixels (4x fewer H2D bytes, mean/std "
         "fused on device), 'float32' the host-normalized tensor",
         choices=("uint8", "float32"))
register("MXNET_SERVE_MAX_BATCH", int, 32,
         "InferenceEngine (serving.engine): largest batch bucket — the "
         "dispatcher coalesces queued requests up to this many examples "
         "per executable call")
register("MXNET_SERVE_MAX_WAIT_US", int, 2000,
         "InferenceEngine: microseconds the dispatcher waits for more "
         "requests to fill a bucket before dispatching a partial batch "
         "(the latency/throughput coalescing knob)")
register("MXNET_SERVE_QUEUE_CAP", int, 256,
         "InferenceEngine: bounded request-queue capacity; submits "
         "beyond it are rejected with QueueFull (backpressure instead "
         "of unbounded memory growth)")
register("MXNET_SERVE_BUCKETS", str, "",
         "InferenceEngine: comma-separated batch bucket sizes (e.g. "
         "'1,2,4,8'). Empty = powers of two up to "
         "MXNET_SERVE_MAX_BATCH. The bucket set is CLOSED: every "
         "request batch is padded up to a bucket, so the compiled "
         "executable set is fixed after warmup()")
register("MXNET_SERVE_LANES", str, "high,normal,low",
         "InferenceEngine priority lanes, highest first (comma-"
         "separated names).  The dispatcher drains lanes in strict "
         "priority order, earliest-deadline-first within a lane; "
         "submits default to the FIRST lane, so single-lane callers "
         "see the pre-lane behavior unchanged")
register("MXNET_SERVE_LANE_QUOTAS", str, "",
         "Per-lane queue-occupancy quotas as comma-separated fractions "
         "of MXNET_SERVE_QUEUE_CAP, positionally matching "
         "MXNET_SERVE_LANES (short lists repeat the last value). "
         "Empty = auto: 1.0 for the top lane, then 0.75, 0.5, ... "
         "floor 0.25.  A submit that would push its lane past quota "
         "is SHED with the typed Shed error while higher lanes still "
         "have headroom — graceful degradation instead of uniform "
         "queueing collapse")
register("MXNET_SERVE_TENANT_QUOTA", int, 0,
         "InferenceEngine: max queued requests per tenant (submit "
         "tenant=...); a submit beyond it is shed (typed Shed error, "
         "serve.shed counter labeled by tenant) so one tenant's burst "
         "cannot starve the queue for everyone. 0 = no per-tenant "
         "bound")
register("MXNET_GEN_SLOTS", int, 4,
         "GenerationEngine (serving.generation): decode-batch slot "
         "count — the fixed sequence capacity ONE decode executable "
         "is specialized to.  Finished sequences free their slot at a "
         "step boundary and queued requests join immediately "
         "(continuous batching); HBM grows with slots × per-slot KV "
         "bytes, which generation admission accounts for")
register("MXNET_GEN_MAX_LEN", int, 64,
         "GenerationEngine: max_len bucket — the per-slot KV/state "
         "buffer length the decode executable is specialized to; "
         "bounds prompt length and emitted tokens per request.  Must "
         "not exceed the model's positional table")
register("MXNET_GEN_BUCKETS", str, "",
         "GenerationEngine: comma-separated PROMPT-length buckets "
         "(prefill executables; prompts pad up to a bucket).  Empty "
         "= powers of two from 8 up to MXNET_GEN_MAX_LEN.  The set "
         "is CLOSED: after warmup() no prompt length ever traces a "
         "new executable (serve.traces stays flat)")
register("MXNET_QUANT_CALIB_MODE", str, "naive",
         "serving.quantize_for_serving default calibration mode: "
         "'naive' (min/max over the calibration batches), 'entropy' "
         "(KL-divergence optimal thresholds — clips activation "
         "outliers, usually the better accuracy at the same bits), "
         "or 'none' (dynamic per-batch ranges, slowest)")
register("MXNET_QUANT_CALIB_BATCHES", int, 10,
         "serving.quantize_for_serving default number of calibration "
         "batches consumed from calib_data. 0 = the whole iterable")
register("MXNET_AMP_DTYPE", str, "",
         "Default mixed-precision compute dtype for ShardedTrainer/"
         "ResilientTrainer built with amp=None: 'bfloat16' (TPU-"
         "native: f32 exponent range, no loss scaling) or 'float16' "
         "(parity path — pair with a LossScaler; ResilientTrainer "
         "arms one automatically, backed by the NaN-guard).  Empty = "
         "full f32.  Master weights stay f32 either way; the cast "
         "policy lives in the op registry (contrib.amp.init) so "
         "imperative, symbolic AND jitted step traces all see it")
register("MXNET_SERVE_HBM_BUDGET", int, 0,
         "ModelRegistry: per-device HBM budget in bytes for serving "
         "admission control. 0 = auto (the device's PJRT bytes_limit "
         "where the backend reports one, else unbudgeted); a model "
         "whose projected footprint does not fit the budget on enough "
         "devices is refused with AdmissionDenied")
register("MXNET_SERVE_HBM_TEMP_FACTOR", float, 2.0,
         "ModelRegistry footprint projection: multiplier applied to "
         "the (input + output) activation bytes of the largest bucket "
         "to cover XLA temp buffers before a measured "
         "memory_analysis row exists in the cost registry")
register("MXNET_SERVE_BREAKER_FAILS", int, 5,
         "ModelRegistry circuit breaker: consecutive terminal request "
         "failures on ONE model backend before its breaker OPENS "
         "(submits fail fast with CircuitOpen instead of queueing "
         "onto a dead backend) — the whole-model generalization of "
         "MXNET_SERVE_REPLICA_FAILS")
register("MXNET_SERVE_BREAKER_COOLDOWN_S", float, 10.0,
         "ModelRegistry circuit breaker: seconds an OPEN breaker "
         "rejects before letting ONE probe request through "
         "(half-open); probe success re-closes it, failure restarts "
         "the cooldown")
register("MXNET_SERVE_REPLICA_FAILS", int, 3,
         "InferenceEngine: consecutive terminal dispatch failures on "
         "ONE replica device before it is marked unhealthy and routed "
         "around (serve.replica_unhealthy counter + flight-recorder "
         "event); a healthy dispatch resets the streak")
register("MXNET_SERVE_REPLICA_COOLDOWN_S", float, 5.0,
         "InferenceEngine: seconds an unhealthy replica is skipped by "
         "the round-robin before ONE probe batch is routed back to it "
         "(success re-admits it — serve.replica_recovered; failure "
         "restarts the cooldown)")
register("MXNET_ELASTIC_STALE_STEPS", int, 1,
         "ElasticTrainer heartbeat health: steps without a kvstore "
         "heartbeat before a replica is reported SLOW "
         "(mesh.replica_slow counter; observation only, no shrink)")
register("MXNET_ELASTIC_DOWN_STEPS", int, 2,
         "ElasticTrainer heartbeat health: steps without a kvstore "
         "heartbeat before a replica is declared DOWN — the mesh "
         "drains, shrinks to the survivors, re-shards ZeRO state from "
         "the last atomic checkpoint and training continues")
register("MXNET_ELASTIC_MIN_REPLICAS", int, 1,
         "ElasticTrainer: smallest mesh the supervisor will shrink to; "
         "losing a replica below this floor is a hard error (the job "
         "cannot meaningfully continue)")
register("MXNET_BN_STABLE_VAR", bool, False,
         "BatchNorm batch statistics: 1 = shifted two-pass variance "
         "E[(x-mean)^2] (numerically safe when |mean| >> std, e.g. f32 "
         "nets on unnormalized inputs — ADVICE.md round 5), 0 = fused "
         "one-pass E[x^2]-E[x]^2 (single read of x; the bf16 default "
         "where activations are normalized and HBM reads are the step "
         "time)")
register("MXNET_TELEMETRY", bool, False,
         "Telemetry instrumentation (telemetry/): spans on the "
         "profiler timeline + per-step train.* counters.  Off = every "
         "hook is a single bool read (near-zero hot-path overhead); "
         "the monitor.events counters the subsystems always report "
         "are unaffected")
register("MXNET_TELEMETRY_PORT", int, 0,
         "MetricsExporter HTTP endpoint port (/metrics Prometheus "
         "text, /metrics.json, /healthz); 0 = no endpoint.  Used by "
         "telemetry.start() / MetricsExporter.serve_http()")
register("MXNET_TELEMETRY_EXPORT_PATH", str, "",
         "MetricsExporter periodic-file path: counters + percentiles "
         "written atomically every MXNET_TELEMETRY_EXPORT_S seconds "
         "('.prom'/'.txt' = Prometheus text, else JSON — the teletop "
         "snapshot format). Empty = no file export")
register("MXNET_TELEMETRY_EXPORT_S", float, 15.0,
         "Seconds between periodic telemetry file exports")
register("MXNET_BLACKBOX", bool, True,
         "Flight recorder (telemetry/flightrec.py): always-on bounded "
         "event ring + black-box JSON dumps on rollback/preemption/"
         "uncaught exceptions/SIGUSR2, and per-executable cost "
         "metering (telemetry/costs.py).  0 reduces every hook to a "
         "single bool read")
register("MXNET_BLACKBOX_RING", int, 4096,
         "Flight-recorder ring capacity (events retained for the "
         "last-N timeline a black-box dump embeds)")
register("MXNET_BLACKBOX_DIR", str, "",
         "Directory for black-box dumps (auto-named "
         "blackbox-<ts>-p<pid>-<seq>-<reason>.json). Empty = the "
         "system temp directory (crash hooks armed outside bench/tests "
         "must not litter the launch directory)")
register("MXNET_ZERO_LEVEL", int, 0,
         "Default ZeRO stage for ShardedTrainer(zero=None): 0 = fully "
         "replicated, 1 = optimizer state sharded along the data axis "
         "(the legacy WSC path, bit-compatible with earlier releases), "
         "2 = + gradients reduce-scattered in size-capped buckets and "
         "the update computed shard-locally, 3 = + parameters STORED "
         "sharded (gathered on demand at step start, per-replica "
         "persistent param memory ~1/N).  Levels 2-3 use the explicit "
         "overlap-first step (parallel/zero.py) and require a 1-d "
         "data-parallel mesh with replicated param specs",
         choices=(0, 1, 2, 3))
register("MXNET_ZERO_BUCKET_MB", float, 0.0,
         "Gradient-bucket size cap in MB for the ZeRO-2/3 "
         "reduce-scatter (parallel/zero.py): grads of small/indivisible "
         "params are concatenated into buckets no larger than this "
         "before their collective launches.  0 = auto: the compile "
         "autotuner (compile/autotune.py) picks the cap from measured "
         "cross-run history — probe rows first, then cost rows — "
         "falling back to the one-shot costs.suggest_bucket_mb "
         "heuristic (which then warns that it was the deciding input) "
         "when history is cold")
register("MXNET_AUTOTUNE", bool, True,
         "History-trained autotuner (compile/autotune.py): resolve "
         "executable-shaping knobs (ZeRO bucket cap, batch size, "
         "serve bucket ladders, donation, remat) from measured "
         "kind=\"autotune\" probe rows and kind=\"cost\" executable "
         "rows persisted across runs under MXNET_HISTORY_DIR, with "
         "typed autotune/decision records (ring event + history row + "
         "blackbox block).  0 = every suggest_* returns its fallback "
         "(the pre-ISSUE-18 heuristics) and records nothing")
register("MXNET_ZERO_SOLO_KB", int, 256,
         "Param size in KB above which a param with a data-divisible "
         "axis gets its OWN reduce-scatter along that axis (no "
         "flatten/concat copy) instead of joining a concat bucket")
register("MXNET_ZERO_OVERLAP", str, "auto",
         "ZeRO-2/3 collective schedule: 'bwd' launches each bucket's "
         "reduce-scatter as soon as its grads are ready (interleaved "
         "with backward — hides collective latency behind compute on "
         "backends with async collectives), 'trail' coalesces every "
         "bucket collective after backward at one synchronized point "
         "(host-bound CPU meshes: staggered rendezvous arrival makes "
         "interleaved collectives convoy — measured ~10x their "
         "isolated cost).  'auto' = trail on CPU backends, bwd "
         "elsewhere", choices=("auto", "bwd", "trail"))
register("MXNET_DISPATCH_THREADS", int, -1,
         "ShardedTrainer per-replica dispatch fan-out: worker threads "
         "that device_put each replica's batch shard concurrently "
         "(JAX dispatch releases the GIL into C++) and time it into "
         "train.dispatch_replica_us{replica=}.  -1 = auto (one thread "
         "per replica, capped at 8, engaged only for multi-replica "
         "meshes fed from host arrays of >= 1 MB), 0 = off, N = "
         "exactly N worker threads (1 = uploads serialize through one "
         "worker but per-replica timing attribution is kept)")
register("MXNET_STRAGGLER_WINDOW", int, 8,
         "Fleet straggler detector (telemetry/fleet.py): per-replica "
         "rolling window of published step times the skew statistic is "
         "computed over.  Smaller = faster detection, noisier verdict; "
         "the detector needs at least 2 samples per replica before it "
         "judges")
register("MXNET_STRAGGLER_SIGMA", float, 4.0,
         "Fleet straggler detector: a replica whose windowed median "
         "step time exceeds the OTHER replicas' median by this many "
         "robust sigmas (1.4826*MAD, leave-one-out so a small "
         "fleet's outlier cannot inflate its own baseline) — with a "
         "floor of 50%% over that median, so a uniform fleet "
         "(MAD ~ 0) never flags micro-skew — is reported as a "
         "straggler: mesh.straggler counter + ring event, and "
         "ElasticTrainer's existing slow-(observed) replica state")
register("MXNET_FLEET_PUBLISH_STEPS", int, 1,
         "Fleet telemetry publish cadence: every N supervised steps "
         "each replica pushes its compact snapshot (step time, "
         "dispatch/collective walls, HBM watermark) "
         "through the kvstore at __mesh__/telemetry/<rid> for "
         "rank 0 to merge into the FleetView.  0 disables fleet "
         "publishing/straggler detection")
register("MXNET_HISTORY_DIR", str, "",
         "Durable telemetry history (telemetry/history.py): directory "
         "the per-process append-only shard files "
         "(history-<ts>-p<pid>.jsonl) are written to at exporter-tick "
         "cadence — counter deltas, percentile summaries, "
         "cost-registry rows (the autotuner's persisted measured-cost "
         "substrate), per-replica fleet rows and SLO alert "
         "transitions, queryable across runs via telemetry.history."
         "query and `blackbox history`.  Empty = history off (every "
         "write is a no-op)")
register("MXNET_HISTORY_SHARD_KB", int, 4096,
         "Size cap in KB per history shard file; a shard past the cap "
         "is compacted in place (newest half kept intact, older half "
         "downsampled 2:1, atomically rewritten) so long-lived "
         "processes bound their on-disk history while keeping its "
         "envelope")
register("MXNET_SLO_FAST_S", float, 60.0,
         "SLO burn-rate FAST window in seconds (telemetry/slo.py): "
         "the reactive window of the multi-window burn-rate rules — "
         "an alert fires only when both the fast and slow windows "
         "burn the error budget at >= 1x, and clears when the fast "
         "window recovers")
register("MXNET_SLO_SLOW_S", float, 300.0,
         "SLO burn-rate SLOW window in seconds: the de-flaking window "
         "of the multi-window burn-rate rules (a one-tick blip that "
         "clears before the slow window accumulates never pages)")
register("MXNET_SLO_SHED_BUDGET", float, 0.02,
         "Default serving error budget (telemetry/slo.py): the "
         "allowed shed fraction for the TOP priority lane's "
         "burn-rate rule; lower lanes are designed to shed under "
         "overload and budget max(this, 1 - lane quota) following "
         "the MXNET_SERVE_LANE_QUOTAS ladder")
register("MXNET_CTL_TICK_S", float, 1.0,
         "FleetSupervisor loop cadence in seconds (serving/"
         "controlplane.py): how often the background supervisor "
         "thread evaluates the SLO surface and acts (scale, ramp, "
         "rollback).  Manual `tick()` callers ignore this")
register("MXNET_CTL_UP_ROUNDS", int, 2,
         "Scale-up hysteresis: consecutive supervisor ticks with a "
         "firing shed-burn rule on a watched lane before the replica "
         "set grows by one.  Higher = slower to react, harder to flap")
register("MXNET_CTL_DOWN_ROUNDS", int, 6,
         "Scale-down hysteresis: consecutive QUIET ticks (no watched "
         "alert firing) before the replica set shrinks by one toward "
         "min_replicas.  HBM ledger pressure (any pool device past "
         "MXNET_CTL_HBM_PRESSURE committed) halves the requirement — "
         "idle capacity on a nearly-full ledger is the first thing "
         "to give back")
register("MXNET_CTL_COOLDOWN_S", float, 10.0,
         "Minimum seconds between supervisor scale transitions (and "
         "between emergency rebuilds): with the round hysteresis "
         "above this bounds the loop at <= 1 transition per direction "
         "per window, the no-flapping contract")
register("MXNET_CTL_HBM_PRESSURE", float, 0.9,
         "Committed/budget fraction past which a pool device counts "
         "as HBM-pressured for the supervisor's scale-down decision "
         "(unbudgeted devices never register pressure)")
register("MXNET_CTL_CANARY_FRACTION", float, 0.1,
         "Initial traffic fraction mirrored to a freshly-admitted "
         "canary version (ModelRegistry.register_version / "
         "FleetSupervisor.deploy); the supervisor ramps it from here")
register("MXNET_CTL_CANARY_STEP", float, 0.2,
         "Canary ramp increment: fraction added each time every SLO "
         "rule for the model stays quiet for a full observation "
         "window (MXNET_CTL_OBSERVE_ROUNDS ticks)")
register("MXNET_CTL_CANARY_MAX", float, 0.5,
         "Canary traffic ceiling: the ramp stops here, and one more "
         "fully-quiet observation window at the ceiling PROMOTES the "
         "version (refresh_params weight-swap onto the primary)")
register("MXNET_CTL_OBSERVE_ROUNDS", int, 3,
         "Canary observation window in supervisor ticks: the ramp "
         "advances (or promotes, at the ceiling) only after this many "
         "consecutive ticks with every rule for the model quiet; any "
         "firing model rule restarts the window")
register("MXNET_CTL_DEGRADE_S", float, 0.05,
         "Deterministic per-batch stall applied to an engine tainted "
         "by the model.bad_version fault site (outputs are also "
         "sign-flipped) — the knob the chaos scenarios size so the "
         "canary's labeled p99 provably breaches its rule")
register("MXNET_SERVE_BUILD_TIMEOUT_S", float, 120.0,
         "Bounded engine-build timeout for ModelRegistry.register / "
         "register_version / resize: a build (param replication + "
         "functionalization) that wedges past this raises the typed "
         "RegistrationTimeout, rolls the ledger hold back and leaves "
         "a flight-recorder event instead of holding the deploy path "
         "hostage.  0 disables the bound")
register("MXNET_GATE_REPORT_DIR", str, "",
         "Directory the CI gates (check_overhead/check_feed/"
         "check_serve/check_scaling) write per-run JSON artifacts to "
         "(per-trial numbers + pass/skip/inconclusive verdicts, "
         "auto-named <gate>-<ts>-p<pid>.json) so flake rates become a "
         "readable trend.  Empty = no artifact")
register("MXNET_INT64_TENSOR_SIZE", bool, False,
         "Large-tensor support: enable 64-bit index arithmetic so "
         "arrays past 2**31 elements index correctly (ref: the "
         "USE_INT64_TENSOR_SIZE build flag). Honored at import time "
         "only (flips jax_enable_x64 before any trace). Off by "
         "default for the reference's reason: wider index math costs "
         "speed/memory on every gather")
register("MXNET_REQTRACE", bool, True,
         "Per-request lifecycle journal (telemetry/reqtrace.py): every "
         "serving/generation request gets a compact phase-stamped "
         "record; tail outliers and terminal failures are promoted to "
         "exemplars with full waterfalls on dumps, history rows and "
         "firing SLO alerts.  On by default — the journal is pre-sized "
         "structs filled from stamps the engines already take, held "
         "to <2% by tools/check_overhead.py's serving trial")
register("MXNET_REQTRACE_RING", int, 512,
         "Per-engine request-journal ring size (retired records kept "
         "for snapshots/teletop).  Bounded deque: old records fall "
         "off; exemplars live in their own retention (below)")
register("MXNET_REQTRACE_WINDOW", int, 256,
         "Per-lane rolling window of completed-request e2e samples "
         "the promotion threshold (p99) is computed over.  Promotion "
         "needs at least 20 samples in the lane window first")
register("MXNET_REQTRACE_EXEMPLARS", int, 32,
         "Promoted exemplars retained per engine journal (the "
         "process-wide cross-engine set alerts attach from keeps the "
         "newest 64 regardless)")
register("MXNET_REQTRACE_PIN_P99_US", float, 0.0,
         "When > 0, replaces the rolling per-lane p99 promotion "
         "threshold with this fixed e2e value in µs — deterministic "
         "promotion for tests and drills.  0 = rolling threshold")
register("MXNET_MEMWATCH", bool, True,
         "Sampled per-device memory observatory (telemetry/"
         "memwatch.py): PJRT memory_stats (jax.live_arrays fallback "
         "on statless backends), tenant attribution against the "
         "serving ledger / KV pools / ZeRO plans, per-phase peak "
         "watermarks, and the mem-drift SLO rule's evidence.  On by "
         "default — sampling rides the exporter tick and dump/warmup "
         "transitions, never a request or step path; held to <2% by "
         "tools/check_overhead.py's memwatch serving trial")
register("MXNET_MEMWATCH_MIN_S", float, 0.25,
         "Probe throttle: an unforced memwatch.sample() within this "
         "many seconds of the previous sample returns it unchanged "
         "instead of re-probing (live_arrays scans are O(live "
         "buffers)) — phase transitions and forced OOM/dump/bench "
         "samples always probe; 0 disables the throttle (tests)")
register("MXNET_MEMWATCH_RING", int, 128,
         "Bounded ring of retained memwatch samples (teletop pane + "
         "dump block read the newest; watermarks aggregate across "
         "the whole run regardless)")
register("MXNET_MEMWATCH_DRIFT_FACTOR", float, 1.5,
         "slo.MemDriftRule threshold: a tenant whose measured "
         "resident bytes contradict its ledger commitment by more "
         "than this factor (either direction) fires the mem-drift "
         "alert and re-reconciles the ledger row")
register("MXNET_MEMWATCH_FRESH_S", float, 30.0,
         "Maximum age in seconds for a memwatch sample to count as "
         "FRESH: the controlplane HBM-pressure upgrade, the "
         "registry's stats() measured_bytes/drift columns and the "
         "drift rule all fall back to ledger estimates (or go "
         "unjudgeable) on staler samples")
register("MXNET_MEMWATCH_TOP", int, 5,
         "Top-N consumers carried on a firing mem-drift alert, the "
         "blackbox memwatch block and the memautopsy verdict table")
