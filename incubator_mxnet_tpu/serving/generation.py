"""Generation serving: KV-cached decode with continuous batching
(ISSUE 14 tentpole).

The serving stack so far (engine → lanes → registry → breaker) is
one-shot: submit a tensor, get a tensor.  Autoregressive generation —
*the* million-user workload — only existed as `contrib/text/decode`'s
host loop, which re-runs the whole network per emitted token (O(n²)
compute, no KV cache).  `GenerationEngine` makes generation a
first-class serving workload under the repo's compile-time-
specialization doctrine: the executable set is CLOSED and warmed ahead
of traffic, and every piece of dynamic behavior — who is in the batch,
at what length, with what prompt — is expressed as DATA flowing
through fixed-shape executables, never as shapes that would retrace.

**Executables** (all compiled by `warmup()` through
`telemetry.costs.metered_jit`, recompiles metered on `serve.traces`
exactly like the one-shot engine):

1. ``prefill`` — one signature per power-of-two PROMPT bucket
   (`MXNET_GEN_BUCKETS`): encode the padded prompt, produce one slot's
   decode cache.  Exactness under padding is the model's contract
   (`init_cache`): variable-length RNN state freezing + attention
   masks whose pad weights underflow to exactly 0, so a bucketed
   prompt decodes token-identically to the unpadded forward (the
   greedy-parity oracle in tests).
2. ``decode_step`` — ONE executable specialized to the engine's
   (slot-count bucket, max_len bucket): a fixed (S, …) batch advances
   every slot one token.  Its KV/state buffers are DONATED between
   steps (`donate_argnums` + the PR 10 `expect_donated` audit at
   build, plus a runtime no-copy probe on the first step — a backend
   that silently copies warns with the executable label and counts
   ``gen.donation_copy``).  Per-sequence state (cur position, last
   token, emitted tokens, the tokens it may still emit) lives in
   device arrays indexed by slot INSIDE the donated cache.
3. ``join`` — admit one prefilled request into a free slot: an
   indexed in-place write of the slot's row into every leaf of the
   donated cache (`lax.dynamic_update_slice` at the slot, axis 0), so
   an admission moves one slot's bytes whatever the slot count.  Slot
   and token budget come as one int32[2].  The warm-up's join runs the
   same no-copy probe as the first decode step.  Joins and retires
   never reshape anything.

**Who is live, on the device.**  The slot-major leaf ``left`` (S,) int32
holds the tokens each slot may still emit: ``join`` writes the admitted
request's budget, a step hands the model ``live = left > 0`` and leaves
``left = 0`` where it emitted ``eos``, ``left - 1`` elsewhere.  No
transfer a step keeps it: the host's rules stay the authority for
retiring a stream (eos, budget, ``max_len``, deadline, cancel), and
``left`` only has to cover them — every slot whose token the host takes
from a step was live on the device in that step.  A stream shed by its
deadline stays live on the device until its budget runs out or the slot
is joined again: it costs its rows, never a token.  A model whose step
reads only what is live (`TransformerNMT`) then pays for the slots that
hold a stream, not for the slots the engine holds.

**Continuous batching.**  The decode loop advances the fixed-slot
batch step by step.  A sequence that finishes (EOS / token budget /
deadline) frees its slot at the step boundary, and queued requests
join immediately — no drain barrier.  Admission order is the PR 8
`_LaneQueue`: strict priority across lanes, EDF within one, per-lane
occupancy quotas and per-tenant quotas shed excess work with the
existing typed errors (`Shed`/`QueueFull`/`DeadlineExceeded`); a
born-expired or infeasible-deadline request (prefill EWMA says it
cannot emit a first token in time) is shed before touching the
device.  ``continuous=False`` degrades to drain batching (a new batch
only forms when every slot is free) — the A/B baseline
`bench.py generate` and `tools/check_decode.py` measure TTFT against.

**The step dispatched early.**  Dispatch is asynchronous, and the host
needs a few milliseconds between one step's report and the next step's
dispatch (read-back, the per-slot loop, the call).  Where a step boundary
can decide nothing — every slot is taken by a stream that is in the step
in flight, and none of them is on its last token by budget or position —
the engine dispatches the next step BEFORE it waits for this one's
report (`_settled`), so the device goes from one step into the next.
With a free slot, or a stream about to end by its budget, it goes step
by step: an admission is never a step late.  A stream that ends by
``eos`` or its deadline under a step dispatched early is found one step
late; its slot computes one token more, which nobody reads.

**Streaming.**  `submit()` returns a `GenerationStream`: iterate it
for tokens as they are emitted (time-to-first-token and inter-token
latency land in the labeled percentile rings `gen.ttft_us` /
`gen.intertoken_us` split by lane), or call `.result()` for the final
token array.  `drain()`/`close()` resolve every stream exactly once.

**Observability.**  The engine writes the always-on phase log
(`telemetry/spans.py`: rows `(name, t0, t1, ident, parent, n)` on
`time.monotonic()`, mirrored to the profiler as `TraceAnnotation`s, so
a device trace names its idle gaps by these names):

- per scheduler round `gen.tick` (ident = tick number, n = live
  slots) and, with parent = that tick, `gen.admit` (n = requests
  admitted), per admitted request `gen.prefill` (pad + `device_put` +
  dispatch) and `gen.join` (dispatch), ident = the request's id;
  `gen.decode` (dispatch, n = live), `gen.sync` (the wait on the
  step's tokens) and `gen.emit` (the per-slot loop: push, observe,
  retire; n = tokens pushed).  A round with nothing queued and
  nothing live writes no row; the wait for work is one `gen.idle`.
- per request, ident = its id, parent = the tick that admitted it:
  `gen.req.queue` [enqueue, pop], `gen.req.admit` [pop, joined],
  `gen.req.first` [joined, first token pushed].  The same stamps fill
  the request journal (`reqtrace.Record`: `t_collect`, `t_exec`,
  `t_first`).

Dispatch is asynchronous: `gen.prefill`, `gen.join` and `gen.decode`
are the host's time to dispatch, and the device's time for all three
shows up in `gen.sync`.  The executables are named by role whatever
the cost label (`jit__traced_gen_prefill`, `_gen_join`, `_gen_decode`).
Beside the log: `gen.*` counters, a slot-occupancy gauge
(`gen.slots_live` ring + flight-recorder events on every join/retire),
and per-lane TTFT SLO targets (`slo_targets()`) that
`telemetry/slo.py`'s default generation rules alert on.

Model contract (``models/seq2seq.py``, ``models/transformer.py``, and for
the decoder-only models one shell, ``models/sparse_decoder.py``
``_ServedDecoder``), one for encoder-decoder and decoder-only models:

- ``init_cache(prompt, valid_len, max_len=, mem_len=)`` → dict of
  NDArray leaves, ALL slot-major (axis 0 = request), shapes a pure
  function of (prompt bucket, max_len, mem_len).  A stream starts at
  token ``bos``, position 0, unless the row says otherwise in two more
  leaves, ``start_tok`` and ``start_pos`` (B,): a decoder-only model's
  prefill IS its prompt, so it starts at the prompt's last token and
  position.  ``join`` writes the start into the slot with the row.
  A leaf need not have a time axis: a recurrent state a layer
  (`HybridDecoder`'s ``s`` and ``c``) is a slot-major leaf like any
  other, which a step rewrites whole.  Two clauses hold such a leaf to
  what rows of K/V get for free.  (1) The first decode step reads
  ``start_tok`` AGAIN: a row is rewritten with what it held, a
  recurrence would apply the token twice, so a decoder-only prefill
  hands over recurrent state as of BEFORE its prompt's last token.
  (2) A prompt is padded to its bucket: padded rows are never read,
  padding would run on through a recurrence, so the prompt's scans
  stop at ``valid_len``.  ``join`` rewrites every leaf of the slot, so
  a state ends with its stream.
- ``decode_step(tok, pos, cache, live)`` → (next-token logits (B, V),
  updated cache).  One token per slot per call; position is data, and
  so is ``live`` (B,) bool: the slots that hold a stream.  A model may
  skip the work of the others (their logits are then finite and mean
  nothing; nobody reads them) or ignore ``live``.
  A leaf ``counts`` (B, k) int32, if the cache has one, is what the
  step did for each slot under the k names of the model's
  ``step_counts``: it comes back with the step's tokens and the engine
  adds each column, summed over the live slots, to the counter of that
  name.
- A stream ends at ``eos``, at its token budget, or when its position
  reaches ``max_len``: a prompt that lives in the cache and its new
  tokens share the slot's ``max_len`` rows.  An engine built with
  ``eos=None`` has no end token: every stream runs to its budget.
"""
from __future__ import annotations

import queue
import threading
import time
import weakref
from concurrent.futures import Future

import numpy as _np

from .. import config as _cfg
from .. import fault
from ..context import Context, current_context
from ..monitor import events
from ..telemetry import costs as _costs
from ..telemetry import flightrec as _bb
from ..telemetry import reqtrace as _reqtrace
from ..telemetry import spans as _tele
from .engine import (DeadlineExceeded, EngineClosed, QueueFull, Shed,
                     _LaneQueue, _OverQuota, _parse_lane_quotas,
                     _parse_lanes)

__all__ = ["GenerationEngine", "GenerationStream",
           "project_generation_footprint"]

_END = object()          # stream sentinel: normal end

# Bytes of prefilled rows admitted between two decode steps (at least one
# row; two of the 356 MB rows of a 16-layer, 10 k-token slot; 37 of the
# 21 MB rows of a slot whose layers mostly hold a recurrent state).  Dispatch is
# asynchronous and a dispatched prefill holds its row and its temporaries
# from then on, so a tick that fills many free slots at once would hold them
# all: with rows of a few MB that is nothing, with rows of hundreds of MB it
# is the device's memory.  The step's sync at the end of a tick is what
# bounds the queue.
_ADMIT_BYTES = 768 << 20


def _parse_prompt_buckets(spec, max_len):
    """Power-of-two prompt-length buckets (`MXNET_GEN_BUCKETS`): the
    closed signature set prefill is warmed over.  Empty = 8, 16, …
    up to max_len (always at least one bucket)."""
    if spec and isinstance(spec, (list, tuple, set, frozenset)):
        bs = sorted({int(s) for s in spec})
    elif spec:
        bs = sorted({int(s) for s in str(spec).split(",") if s.strip()})
    else:
        bs, b = [], 8
        while b < int(max_len):
            bs.append(b)
            b *= 2
        bs.append(int(max_len))
        bs = sorted(set(bs))
    if not bs or bs[0] < 1:
        raise ValueError("generation prompt buckets must be positive "
                         "ints, got %r" % (spec,))
    return tuple(bs)


def _pure_method(block, method, training=False):
    """`parallel.functional.functionalize` for an arbitrary block
    METHOD over pytree inputs: returns
    ``pure(params_dict, *ivals) -> jax pytree`` where every jax-array
    leaf of ``ivals`` crosses the seam wrapped as NDArray and every
    NDArray leaf of the result is unwrapped.  The param swap /
    autograd / RNG discipline is the same as `functionalize` — this is
    the seam `init_cache`/`decode_step` trace through."""
    import jax
    from .. import autograd as _ag
    from .. import random as _rnd
    from ..gluon.block import _STATE
    from ..ndarray.ndarray import NDArray
    pd = block.collect_params()
    params = list(pd.values())

    def _wrap(v):
        # jax leaves (incl. tracers) cross wrapped; python scalars
        # (max_len/mem_len attrs) pass through untouched
        return NDArray(v) if isinstance(v, jax.Array) else v

    def _unwrap(v):
        return v._data if isinstance(v, NDArray) else v

    def pure(pvals, *ivals):
        saved = []
        for p in params:
            ctx0 = next(iter(p._data))
            saved.append((p, ctx0, p._data[ctx0]))
            p._data[ctx0] = NDArray(pvals[p.name], ctx=ctx0)
        states = []
        prev_state, _STATE.active = _STATE.active, states
        prev_rec = _ag.set_recording(False)
        prev_train = _ag.set_training(training)
        # trace-local RNG: needs_rng ops (the fused RNN) split a key at
        # trace time; without a pushed holder that split leaks a tracer
        # into the global key state.  Inference is deterministic (no
        # dropout), so a constant key is correct — and constant-folds.
        holder = _rnd.KeyHolder(jax.random.PRNGKey(0))
        _rnd.push_trace_key(holder)
        try:
            nd_in = jax.tree_util.tree_map(_wrap, ivals)
            out = getattr(block, method)(*nd_in)
        finally:
            _rnd.pop_trace_key()
            _ag.set_training(prev_train)
            _ag.set_recording(prev_rec)
            _STATE.active = prev_state
            for p, ctx0, orig in saved:
                p._data[ctx0] = orig
        return jax.tree_util.tree_map(
            _unwrap, out, is_leaf=lambda v: isinstance(v, NDArray))

    return pure


def _split_start(row, bos):
    """A prefilled row as the engine keeps it: the model's leaves under
    ``m``, and where the stream starts under ``tok``/``pos`` — the
    row's own ``start_tok``/``start_pos`` leaves, or (bos, 0)."""
    import jax
    import jax.numpy as jnp
    m = dict(row)
    n = jax.tree_util.tree_leaves(m)[0].shape[0]
    tok = m.pop("start_tok", jnp.full((n,), bos, jnp.int32))
    pos = m.pop("start_pos", jnp.zeros((n,), jnp.int32))
    return {"m": m, "tok": tok, "pos": pos}


def project_generation_footprint(block, slots, max_len, buckets,
                                 vocab_hint=None, temp_factor=None):
    """Projected per-device HBM bytes for GENERATION serving: param
    bytes + ``slots × kv_bytes_per_slot`` (the term one-shot admission
    has no analogue for — HBM now scales with CONCURRENT SEQUENCES,
    not just model size) + a temp-factor margin over the decode-step
    activations.  KV bytes come from `jax.eval_shape` over the
    model's own ``init_cache`` — a trace, never a compile.  Returns
    (total_bytes, detail) with the KV term broken out so an
    `AdmissionDenied` can NAME it."""
    import jax
    from .registry import _param_bytes
    if temp_factor is None:
        temp_factor = float(_cfg.get("MXNET_SERVE_HBM_TEMP_FACTOR"))
    pb = _param_bytes(block)
    mem_len = int(max(buckets))
    pure = _pure_method(block, "init_cache")
    pvals = {p.name: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for p, v in ((p, p.data()._data)
                          for p in block.collect_params().values())}
    src = jax.ShapeDtypeStruct((1, mem_len), _np.int32)
    vl = jax.ShapeDtypeStruct((1,), _np.int32)
    cache = jax.eval_shape(lambda pv, s, v: _split_start(pure(
        pv, s, v, int(max_len), mem_len), 0)["m"], pvals, src, vl)
    kv_slot = sum(int(_np.prod(a.shape[1:]))
                  * _np.dtype(a.dtype).itemsize
                  for a in jax.tree_util.tree_leaves(cache))
    kv_total = int(slots) * kv_slot
    # decode activations are O(slots × vocab) for the logits row plus
    # the per-layer working set the temp factor covers.  The vocab is
    # DERIVED from the model's own decode_step output aval (another
    # eval_shape — still a trace) unless hinted; without it the
    # margin would be vacuously zero and admission would only learn
    # the working set at warmup-reconcile time, after the OOM-prone
    # first compile
    vocab = int(vocab_hint or 0)
    if not vocab:
        try:
            step = _pure_method(block, "decode_step")
            tok = jax.ShapeDtypeStruct((1,), _np.int32)
            logits, _ = jax.eval_shape(
                step, pvals, tok, vl, cache,
                jax.ShapeDtypeStruct((1,), _np.bool_))
            vocab = int(logits.shape[-1])
        except Exception:       # noqa: BLE001 — degrade to KV-only
            pass
    act = int(slots) * max(vocab, 1) * 4
    total = int(pb + kv_total + temp_factor * act)
    return total, {"param_bytes": int(pb),
                   "kv_bytes_per_slot": int(kv_slot),
                   "slots": int(slots),
                   "kv_bytes": int(kv_total),
                   "max_len": int(max_len),
                   "mem_len": mem_len,
                   "temp_factor": float(temp_factor)}


class GenerationStream:
    """Streaming handle for one generation request.

    - Iterate for tokens as they are emitted (``for tok in stream``).
    - ``result(timeout)`` blocks for the FULL sequence (np.int32
      array) or raises the terminal error (DeadlineExceeded /
      EngineClosed / Shed).
    - ``future`` is the underlying `concurrent.futures.Future`
      (resolved exactly once by the engine's drain/close contract).
    """

    def __init__(self, lane, tenant):
        self.lane = lane
        self.tenant = tenant
        self.future = Future()
        self._q = queue.Queue()
        self._tokens = []
        self._t_first = None

    # -- engine side ---------------------------------------------------
    def _push(self, tok):
        self._tokens.append(int(tok))
        self._q.put(int(tok))

    def _finish(self, exc=None):
        """Resolve exactly once (idempotent — the close() flush may
        race a retire)."""
        if self.future.done():
            return False
        try:
            if exc is not None:
                self.future.set_exception(exc)
            else:
                self.future.set_result(
                    _np.asarray(self._tokens, _np.int32))
        except Exception:       # noqa: BLE001 — cancelled by caller
            events.incr("gen.cancelled")
        self._q.put(exc if exc is not None else _END)
        return True

    # -- caller side ---------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is _END:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item

    def tokens(self):
        """Tokens emitted so far (list copy, non-blocking)."""
        return list(self._tokens)

    def result(self, timeout=None):
        return self.future.result(timeout)

    def done(self):
        return self.future.done()


class _GenRequest:
    __slots__ = ("prompt", "max_new", "deadline", "lane", "tenant",
                 "stream", "t_enq", "t_exec", "tick", "future", "n",
                 "acct", "rec", "rid")

    def __init__(self, prompt, max_new, deadline, lane, tenant):
        self.prompt = prompt
        self.max_new = max_new
        self.t_enq = time.monotonic()
        self.deadline = None if deadline is None \
            else self.t_enq + float(deadline)
        self.lane = lane
        self.tenant = tenant
        self.stream = GenerationStream(lane, tenant)
        self.future = self.stream.future    # _LaneQueue/engine duck type
        self.n = 1
        self.acct = False       # queue/tenant accounting released once
        self.t_exec = None      # joined into its slot (phase log)
        self.tick = None        # the tick that admitted it
        self.rec = None         # reqtrace.Record (journal lifecycle)
        self.rid = None         # the journal's id, or the engine's own


class _Slot:
    __slots__ = ("req", "emitted", "t_last", "pos")

    def __init__(self, req):
        self.req = req
        self.emitted = 0
        self.t_last = None      # last token wall (inter-token meter)
        self.pos = None         # the position its last token was read at


class _Flight:
    """A dispatched decode step whose report the host has not read: the
    report, who sat in each slot when it was dispatched, and when."""
    __slots__ = ("report", "seats", "t0")

    def __init__(self, report, seats, t0):
        self.report = report
        self.seats = seats      # [(slot index, _Slot)]
        self.t0 = t0


class GenerationEngine:
    """KV-cached autoregressive decode with continuous batching over a
    fixed slot set.

    block: a model implementing ``init_cache``/``decode_step`` (the
        explicit-cache contract — `models.Seq2Seq`,
        `models.TransformerNMT`, `models.SparseDecoder`,
        `models.HybridDecoder`).  Parameters
        must be initialized.
    bos / eos: special token ids (decode starts from bos unless the
        prefilled row names its own start; an emitted eos retires the
        sequence; eos None: no token does, a stream ends at its budget
        or max_len, the fixed answer lengths of a throughput benchmark).
    slots / max_len: the (slot-count bucket, max_len bucket) the ONE
        decode executable is specialized to (`MXNET_GEN_SLOTS`,
        `MXNET_GEN_MAX_LEN`).  max_len bounds prompt length AND
        emitted tokens per request, and a stream retires when its
        position reaches it.
    prompt_buckets: closed prompt-length bucket set
        (`MXNET_GEN_BUCKETS`; empty = powers of two up to max_len).
    continuous: True = continuous batching (join at step boundaries);
        False = drain batching (the measured baseline).

    Lifecycle: construct → ``warmup()`` → ``submit()`` traffic →
    ``drain()`` / ``close()``.
    """

    def __init__(self, block, bos, eos, ctx=None, slots=None,
                 max_len=None, prompt_buckets=None, queue_cap=None,
                 lanes=None, lane_quotas=None, tenant_quota=None,
                 continuous=True, cost_label=None, max_new_default=None):
        self._block = block
        for m in ("init_cache", "decode_step"):
            if not callable(getattr(block, m, None)):
                raise TypeError(
                    "generation needs a model with the explicit-cache "
                    "decode contract (missing %r) — see "
                    "models/seq2seq.py / models/transformer.py" % m)
        self._bos = int(bos)
        self._eos = None if eos is None else int(eos)
        # what fills a slot's token and record before it holds a stream
        self._pad = self._bos if eos is None else self._eos
        # the names of the model's per-slot counts (decode_step contract)
        self._count_names = tuple(getattr(block, "step_counts", ()))
        self._ctx = ctx if isinstance(ctx, Context) else (
            Context(*ctx) if ctx is not None else current_context())
        self._S = int(slots if slots is not None
                      else _cfg.get("MXNET_GEN_SLOTS"))
        self._L = int(max_len if max_len is not None
                      else _cfg.get("MXNET_GEN_MAX_LEN"))
        if self._S < 1 or self._L < 2:
            raise ValueError("need slots >= 1 and max_len >= 2")
        blk_max = getattr(block, "_max_length", None)
        if blk_max is not None and self._L > int(blk_max):
            raise ValueError(
                "max_len %d exceeds the model's positional table "
                "(max_length=%d)" % (self._L, int(blk_max)))
        self._buckets = _parse_prompt_buckets(
            prompt_buckets if prompt_buckets is not None
            else _cfg.get("MXNET_GEN_BUCKETS"), self._L)
        self._mem_len = int(self._buckets[-1])
        self._max_new_default = int(max_new_default or self._L)
        self._continuous = bool(continuous)
        self._label = str(cost_label or "serve.gen")
        self._journal = _reqtrace.journal(
            "gen",
            self._label.split(":", 1)[1]
            if ":" in self._label else self._label)

        cap = max(1, int(queue_cap if queue_cap is not None
                         else _cfg.get("MXNET_SERVE_QUEUE_CAP")))
        self._lanes = _parse_lanes(
            lanes if lanes is not None
            else _cfg.get("MXNET_SERVE_LANES"))
        self._lane_caps = _parse_lane_quotas(
            lane_quotas if lane_quotas is not None
            else _cfg.get("MXNET_SERVE_LANE_QUOTAS"), self._lanes, cap)
        self._q = _LaneQueue(cap, self._lanes, self._lane_caps)
        self._tenant_quota = int(
            tenant_quota if tenant_quota is not None
            else _cfg.get("MXNET_SERVE_TENANT_QUOTA"))
        self._tenant_q = {}

        self._lock = threading.Lock()
        self._work = threading.Event()  # submit → wake the idle loop
        from collections import deque
        self._lane_deadline_s = {}      # lane -> deque of rel deadlines
        self._deque_cls = deque
        self._slots = [None] * self._S  # host mirror: _Slot | None
        self._prefill_ewma = {}         # bucket -> prefill seconds
        self._step_ewma = None          # decode-step seconds
        self._steps = 0
        self._ticks = 0
        self._ahead = None              # the _Flight dispatched early
        self._t_report = 0.0            # when the last report arrived
        self._thread = None
        self._draining = False
        self._stop = False
        self._closed = False
        self._warm = False
        self._donation_checked = False

        # deferred-shape params (the LSTM flat vector before a first
        # forward): prime with one tiny teacher-forced forward so
        # extract_params sees concrete shapes
        try:
            from ..parallel.functional import extract_params
            extract_params(block)
        except Exception:               # noqa: BLE001
            from .. import nd
            src = nd.array(_np.full((1, int(self._buckets[0])),
                                    self._bos, _np.int32), ctx=self._ctx)
            tgt = nd.array(_np.full((1, 1), self._bos, _np.int32),
                           ctx=self._ctx)
            block(src, tgt)
        self._build_executables()
        self._cache = None              # device cache (built on warmup
                                        # or first traffic)
        _bb.install_crash_hooks()

    # -- executable construction ---------------------------------------
    def _build_executables(self):
        import jax
        import jax.numpy as jnp
        from ..parallel.functional import extract_params
        block = self._block
        L = self._L
        # not `self`: a closure that held the engine would tie it into a
        # cycle with its own executables, and its cache would wait for
        # the collector
        bos, eos, pad = self._bos, self._eos, self._pad
        pure_init = _pure_method(block, "init_cache")
        pure_step = _pure_method(block, "decode_step")
        mem_len = self._mem_len
        max_len = self._L

        def prefill(params, src, valid):
            # trace-time side effect only — the recompile meter the
            # zero-recompile contract is asserted on (the same
            # serve.traces the one-shot engine meters)
            events.incr("serve.traces")
            row = pure_init(params, src, valid, max_len, mem_len)
            with _costs.part("cache"):
                return _split_start(row, bos)

        def decode_step(params, cache):
            events.incr("serve.traces")
            tok, pos, left = cache["tok"], cache["pos"], cache["left"]
            logits, new_m = pure_step(params, tok, pos, cache["m"],
                                      left > 0)
            with _costs.part("head"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # the engine's own bookkeeping is part `cache`, as `join` is
            with _costs.part("cache"):
                return _after_step(cache, new_m, nxt, pos, left)

        def _after_step(cache, new_m, nxt, pos, left):
            # the device-resident emitted-token record (ISSUE 14
            # contract: per-sequence state lives in device arrays
            # indexed by slot).  Host streaming is authoritative
            # today; this S×L int32 row is what device-side consumers
            # (batched end-of-sequence D2H, future sampling/beam
            # state) read without a per-step host hop
            oh = jax.nn.one_hot(pos, L, dtype=jnp.int32)
            out = cache["out"] * (1 - oh) + nxt[:, None] * oh
            # what the host reads of a step, in one array: the token,
            # the position it was read at, the model's counts
            report = jnp.concatenate(
                [nxt[:, None], pos[:, None],
                 new_m.get("counts", jnp.zeros((nxt.shape[0], 0),
                                               jnp.int32))], axis=1)
            return report, {
                "m": new_m, "tok": nxt,
                # clamp keeps dead slots' one-hot writes in range; a
                # LIVE slot never reaches the clamp (the host retires
                # at max_new <= max_len)
                "pos": jnp.minimum(pos + 1, L - 1).astype(jnp.int32),
                # the tokens a slot may still emit: what the model is
                # told is live.  The host's rules retire a stream (eos,
                # budget, max_len, deadline, cancel); this only has to
                # cover them: device-live ⊇ host-live at every step
                "left": (jnp.maximum(left - 1, 0) if eos is None else
                         jnp.where(nxt == eos, 0, jnp.maximum(left - 1, 0))
                         ).astype(jnp.int32),
                "out": out}

        def join(cache, row, seat):
            events.incr("serve.traces")
            slot, budget = seat[0], seat[1]

            def put(c, r):
                # every leaf is slot-major: the slot's row is one
                # contiguous block, written in place into the donated
                # leaf — the other S-1 slots are never read
                return jax.lax.dynamic_update_slice(
                    c, r.astype(c.dtype), (slot,) + (0,) * (c.ndim - 1))

            with _costs.part("cache"):
                return {"m": jax.tree_util.tree_map(put, cache["m"],
                                                    row["m"]),
                        "tok": put(cache["tok"], row["tok"]),
                        "pos": put(cache["pos"], row["pos"]),
                        "left": put(cache["left"], budget[None]),
                        "out": put(cache["out"],
                                   jnp.full((1, L), pad, jnp.int32))}

        # prefill: one signature per prompt bucket, warmed; decode
        # and join donate the cache — the PR 10 audit arms the
        # donation contract at build time, the runtime probe below
        # proves no silent copy on the live path
        # the role names the executable, whatever the user's cost label
        self._prefill = _costs.metered_jit(
            prefill, label=self._label + ":prefill", kind="serve",
            role="gen_prefill")
        self._decode = _costs.metered_jit(
            decode_step, donate_argnums=(1,),
            label=self._label + ":decode_step", kind="serve",
            expect_donated=(1,), role="gen_decode")
        self._join = _costs.metered_jit(
            join, donate_argnums=(0,), label=self._label + ":join",
            kind="serve", expect_donated=(0,), role="gen_join")
        dev = self._ctx.jax_device
        self._params = {n: jax.device_put(v, dev)
                        for n, v in extract_params(block).items()}

    def _init_cache_arrays(self):
        """The engine's base device cache: zeros of the decode
        signature (model leaves slot-major at S, plus the per-slot
        tok/pos/out state arrays).  Also the TERMINAL-failure reset:
        a decode/join executable that died mid-donation leaves deleted
        buffers behind — rebuilding here keeps the engine serviceable
        (running sequences were already failed by the caller)."""
        import jax
        import jax.numpy as jnp
        S, L = self._S, self._L
        pure = _pure_method(self._block, "init_cache")
        pvals = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n, v in self._params.items()}
        src = jax.ShapeDtypeStruct((1, self._mem_len), _np.int32)
        vl = jax.ShapeDtypeStruct((1,), _np.int32)
        row = jax.eval_shape(lambda pv, s, v: _split_start(pure(
            pv, s, v, self._L, self._mem_len), 0)["m"], pvals, src, vl)
        dev = self._ctx.jax_device
        m = jax.tree_util.tree_map(
            lambda a: jax.device_put(
                jnp.zeros((S,) + tuple(a.shape[1:]), a.dtype), dev),
            row)
        self._cache = {
            "m": m,
            "tok": jax.device_put(
                jnp.full((S,), self._pad, jnp.int32), dev),
            "pos": jax.device_put(jnp.zeros((S,), jnp.int32), dev),
            "left": jax.device_put(jnp.zeros((S,), jnp.int32), dev),
            "out": jax.device_put(
                jnp.full((S, L), self._pad, jnp.int32), dev)}
        self._slot_bytes = sum(
            int(_np.prod(a.shape[1:])) * _np.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(self._cache))

    def kv_cache_bytes(self):
        """Total device bytes held by the slot cache (the KV term of
        generation admission), the per-slot share, and the devices the
        leaves actually sit on."""
        import jax
        if self._cache is None:
            self._init_cache_arrays()
        leaves = jax.tree_util.tree_leaves(self._cache)
        total = sum(int(_np.prod(a.shape))
                    * _np.dtype(a.dtype).itemsize for a in leaves)
        return {"total": total, "per_slot": total // self._S,
                "slots": self._S,
                "devices": sorted({"%s:%d" % (d.platform, d.id)
                                   for a in leaves
                                   for d in a.devices()})}

    # -- warmup ---------------------------------------------------------
    def warmup(self):
        """Compile the WHOLE executable set before traffic:
        one prefill per prompt bucket, the join, and the (S, max_len)
        decode step — after it `serve.traces` stays flat under any mix
        of prompt lengths and batch membership (the zero-recompile
        contract).  Returns a summary dict."""
        import jax
        t0 = time.monotonic()
        per_bucket = {}
        try:
            # same deterministic OOM drill + forensic catch as the
            # one-shot engine's warmup: the KV slot cache allocated
            # here is exactly the residency an OOM dump must attribute
            fault.maybe_raise(
                "serve.oom", 0, msg="RESOURCE_EXHAUSTED: out of "
                "memory while warming %r (injected)" % self._label)
            if self._cache is None:
                self._init_cache_arrays()
            dev = self._ctx.jax_device
            for b in self._buckets:
                src = jax.device_put(
                    _np.full((1, b), self._bos, _np.int32), dev)
                vl = jax.device_put(_np.full((1,), b, _np.int32), dev)
                tb = time.monotonic()
                row = self._prefill(self._params, src, vl)
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(row)[0])
                per_bucket[b] = round(time.monotonic() - tb, 4)
            old_probe = self._probe_leaf()
            # slot 0 with a budget of one token: the step below spends
            # it, and the slot is dead again when traffic starts
            self._cache = self._join(
                self._cache, row,
                jax.device_put(_np.array([0, 1], _np.int32), dev))
            self._donation_probe(old_probe, "join")
            nxt, self._cache = self._decode(self._params, self._cache)
            _np.asarray(nxt)            # sync
        except Exception as e:
            from ..telemetry import memwatch as _mw
            _mw.guard_oom("gen.warmup", e)
            raise
        self._warm = True
        events.incr("gen.warmups")
        # probe row from the warmup's own measured walls (ISSUE 19
        # satellite: probe writers outside bench/) — autotune evidence
        # for the prompt-bucket ladder, durable when history is on
        try:
            from ..compile import autotune as _autotune
            if per_bucket:
                _autotune.note_probe(
                    "gen_buckets", self._label,
                    ",".join(str(b) for b in self._buckets),
                    sum(per_bucket.values()) * 1e6,
                    source="gen.warmup", slots=self._S)
        except Exception:               # noqa: BLE001
            pass
        return {"prompt_buckets": list(self._buckets),
                "slots": self._S, "max_len": self._L,
                "wall_s": round(time.monotonic() - t0, 3),
                "bucket_wall_s": per_bucket,
                "kv_cache": self.kv_cache_bytes(),
                "traces": events.get("serve.traces")}

    # -- submission ------------------------------------------------------
    def _shed_mark(self, lane, tenant, reason, deadline=False):
        events.incr("gen.rejected")
        if deadline:
            events.incr("gen.deadline_expired")
        events.incr("gen.shed")
        events.incr("gen.shed", labels={"lane": lane or "-",
                                        "reason": reason})
        if tenant is not None:
            events.incr("gen.shed", labels={"tenant": tenant})

    def _shed(self, lane, tenant, reason, msg):
        self._shed_mark(lane, tenant, reason)
        raise Shed(msg)

    def submit(self, prompt, max_new_tokens=None, deadline=None,
               lane=None, tenant=None):
        """Enqueue one generation request.

        prompt: 1-D int token sequence (list/np array), length ≤ the
            largest prompt bucket.
        max_new_tokens: emitted-token budget (default: the engine's
            max_len bucket).
        deadline: seconds from now for the FULL generation; expiry —
            even mid-decode — resolves the stream with
            DeadlineExceeded and frees the slot.
        Returns a `GenerationStream`.  Raises QueueFull / Shed /
        DeadlineExceeded / EngineClosed synchronously.
        """
        if fault.should_fire("serve.enqueue"):
            events.incr("gen.rejected")
            raise QueueFull("injected enqueue fault (serve.enqueue)")
        prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.size > self._buckets[-1]:
            raise ValueError(
                "prompt of %d tokens exceeds the largest prompt "
                "bucket (%d); the bucket set is closed by design "
                "(MXNET_GEN_BUCKETS)" % (prompt.size, self._buckets[-1]))
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self._max_new_default)
        if not 0 < max_new <= self._L:
            raise ValueError("max_new_tokens must be in [1, %d] (the "
                             "max_len bucket)" % self._L)
        lane = self._lanes[0] if lane is None else str(lane)
        if lane not in self._lane_caps:
            raise ValueError("unknown lane %r (engine lanes: %s)"
                             % (lane, ",".join(self._lanes)))
        tenant = str(tenant) if tenant is not None else None
        req = _GenRequest(prompt, max_new, deadline, lane, tenant)
        req.rec = self._journal.start(req.t_enq, lane, tenant)
        req.rid = req.rec.rid if req.rec is not None \
            else _reqtrace.next_rid()
        if req.deadline is not None and req.deadline <= req.t_enq:
            self._shed_mark(lane, tenant, "deadline", deadline=True)
            exc = DeadlineExceeded("deadline is not in the future")
            rec, req.rec = req.rec, None
            self._journal.retire(rec, exc=exc)
            raise exc
        try:
            with self._lock:
                if self._closed or self._draining:
                    events.incr("gen.rejected")
                    raise EngineClosed("engine is draining/closed")
                if tenant is not None and self._tenant_quota > 0 and \
                        self._tenant_q.get(tenant, 0) >= \
                        self._tenant_quota:
                    self._shed(
                        lane, tenant, "tenant_quota",
                        "tenant %r over quota (%d queued, cap %d)"
                        % (tenant, self._tenant_q.get(tenant, 0),
                           self._tenant_quota))
                try:
                    self._q.put_nowait(req)
                except _OverQuota as oq:
                    self._shed(
                        lane, tenant, "lane_quota",
                        "lane %r over quota (%d queued, cap %d); "
                        "excess work is shed under overload — see "
                        "MXNET_SERVE_LANE_QUOTAS"
                        % (oq.lane, oq.depth, oq.cap))
                except queue.Full:
                    events.incr("gen.rejected")
                    raise QueueFull(
                        "generation queue at capacity (%d); retry "
                        "later or raise MXNET_SERVE_QUEUE_CAP"
                        % self._q.maxsize)
                if tenant is not None:
                    self._tenant_q[tenant] = \
                        self._tenant_q.get(tenant, 0) + 1
                if deadline is not None:
                    dq = self._lane_deadline_s.get(lane)
                    if dq is None:
                        dq = self._lane_deadline_s[lane] = \
                            self._deque_cls(maxlen=256)
                    dq.append(float(deadline))
        except (Shed, QueueFull, EngineClosed) as e:
            # synchronous refusals never reach _resolve — this is
            # their journal retire point
            rec, req.rec = req.rec, None
            self._journal.retire(rec, exc=e)
            raise
        events.incr("gen.requests")
        events.incr("gen.requests", labels={"lane": lane})
        if tenant is not None:
            events.incr("gen.requests", labels={"tenant": tenant})
        self._ensure_loop()
        self._work.set()
        return req.stream

    def _ensure_loop(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=GenerationEngine._decode_loop,
                    args=(weakref.ref(self),), daemon=True,
                    name="GenDecodeLoop")
                self._thread.start()

    # -- decode loop -----------------------------------------------------
    @staticmethod
    def _decode_loop(ref):
        """Weakref-held loop (the dispatcher pattern): an engine
        dropped without close() lets this thread retire at its next
        poll instead of pinning the params + KV cache forever."""
        eng0 = ref()
        if eng0 is None:
            return
        wake = weakref.ref(eng0._work)  # the Event may outlive checks
        del eng0                        # but must not pin the engine
        waiting = None                  # the open gen.idle phase
        while True:
            eng = ref()
            if eng is None:
                return
            try:
                state = eng._tick()
                if state == "closed":
                    # a request this thread popped/joined after
                    # close()'s own sweep must still resolve — the
                    # flush is idempotent, so both sides may run it
                    eng._flush_leftovers()
                    return
                idle = state == "idle"
            except Exception as e:      # noqa: BLE001 — the loop must
                import logging          # survive anything; slots are
                logging.getLogger(__name__).exception(
                    "generation decode loop error (recovered)")
                events.incr("gen.loop_errors")
                _bb.record("fault", "gen.loop",
                           error=type(e).__name__)
                _bb.crash_dump("gen.loop", e)
                idle = True
            finally:
                del eng
            if idle:
                # block on the submit-side event, not a poll: TTFT
                # must not pay an idle-loop sleep quantum.  The
                # strong ref lapsed above, so an abandoned engine
                # still GCs (wait() wakes on timeout and re-derefs).
                ev = wake()
                if ev is not None:
                    # one gen.idle row per idle period, not per poll:
                    # an engine left idle must not fill the ring
                    if waiting is None:
                        waiting = _tele.phase("gen.idle").start()
                    if ev.wait(0.05):
                        ev.clear()
                        waiting.stop()
                        waiting = None

    def _live(self):
        return [i for i, s in enumerate(self._slots) if s is not None]

    def _free(self):
        return [i for i, s in enumerate(self._slots) if s is None]

    def _tick(self):
        """One scheduler round: admit into free slots at this step
        boundary, then advance the decode batch one token.  Returns
        'ran' | 'idle' | 'closed'.  (The loop terminates only through
        the _stop check here; close() flips it and then both sides
        run the idempotent leftover flush.)"""
        if self._stop:
            return "closed"
        if not self._q.qsize() and not any(self._slots):
            return "idle"               # nothing to do: no row
        self._ticks += 1
        with _tele.phase("gen.tick", self._ticks) as tick:
            self._admit(tick.ident)
            live = self._live()
            tick.n = len(live)
            if not live:
                return "idle"
            self._step(live, tick.ident)
        return "ran"

    def _admit(self, tick):
        """Fill free slots from the lane queue.  Continuous mode joins
        whenever a slot is free; drain mode only when EVERY slot is
        free (the baseline the TTFT comparison measures against)."""
        free = self._free()
        if not free:
            return
        if not self._continuous and len(free) != self._S:
            return
        if self._cache is None:
            self._init_cache_arrays()
        room = max(1, _ADMIT_BYTES // self._slot_bytes)
        with _tele.phase("gen.admit", parent=tick) as admit:
            while free and admit.n < room:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    return
                t_collect = time.monotonic()    # queue phase ends here
                _tele.phase_at("gen.req.queue", req.t_enq, t_collect,
                               req.rid, tick)
                if req.rec is not None:
                    req.rec.t_collect = t_collect
                req.tick = tick
                slot = free.pop(0)
                if self._admit_one(req, slot, t_collect):
                    admit.n += 1
                else:
                    free.insert(0, slot)    # shed — the slot stays free

    def _admit_one(self, req, slot, now):
        """Prefill + join one request into `slot`.  Returns True when
        the slot was taken.  Sheds born-expired and
        infeasible-deadline requests (prefill EWMA + one step says no
        first token can land in time) with the typed errors."""
        if self._closed or self._stop:
            # a close() raced the pop: resolve, never strand — the
            # accounting flag keeps this exactly-once against the
            # close-side flush
            self._resolve(req, exc=EngineClosed(
                "engine closed before dispatch"))
            return False
        bucket = self._bucket_for(req.prompt.size)
        if req.rec is not None:
            req.rec.bucket = bucket
        if req.deadline is not None:
            est = self._prefill_ewma.get(bucket, 0.0) \
                + (self._step_ewma or 0.0)
            if now + est * 1.25 > req.deadline:
                self._shed_mark(req.lane, req.tenant, "deadline",
                                deadline=True)
                self._resolve(req, exc=DeadlineExceeded(
                    "deadline %s before the first token could land "
                    "(prefill estimate %.3fs)"
                    % ("expired" if now > req.deadline
                       else "infeasible", est)))
                return False
        if not req.stream.future.set_running_or_notify_cancel():
            events.incr("gen.cancelled")
            rec, req.rec = req.rec, None
            self._journal.retire(rec, status="cancelled",
                                 reason="cancelled while queued")
            self._retire_accounting(req)
            return False
        import jax
        dev = self._ctx.jax_device
        try:
            with _tele.phase("gen.prefill", req.rid, req.tick,
                             int(req.prompt.size)) as pre:
                padded = _np.zeros((1, bucket), _np.int32)
                padded[0, :req.prompt.size] = req.prompt
                fault.maybe_raise("serve.infer", step=self._steps)
                row = self._prefill(
                    self._params, jax.device_put(padded, dev),
                    jax.device_put(
                        _np.array([req.prompt.size], _np.int32), dev))
        except Exception as e:          # noqa: BLE001 — prefill does
            events.incr("gen.failed")   # not donate: only THIS request
            self._resolve(req, exc=e)   # fails, the engine survives
            return False
        if self._cache is None:
            self._init_cache_arrays()
        try:
            with _tele.phase("gen.join", req.rid, req.tick) as join:
                self._cache = self._join(
                    self._cache, row,
                    jax.device_put(_np.array([slot, req.max_new],
                                             _np.int32), dev))
        except Exception as e:          # noqa: BLE001 — join DONATES
            events.incr("gen.failed")   # the cache: running slots lose
            self._resolve(req, exc=e)   # their state too — fail them,
                                        # rebuild, stay serviceable
            for i in self._live():
                self._retire(i, exc=EngineClosed(
                    "slot state lost to a failed join (%s)"
                    % type(e).__name__))
            self._init_cache_arrays()
            _bb.record("gen", "join_failed", error=type(e).__name__)
            return False
        req.t_exec = join.t1            # the admit phase ends here
        _tele.phase_at("gen.req.admit", now, req.t_exec, req.rid,
                       req.tick)
        if req.rec is not None:
            req.rec.t_exec = req.t_exec
        dt = join.t1 - pre.t0
        prev = self._prefill_ewma.get(bucket)
        self._prefill_ewma[bucket] = dt if prev is None \
            else 0.3 * dt + 0.7 * prev
        events.observe_time("gen.prefill_us", dt)
        events.incr("gen.prefills")
        events.incr("gen.joins")
        self._slots[slot] = _Slot(req)
        self._occupancy_event("join", slot, req)
        return True

    def _bucket_for(self, n):
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _step(self, live, tick):
        """Advance every live slot one token; stream, then retire
        finished sequences at this boundary.  A terminal decode
        failure fails every LIVE sequence (typed, exactly once) and
        rebuilds the cache — donated buffers cannot be retried.

        The step read here is the one dispatched early in the last
        tick, if there is one.  And before the host waits for it, the
        NEXT step is dispatched early whenever this boundary can decide
        nothing (`_settled`): the device then goes from one step into
        the next while the host reads, emits and dispatches."""
        flight, self._ahead = self._ahead, None
        if flight is not None and not any(
                self._slots[i] is slot for i, slot in flight.seats):
            flight = None       # every stream it ran over has ended
        if flight is None:
            flight = self._dispatch(live, tick, self._steps)
            if flight is None:
                return
        if self._settled(flight):
            self._ahead = self._dispatch(live, tick, self._steps + 1)
            if self._ahead is None:     # failed: every stream is failed,
                return                  # the cache rebuilt
        try:
            with _tele.phase("gen.sync", self._steps, tick) as sync:
                report = _np.asarray(flight.report)     # (S, 2 + k):
                                                        # the device's time
        except Exception as e:              # noqa: BLE001
            return self._step_failed(live, e)
        # a step that waited in line behind its predecessor took what
        # passed since that one's report, not since its own dispatch
        dt = sync.t1 - max(flight.t0, self._t_report)
        self._t_report = sync.t1
        with _tele.phase("gen.emit", self._steps, tick) as emit:
            emit.n = self._emit(flight.seats, report, dt, emit.t0)

    def _dispatch(self, live, tick, step):
        """Dispatch decode step number `step` over the donated cache.
        Returns its _Flight, or None after a terminal failure."""
        from ..parallel.resilience import retry_transient
        with _tele.phase("gen.decode", step, tick, len(live)) as decode:
            # injected transient faults fire HOST-side (before the
            # executable), so the retry budget is donation-safe;
            # serve.decode_slow stalls a step (deadline/straggler
            # tests) without failing it
            fault.maybe_slow("serve.decode_slow", step=step)
            retry_transient(
                lambda: fault.maybe_raise("serve.infer", step=step),
                what="gen.decode_step", event="gen.retries")
            old_probe = None
            if not self._donation_checked:
                old_probe = self._probe_leaf()
            try:
                nxt, self._cache = self._decode(self._params,
                                                self._cache)
            except Exception as e:          # noqa: BLE001
                self._step_failed(live, e)
                return None
            if old_probe is not None:
                self._donation_checked = True
                self._donation_probe(old_probe, "decode_step")
        return _Flight(nxt, [(i, self._slots[i]) for i in live],
                       decode.t0)

    def _settled(self, flight):
        """True when the boundary after `flight` can decide nothing, so
        the step after it may be dispatched before its report is read:
        every slot is taken by a stream that is in `flight`, and none of
        them can end there by its budget or its position.  (A stream
        that ends by `eos` or its deadline is then found a step late:
        its slot computes one token more, which nobody reads.)  With a
        free slot, a stream on its last token or one not yet read from,
        the engine goes step by step as it always did."""
        if len(flight.seats) != self._S:
            return False
        last_row = self._L - 1
        for i, slot in flight.seats:
            if self._slots[i] is not slot or slot.pos is None \
                    or slot.emitted + 1 >= slot.req.max_new \
                    or slot.pos + 1 >= last_row:
                return False
        return True

    def _step_failed(self, live, e):
        """Terminal: the donated cache may be gone.  Fail the live
        slots and rebuild it."""
        events.incr("gen.failed")
        self._ahead = None
        for i in list(live):
            self._retire(i, exc=e)
        self._init_cache_arrays()
        _bb.record("gen", "step_failed", error=type(e).__name__)

    def _probe_leaf(self):
        """The largest leaf of the model's cache: the one whose copy
        would cost most, and one every donating executable writes."""
        import jax
        return max(jax.tree_util.tree_leaves(self._cache["m"]),
                   key=lambda a: a.size)

    def _donation_probe(self, old_leaf, what):
        """`old_leaf` is a cache leaf held from before a call of the
        donating executable `what`: still alive after it, the
        build-time audit passed (the argnums ARE donated) but the
        backend copied anyway — count it and say so by label."""
        if old_leaf.is_deleted():
            return
        events.incr("gen.donation_copy")
        import warnings
        warnings.warn(
            "executable %r: donated KV cache was COPIED, not aliased — "
            "its HBM traffic doubles (backend ignores donation)"
            % (self._label + ":" + what))

    def _emit(self, seats, report, dt, now):
        """The host's share of a step, after its report arrived (a row
        a slot: token, the position it was read at, the model's
        counts): meter it, push the token of each stream that still has
        the seat it had when the step was dispatched, retire what
        finished.  Returns the number of tokens pushed."""
        # a racing close() swept a slot (its stream is resolved), or a
        # step dispatched early ran over a stream that had just ended
        seats = [(i, slot) for i, slot in seats if self._slots[i] is slot]
        live = [i for i, _ in seats]
        self._step_ewma = dt if self._step_ewma is None \
            else 0.3 * dt + 0.7 * self._step_ewma
        self._steps += 1
        events.observe_time("gen.step_us", dt)
        events.incr("gen.steps")
        events.incr("gen.tokens", len(live))
        events.observe("gen.slots_live", len(live))
        if self._count_names:
            for name, total in zip(self._count_names,
                                   report[live, 2:].sum(axis=0)):
                events.incr(name, int(total))
        last_row = self._L - 1
        pushed = 0
        for i, slot in seats:
            if self._slots[i] is not slot:      # close() swept it just now
                continue
            req = slot.req
            tok = int(report[i, 0])
            slot.emitted += 1
            slot.pos = int(report[i, 1])
            if slot.t_last is None:
                _tele.phase_at("gen.req.first", req.t_exec, now,
                               req.rid, req.tick)
                if req.rec is not None:
                    req.rec.t_first = now
                events.observe_time("gen.ttft_us", now - req.t_enq)
                events.observe("gen.ttft_us",
                               int((now - req.t_enq) * 1e6),
                               labels={"lane": req.lane})
            else:
                events.observe_time("gen.intertoken_us",
                                    now - slot.t_last)
                events.observe("gen.intertoken_us",
                               int((now - slot.t_last) * 1e6),
                               labels={"lane": req.lane})
            slot.t_last = now
            req.stream._push(tok)
            pushed += 1
            if req.deadline is not None and now > req.deadline:
                # mid-decode deadline: shed, free the slot THIS step
                self._shed_mark(req.lane, req.tenant, "deadline",
                                deadline=True)
                self._retire(i, exc=DeadlineExceeded(
                    "deadline expired after %d token(s)"
                    % slot.emitted))
            elif tok == self._eos or slot.emitted >= req.max_new \
                    or slot.pos >= last_row:
                self._retire(i)
        return pushed

    def _occupancy_event(self, kind, slot, req):
        live = len(self._live())
        _bb.record("gen", kind, slot=int(slot), lane=req.lane,
                   live=live, free=self._S - live, step=self._steps)

    def _retire(self, i, exc=None):
        with self._lock:        # close()'s sweep may race this clear;
            slot = self._slots[i]   # one winner takes the request
            self._slots[i] = None
        if slot is None:
            return
        req = slot.req
        if req.rec is not None:         # decode phase ends here
            req.rec.t_fin = time.monotonic()
        self._resolve(req, exc=exc, accepted=True)
        events.incr("gen.retires")
        e2e = time.monotonic() - req.t_enq
        events.observe_time("gen.e2e_us", e2e)
        events.observe("gen.e2e_us", int(e2e * 1e6),
                       labels={"lane": req.lane})
        self._occupancy_event("retire", i, req)

    def _retire_accounting(self, req):
        """Queue-slot + tenant-hold release — exactly once per ACCEPTED
        request.  The per-request flag (flipped under the lock) makes
        the release idempotent: a close() sweeping slots can race the
        decode thread's own retire, and whoever loses must be a no-op,
        not a second task_done()."""
        with self._lock:
            if req.acct:
                return
            req.acct = True
            if req.tenant is not None:
                n = self._tenant_q.get(req.tenant, 0) - 1
                if n > 0:
                    self._tenant_q[req.tenant] = n
                else:
                    self._tenant_q.pop(req.tenant, None)
        self._q.task_done()

    def _resolve(self, req, exc=None, accepted=True):
        req.stream._finish(exc)
        rec, req.rec = req.rec, None    # single journal retire point
        if rec is not None:             # for accepted requests (swap
            self._journal.retire(rec, exc=exc)  # keeps re-runs no-op)
        if accepted:
            self._retire_accounting(req)

    # -- lifecycle -------------------------------------------------------
    def drain(self, timeout=60.0):
        """Stop intake and wait for every accepted request to resolve
        (queued requests still get generated).  True when fully
        drained in time."""
        self._draining = True
        deadline = time.monotonic() + float(timeout)
        self._ensure_loop()
        with self._q.all_tasks_done:
            while self._q.unfinished_tasks:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                if self._thread is None or \
                        not self._thread.is_alive():
                    break
                self._q.all_tasks_done.wait(min(rem, 0.1))
        return self._q.unfinished_tasks == 0

    def _flush_leftovers(self):
        """Resolve everything still queued or slotted with
        EngineClosed.  Idempotent (the per-request accounting flag +
        future-done guard), and safe to run from BOTH the closing
        thread and the decode loop's exit path — a drain-timeout
        close cannot strand a request the loop popped after the
        close-side sweep, and the two sweeps cannot double-release."""
        leftovers = []
        with self._lock:
            while True:
                try:
                    leftovers.append(self._q.get_nowait())
                except queue.Empty:
                    break
            for i, s in enumerate(self._slots):
                if s is not None:
                    self._slots[i] = None
                    leftovers.append(s.req)
        for req in leftovers:
            self._resolve(req, exc=EngineClosed(
                "engine closed before completion"))

    def close(self, timeout=60.0):
        """drain() + stop the decode loop + resolve any leftover
        stream (EngineClosed) exactly once.  Idempotent."""
        t_end = time.monotonic() + float(timeout)
        self.drain(timeout)
        self._stop = True
        self._work.set()
        t = self._thread
        joined = True
        if t is not None and t.is_alive():
            t.join(max(0.1, t_end - time.monotonic()))
            joined = not t.is_alive()
        with self._lock:
            self._closed = True
        self._flush_leftovers()
        if joined:
            # give the device its memory back now, not when the last
            # reference to the engine goes: whoever closes an engine of
            # gigabytes wants the room (the loop is gone, nothing reads
            # these again)
            self._cache = None
            self._params = None
        return joined

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        self._draining = True
        self._stop = True
        self._closed = True

    # -- introspection ---------------------------------------------------
    def slo_targets(self):
        """{lane: tightest relative deadline seconds among recent
        ACCEPTED deadlined requests} — the TTFT p99 targets the
        default generation SLO rules derive from."""
        with self._lock:
            return {lane: min(dq)
                    for lane, dq in self._lane_deadline_s.items()
                    if dq}

    def slo_lane_quotas(self):
        cap = float(self._q.maxsize)
        return {lane: (1.0 if c is None else c / cap)
                for lane, c in self._lane_caps.items()}

    def install_slo_rules(self, **kw):
        """Register the default generation SLO rules (per-lane TTFT
        p99 vs the observed deadline targets + shed burn rates)."""
        from ..telemetry import slo as _slo
        return _slo.install_default_generation_rules(engine=self, **kw)

    def stats(self):
        with self._lock:
            tenants = dict(self._tenant_q)
        live = self._live()
        return {"counters": events.snapshot("gen."),
                "latency": events.latency_snapshot("gen."),
                "labeled": events.labeled_latency_snapshot("gen."),
                "slots": self._S, "max_len": self._L,
                "prompt_buckets": list(self._buckets),
                "slots_live": len(live),
                "queue_depth": self._q.qsize(),
                "lanes": {"order": list(self._lanes),
                          "depths": self._q.lane_depths(),
                          "caps": dict(self._lane_caps)},
                "tenants_queued": tenants,
                "continuous": self._continuous,
                "steps": self._steps,
                "phases": _tele.phase_totals("gen."),
                "warm": self._warm}
