"""Async device-feed pipeline: uint8-on-wire transfer overlapped with
compute (ISSUE 2 tentpole; SURVEY §2.4 "must sustain v5e input rates").

On an earlier setup (BENCH_r05, before PRs 1-20) the north-star
ResNet-50 ran 2260 img/s on synthetic device-resident batches but 133
img/s end-to-end, bounded by a 7.4 MB/s host→device path; the fed rate
has not been re-measured on this chip.  This module attacks the feed
from three directions:

1. **uint8 on the wire.**  The native reader already produces raw
   augmented pixels (`dtype="uint8"`, io/native.py) — 4x fewer H2D
   bytes than float32.  Mean/std normalization and the cast to the
   compute dtype move ON DEVICE, fused into the train-step executable
   (`HybridBlock.set_input_transform` for the Gluon/CachedOp path,
   `ShardedTrainer(preprocess=...)` for the pod path), so the float
   tensor only ever exists in HBM.
2. **Overlap.**  A background thread reads the NEXT batch from the
   source and `device_put`s it (blocking on transfer completion in the
   worker, never in the consumer) while the current step executes —
   double-buffered by default, depth configurable
   (`MXNET_FEED_DEPTH`).
3. **One transfer per batch.**  The whole batch pytree goes through a
   single batched `device_put` — per-array uploads each pay the
   dispatch round-trip.  With `sharding=` the put lands the
   batch directly on a mesh (sharded on the data axis), so
   `ShardedTrainer.step` consumes it without re-placing.

Per-stage wall/bytes counters land on `monitor.events` (integer
microseconds / bytes), so the feed/compute balance is observable:

    feed.read_us      source wall (read + decode) in the worker
    feed.transfer_us  H2D device_put wall (to transfer completion)
    feed.stall_us     consumer wait — compute starved by the feed
    feed.step_us      consumer wall between batches — the step side
    feed.bytes        bytes shipped on the wire
    feed.queue_depth  ready batches when the consumer arrived (gauge,
                      observed per batch; p50/p99 via percentiles)
    feed.batches / feed.epochs

Stalls above 1 ms additionally land a ("feed", "stall") event in the
flight-recorder ring with the queue depth at that moment, so a
black-box dump separates decode-starved (depth 0 upstream) from
transfer-bound starvation.

`feed_counters()` snapshots them (bench.py includes the snapshot in
its JSON line).
"""
from __future__ import annotations

import queue
import threading
import time
import weakref

import numpy as _np

from .. import config as _cfg
from ..monitor import events
from ..telemetry import flightrec as _bb
from ..telemetry import spans as _tele

#: consumer waits above this land in the flight-recorder ring — a
#: buffered q.get returns in µs, a genuine starvation stall in ms+
_STALL_RECORD_US = 1000

__all__ = ["DeviceFeed", "feed_counters", "make_normalizer",
           "normalize_transform"]

_EOE = ("eoe", None)


def feed_counters():
    """Snapshot of the `feed.*` per-stage counters (µs / bytes / counts)."""
    return events.snapshot("feed.")


def _channel_const(v, ndim, axis):
    """Scalar or per-channel sequence → numpy constant broadcastable
    against an `ndim`-rank batch on `axis` (1 for NCHW, -1 for NHWC)."""
    arr = _np.asarray(v, _np.float32)
    if arr.ndim == 0:
        return arr
    shape = [1] * ndim
    shape[axis] = arr.shape[0]
    return arr.reshape(shape)


def make_normalizer(mean=127.5, std=64.0, dtype="bfloat16", axis=1):
    """Pure jnp function `x → (x.f32 - mean) / std` cast to `dtype`,
    for fusing into a jitted step (`ShardedTrainer(preprocess=...)`).
    `mean`/`std` are scalars or per-channel sequences; `axis` is the
    channel axis of the batch (1 = NCHW)."""
    import jax.numpy as jnp

    def norm(x):
        y = x.astype(jnp.float32)
        m = _channel_const(mean, y.ndim, axis)
        s = _channel_const(std, y.ndim, axis)
        return ((y - m) / s).astype(jnp.dtype(dtype))

    return norm


def normalize_transform(mean=127.5, std=64.0, dtype="bfloat16", axis=1):
    """NDArray-level normalize+cast for `HybridBlock.set_input_transform`:
    traced INTO the cached forward executable, so uint8 stays the wire
    format and the normalize runs on device as part of the fused step."""
    from .. import ndarray as nd
    cache = {}      # (ndim, ctx) → constant NDArrays: uploaded ONCE,
                    # not per eager call (constants are concrete even
                    # inside a trace — device_put of host numpy)

    def transform(x):
        y = x.astype("float32")
        key = (y.ndim, x.context)
        consts = cache.get(key)
        if consts is None:
            consts = (nd.array(_channel_const(mean, y.ndim, axis),
                               ctx=x.context),
                      nd.array(_channel_const(std, y.ndim, axis),
                               ctx=x.context))
            cache[key] = consts
        m, s = consts
        return ((y - m) / s).astype(dtype)

    return transform


class DeviceFeed:
    """Background-transfer iterator over host batches.

    source: an iterable of host batch pytrees (numpy arrays / NDArrays,
        tuples thereof), or a zero-arg callable returning a fresh
        iterator per epoch.  A non-callable source with a ``reset()``
        method is reset between epochs.
    ctx: target Context — batches come back as NDArrays on it.
    sharding: a jax Sharding (or a pytree of them matching the batch
        structure) — batches come back as raw jax global arrays placed
        on it; mutually exclusive with `ctx`.
    depth: batches in flight (default `MXNET_FEED_DEPTH`, 2 = double
        buffer).
    transform: host-side callable applied to each raw batch in the
        worker (label reshapes etc.) before transfer.

    Iteration yields one epoch.  `reset()` starts the next, discarding
    any in-flight batches from the old one; re-entering `iter()` after
    exhaustion re-arms the next epoch automatically (mid-epoch it
    continues the current one, like any iterator).
    `MXNET_FEED_ASYNC=0` degrades to synchronous read+put in the
    consumer (same counters, no thread) for debugging.
    """

    def __init__(self, source, ctx=None, sharding=None, depth=None,
                 transform=None):
        if ctx is not None and sharding is not None:
            raise ValueError("pass ctx= or sharding=, not both")
        self._source = source
        # target context captured EAGERLY: the worker thread must not
        # resolve `with ctx:` scoping lazily (thread-local, empty there)
        if sharding is None:
            from ..context import current_context
            ctx = ctx or current_context()
        self._ctx = ctx
        self._sharding = sharding
        self._transform = transform
        self._depth = max(1, int(depth if depth is not None
                                 else _cfg.get("MXNET_FEED_DEPTH")))
        self._async = bool(_cfg.get("MXNET_FEED_ASYNC"))
        self._gen = 0               # epoch generation; bumping it
        self._q = None              # retires the worker at its next put
        self._thread = None
        self._epoch_it = None       # current epoch's source iterator
        self._tele_parent = None    # consumer-side span ctx (at _start)
        self._exhausted = False
        self._started = False
        self._last_t = None
        self._alias = None          # does device_put alias host bufs?

    # -- placement -----------------------------------------------------
    def _target_device(self):
        return self._ctx.jax_device

    def _host_aliasing(self):
        """Whether device_put to this feed's target ALIASES host numpy
        buffers instead of copying: the CPU backend's placement is
        zero-copy (mutating the source after block_until_ready mutates
        the placed array — verified), so sources that recycle their
        buffers (the decode service's shared-memory slab ring) must be
        copied first.  Real accelerators do a true H2D copy."""
        if self._alias is None:
            import jax
            if self._sharding is not None:
                is_sh = lambda s: isinstance(s, jax.sharding.Sharding)
                plats = {d.platform
                         for s in jax.tree_util.tree_leaves(
                             self._sharding, is_leaf=is_sh)
                         for d in s.device_set}
            else:
                plats = {self._target_device().platform}
            self._alias = "cpu" in plats
        return self._alias

    def _place(self, batch):
        """ONE batched device_put for the whole pytree; returns
        (placed, wire_bytes).  Blocks until the transfer lands — in the
        worker thread, so the consumer never waits on H2D."""
        import jax
        from ..ndarray.ndarray import NDArray
        alias = self._host_aliasing()

        def host(leaf):
            if isinstance(leaf, NDArray):
                return leaf._data
            if isinstance(leaf, jax.Array):
                return leaf
            arr = _np.asarray(leaf)
            return arr.copy() if alias else arr

        hb = jax.tree_util.tree_map(host, batch)
        nbytes = sum(int(getattr(l, "nbytes", 0))
                     for l in jax.tree_util.tree_leaves(hb))
        if self._sharding is not None:
            placed = self._place_sharded(hb)
        else:
            placed = jax.device_put(hb, self._target_device())
        jax.block_until_ready(placed)
        return placed, nbytes

    def _place_sharded(self, hb):
        import jax
        sh = self._sharding
        leaves, treedef = jax.tree_util.tree_flatten(hb)
        is_sh = lambda s: isinstance(s, jax.sharding.Sharding)
        sh_leaves = jax.tree_util.tree_leaves(sh, is_leaf=is_sh)
        if len(sh_leaves) == 1:
            sh_leaves = sh_leaves * len(leaves)
        if jax.process_count() > 1:
            # multi-controller: each process contributes its local rows
            # (same contract as ShardedTrainer._place_batch)
            out = [jax.make_array_from_process_local_data(
                s, _np.asarray(l)) for l, s in zip(leaves, sh_leaves)]
        else:
            out = jax.device_put(leaves, sh_leaves)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _wrap(self, placed):
        if self._ctx is None:
            return placed
        import jax
        from ..ndarray.ndarray import NDArray
        return jax.tree_util.tree_map(
            lambda a: NDArray(a, ctx=self._ctx), placed)

    # -- worker --------------------------------------------------------
    def _epoch_iter(self):
        src = self._source
        return iter(src() if callable(src) else src)

    @staticmethod
    def _run(ref, gen, q):
        """Worker loop.  Holds the feed only through a WEAKREF (strong
        only transiently, never across a queue wait): an abandoned feed
        — consumer broke out mid-epoch and dropped it — becomes a pure
        reference cycle the GC collects, firing __del__/close(), which
        bumps the generation and retires this thread.  A bound-method
        target or a strongly-held source iterator would pin the feed
        (and its queued device batches) forever."""
        while True:
            feed = ref()
            if feed is None or feed._gen != gen:
                return
            # spans parent onto the CONSUMER's trace (captured at
            # _start): the worker thread's read/transfer intervals
            # join the training timeline they feed
            parent = feed._tele_parent
            t0 = time.perf_counter()
            try:
                with _tele.span("feed.read", parent=parent):
                    batch = next(feed._epoch_it)
                    if feed._transform is not None:
                        batch = feed._transform(batch)
                t1 = time.perf_counter()
                with _tele.span("feed.transfer", parent=parent):
                    placed, nbytes = feed._place(batch)
            except StopIteration:
                del feed
                DeviceFeed._safe_put(ref, q, gen, _EOE)
                return
            except Exception as e:              # noqa: BLE001
                # read/transform/transfer errors all surface as the
                # ('error', e) sentinel — never a silent q.get() hang
                del feed
                DeviceFeed._safe_put(ref, q, gen, ("error", e))
                return
            events.add_time("feed.read_us", t1 - t0)
            events.add_time("feed.transfer_us", time.perf_counter() - t1)
            events.incr("feed.bytes", nbytes)
            del feed, batch
            if not DeviceFeed._safe_put(ref, q, gen, ("batch", placed)):
                return
            del placed

    @staticmethod
    def _safe_put(ref, q, gen, item):
        """Bounded put that retires promptly when the epoch generation
        moves on, or the feed itself is collected, while the queue is
        full (reset/close/abandonment)."""
        while True:
            feed = ref()
            if feed is None or feed._gen != gen:
                return False
            del feed
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue

    def _start(self):
        self._exhausted = False
        self._started = True
        self._last_t = None
        # cross-thread span parent: the consumer's innermost open span
        # at feed start (None when telemetry is off / no span is open)
        self._tele_parent = _tele.current()
        self._epoch_it = self._epoch_iter()
        events.incr("feed.epochs")      # epochs STARTED (first included)
        if self._async:
            self._gen += 1
            self._q = queue.Queue(maxsize=self._depth)
            self._thread = threading.Thread(
                target=DeviceFeed._run,
                args=(weakref.ref(self), self._gen, self._q),
                daemon=True, name="DeviceFeed")
            self._thread.start()

    # -- consumer ------------------------------------------------------
    def __iter__(self):
        if self._exhausted:
            self.reset()
        elif not self._started:
            self._start()
        return self

    def __next__(self):
        if self._exhausted:         # incl. after close(); iter()/reset()
            raise StopIteration     # is the intentional-restart path
        if not self._started:
            self._start()
        t0 = time.perf_counter()
        if self._last_t is not None:
            events.add_time("feed.step_us", t0 - self._last_t)
        if not self._async:
            out = self._next_sync(t0)
        else:
            # ready-batch gauge BEFORE the get: depth 0 here plus a
            # stall means the worker (read/decode or H2D) is behind;
            # depth > 0 means the consumer arrived to a full buffer
            depth = self._q.qsize()
            events.observe("feed.queue_depth", depth)
            kind, val = self._q.get()
            stall_s = time.perf_counter() - t0
            events.add_time("feed.stall_us", stall_s)
            stall_us = int(stall_s * 1e6)
            if stall_us > _STALL_RECORD_US:
                # compute starved by the feed: one timeline event per
                # real stall (buffered sub-ms gets are just poll cost);
                # qdepth attributes it — 0 = upstream (decode/wire)
                # starved the worker, >0 = transfer completion lagged
                _bb.record("feed", "stall", us=stall_us, qdepth=depth)
            if kind == "eoe":
                self._exhausted = True
                raise StopIteration
            if kind == "error":
                self._exhausted = True
                raise val
            events.incr("feed.batches")
            out = self._wrap(val)
        self._last_t = time.perf_counter()
        return out

    def _next_sync(self, t0):
        try:
            batch = next(self._epoch_it)
        except StopIteration:
            self._exhausted = True
            raise
        if self._transform is not None:
            batch = self._transform(batch)
        t1 = time.perf_counter()
        placed, nbytes = self._place(batch)
        events.add_time("feed.read_us", t1 - t0)
        events.add_time("feed.transfer_us", time.perf_counter() - t1)
        events.incr("feed.bytes", nbytes)
        events.incr("feed.batches")
        return self._wrap(placed)

    def reset(self):
        """Begin a new epoch: in-flight batches from the old one are
        discarded, the source is reset (its `reset()` when present, a
        fresh call when the source is callable), prefetch restarts."""
        self._gen += 1              # retire the old worker...
        t = self._thread
        if t is not None and t.is_alive():
            t.join()                # ...and wait it out (put timeouts
        self._thread = None         # make this prompt)
        self._epoch_it = None
        src = self._source
        if not callable(src) and hasattr(src, "reset"):
            src.reset()
        self._start()

    def close(self):
        """Stop the background worker; further next() raises
        StopIteration (reset()/iter() re-arm intentionally)."""
        self._gen += 1
        self._thread = None
        self._epoch_it = None
        self._started = False
        self._exhausted = True

    def __del__(self):
        try:
            self.close()
        except Exception:           # noqa: BLE001
            pass
