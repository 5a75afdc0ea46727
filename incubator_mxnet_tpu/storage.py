"""Storage manager facade (ref: include/mxnet/storage.h,
src/storage/pooled_storage_manager.h — GPUPooledStorageManager's
size-bucketed free lists, MXNET_GPU_MEM_POOL_* knobs).

Deliberate TPU re-design: device memory pooling is the PJRT/XLA
allocator's job (a BFC arena owns HBM; XLA's buffer assignment reuses
and donates buffers inside executables), so there is no hand-written
pool here to configure.  What this module preserves from the reference
surface:

- `Storage.get()` singleton with `alloc`/`free` bookkeeping hooks — the
  imperative NDArray path doesn't call it (jax.Array owns its buffer),
  but custom native extensions can use it for host scratch;
- per-device memory introspection (`memory_info`) mapping
  `mx.context.gpu_memory_info` onto PJRT's memory stats;
- the MXNET_GPU_MEM_POOL_* env knobs are registered in `config` and
  accepted (recorded, no-op) so reference launch scripts run unchanged.
"""
from __future__ import annotations

import threading

__all__ = ["Storage", "memory_info", "memory_events",
           "live_arrays_events"]


def memory_info(device=None):
    """(bytes_in_use, bytes_limit) for a device (ref:
    mx.context.gpu_memory_info; backed by PJRT memory_stats)."""
    import jax
    if device is None:
        device = jax.devices()[0]
    elif isinstance(device, int):
        device = jax.devices()[device]
    elif hasattr(device, "jax_device"):
        device = device.jax_device
    stats = getattr(device, "memory_stats", lambda: None)()
    if not stats:
        return (0, 0)
    return (stats.get("bytes_in_use", 0),
            stats.get("bytes_limit", stats.get("bytes_reservable_limit",
                                               0)))


def memory_events(devices=None, counters=None):
    """Sample per-device HBM used/peak onto `monitor.events` as `mem.*`
    observed series (ISSUE 5): `mem.bytes_in_use` / `mem.peak_bytes`
    samples whose p50/p99 render through the MetricsExporter like any
    latency series.  Returns one dict per device that HAS stats.

    Degrades cleanly on backends whose PJRT `memory_stats` returns
    None or raises: that device
    contributes NO event and NO crash — the return is simply shorter
    (empty on a statless backend, e.g. CPU jax)."""
    import jax
    if counters is None:
        from .monitor import events as counters
    out = []
    for d in (devices if devices is not None else jax.devices()):
        d = getattr(d, "jax_device", d)
        try:
            stats = getattr(d, "memory_stats", lambda: None)()
        except Exception:           # noqa: BLE001 — introspection must
            stats = None            # never take the run down
        if not stats:
            continue
        used = int(stats.get("bytes_in_use", 0))
        peak = int(stats.get("peak_bytes_in_use", used))
        limit = int(stats.get("bytes_limit",
                              stats.get("bytes_reservable_limit", 0)))
        counters.observe("mem.bytes_in_use", used)
        counters.observe("mem.peak_bytes", max(peak, used))
        out.append({"device": "%s:%d" % (getattr(d, "platform", "dev"),
                                         getattr(d, "id", 0)),
                    "bytes_in_use": used,
                    "peak_bytes": max(peak, used),
                    "bytes_limit": limit})
    return out


def live_arrays_events(devices=None, counters=None):
    """`memory_events`-shaped rows computed from `jax.live_arrays()`
    — the measured-bytes fallback for backends whose PJRT
    ``memory_stats`` reports nothing (CPU jax).
    Each row carries ``source="live_arrays"``; the per-device sum
    counts every addressable shard on the device that holds it, so
    replicated and sharded arrays both attribute where their bytes
    actually live.  There is no allocator here, so ``peak_bytes`` ==
    ``bytes_in_use`` and ``bytes_limit`` is 0 (unreported)."""
    import jax
    if counters is None:
        from .monitor import events as counters
    per_dev = {}
    for arr in jax.live_arrays():
        try:
            shards = arr.addressable_shards
        except Exception:           # noqa: BLE001 — a deleted array
            shards = None           # must not kill the probe
        if shards:
            for sh in shards:
                d = sh.device
                key = "%s:%d" % (getattr(d, "platform", "dev"),
                                 getattr(d, "id", 0))
                per_dev[key] = per_dev.get(key, 0) \
                    + int(sh.data.nbytes)
            continue
        try:
            nb = int(arr.nbytes)
            devs = list(arr.devices())
        except Exception:           # noqa: BLE001
            continue
        for d in devs:
            key = "%s:%d" % (getattr(d, "platform", "dev"),
                             getattr(d, "id", 0))
            per_dev[key] = per_dev.get(key, 0) + nb // max(1,
                                                          len(devs))
    want = None
    if devices is not None:
        want = set()
        for d in devices:
            d = getattr(d, "jax_device", d)
            want.add("%s:%d" % (getattr(d, "platform", "dev"),
                                getattr(d, "id", 0)))
    out = []
    for key in sorted(per_dev):
        if want is not None and key not in want:
            continue
        used = per_dev[key]
        counters.observe("mem.bytes_in_use", used)
        counters.observe("mem.peak_bytes", used)
        out.append({"device": key, "bytes_in_use": used,
                    "peak_bytes": used, "bytes_limit": 0,
                    "source": "live_arrays"})
    return out


class Storage:
    """Host-scratch allocator facade (singleton, ref: Storage::Get).

    Tracks outstanding allocations for leak diagnostics; allocation
    itself is plain bytearray (aligned host memory — device memory is
    always XLA's)."""

    _instance = None
    _lock = threading.Lock()

    @classmethod
    def get(cls):
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self):
        self._outstanding = {}
        self._next = 0
        self._mu = threading.Lock()

    def alloc(self, size):
        """Returns (handle_id, buffer)."""
        buf = bytearray(size)
        with self._mu:
            hid = self._next
            self._next += 1
            self._outstanding[hid] = size
        return hid, buf

    def free(self, handle_id):
        with self._mu:
            self._outstanding.pop(handle_id, None)

    def direct_free(self, handle_id):
        self.free(handle_id)

    @property
    def outstanding_bytes(self):
        with self._mu:
            return sum(self._outstanding.values())

    @property
    def outstanding_count(self):
        with self._mu:
            return len(self._outstanding)
