"""Device/context model.

TPU-native re-design of the reference's Context
(ref: include/mxnet/base.h — Context, DevMask, cpu()/gpu()/cpu_pinned()).

Here a Context names a JAX device: ``cpu(i)`` → host platform device i,
``tpu(i)`` → accelerator chip i.  ``gpu(i)`` is kept as a compatibility
alias for ``tpu(i)`` so reference-era scripts run unchanged.  cpu_pinned
and cpu_shared map to plain host memory (PJRT host buffers are already
DMA-able; there is no separate pinned pool to manage).
"""
from __future__ import annotations

import threading
from typing import Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
           "current_context", "num_gpus", "num_tpus", "device"]

_DEVTYPE_CANON = {
    "cpu": "cpu",
    "tpu": "tpu",
    "gpu": "tpu",          # compat alias: reference scripts say gpu()
    "cpu_pinned": "cpu",
    "cpu_shared": "cpu",
}


class Context:
    """A device context. Every NDArray lives on exactly one Context.

    Mirrors the semantics of the reference Context (device_type +
    device_id, usable as `with ctx:` to set the default) but resolves to a
    JAX/PJRT device instead of a CUDA ordinal.
    """

    _default = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in _DEVTYPE_CANON:
            raise MXNetError("unknown device type %r" % (device_type,))
        self.device_type = _DEVTYPE_CANON[device_type]
        self._requested_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    # -- JAX resolution ---------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device (raises if absent)."""
        import jax
        # Always bind to PROCESS-LOCAL devices: under jax.distributed
        # (dist kvstore workers) jax.devices() is the GLOBAL list and
        # indexing it would hand out other workers' non-addressable
        # devices (ref: each MXNet worker process owns only its own GPUs).
        if self.device_type == "cpu":
            devs = jax.local_devices(backend="cpu") \
                if jax.default_backend() != "cpu" else jax.local_devices()
        else:
            # an accelerator context never resolves to the host: a
            # script that asks for tpu()/gpu() on a chip-less machine
            # fails here instead of running on the CPU under that name
            if jax.default_backend() == "cpu":
                raise MXNetError(
                    "context %r: no accelerator; the JAX backend found "
                    "is 'cpu' (use mx.cpu(), or mx.num_tpus() to probe)"
                    % (self,))
            devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %r: device id %d out of range (%d devices)"
                % (self, self.device_id, len(devs)))
        return devs[self.device_id]

    # -- default-context management --------------------------------------
    def __enter__(self):
        stack = getattr(Context._default, "stack", None)
        if stack is None:
            stack = Context._default.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default.stack.pop()

    @staticmethod
    def default_ctx() -> "Context":
        stack = getattr(Context._default, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compatibility alias: resolves to the accelerator (TPU) context."""
    return Context("gpu", device_id)


def device(device_type: str, device_id: int = 0) -> Context:
    return Context(device_type, device_id)


_DEFAULT = Context("cpu", 0)


def current_context() -> Context:
    return Context.default_ctx()


def num_tpus() -> int:
    import jax
    if jax.default_backend() == "cpu":
        return 0
    return len(jax.devices())


def num_gpus() -> int:
    """Compat alias (ref: mx.context.num_gpus) — counts accelerator chips."""
    return num_tpus()
