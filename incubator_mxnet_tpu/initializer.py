"""Weight initializers (ref: python/mxnet/initializer.py).

Same registry + `InitDesc`-by-name dispatch as the reference; bodies use
the framework's stateful RNG facade so `mx.random.seed` reproduces runs.
"""
from __future__ import annotations

import json
import math
import re

import numpy as _np

from .base import MXNetError

__all__ = ["Initializer", "Zero", "One", "Constant", "Uniform", "Normal",
           "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear", "LSTMBias",
           "Mixed", "register", "create", "InitDesc"]

_REGISTRY = {}


def register(klass):
    _REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs):
    if isinstance(name, Initializer):
        return name
    if name is None:
        return Uniform()
    if callable(name):
        return name
    key = name.lower()
    aliases = {"zeros": "zero", "ones": "one", "gaussian": "normal",
               "msraprelu": "msraprelu"}
    key = aliases.get(key, key)
    if key not in _REGISTRY:
        raise MXNetError("unknown initializer %r" % name)
    return _REGISTRY[key](**kwargs)


class InitDesc(str):
    """Parameter name + attrs hint (ref: initializer.py InitDesc)."""

    def __new__(cls, name, attrs=None, global_init=None):
        obj = super().__new__(cls, name)
        obj.attrs = attrs or {}
        obj.global_init = global_init
        return obj


class Initializer:
    """Base initializer; `__call__(name, arr)` fills `arr` in place
    (rebinding the buffer, as all mutation does here)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def __call__(self, name, arr):
        if not isinstance(name, str):
            name = ""
        self.init_weight(name, arr)

    def init_weight(self, name, arr):
        if name.endswith("bias"):
            self._init_zero(arr)
        elif name.endswith("gamma"):
            self._init_one(arr)
        elif name.endswith("beta"):
            self._init_zero(arr)
        elif name.endswith("running_mean") or name.endswith("moving_mean"):
            self._init_zero(arr)
        elif name.endswith("running_var") or name.endswith("moving_var"):
            self._init_one(arr)
        else:
            self._init_weight(name, arr)

    # -- default fills ----------------------------------------------------
    def _init_zero(self, arr):
        self._fill(arr, _np.zeros(arr.shape, dtype=arr.dtype))

    def _init_one(self, arr):
        self._fill(arr, _np.ones(arr.shape, dtype=arr.dtype))

    def _init_weight(self, name, arr):
        raise NotImplementedError

    @staticmethod
    def _fill(arr, value):
        from .ndarray import NDArray
        import jax
        arr._data = jax.device_put(
            _np.asarray(value, dtype=arr.dtype), arr.context.jax_device)

    @staticmethod
    def _cpu_key(ctx):
        """Derive a fresh init key ENTIRELY on the host + local cpu
        backend: init-time randomness runs there (threefry is
        backend-deterministic), so a fresh process pays zero
        accelerator compiles for its ~hundreds of per-shape init
        programs — including the device-side threefry seed/fold/split
        chain `split_key` would run (chosen on an earlier setup where
        those compiles cost 38-117 s of BERT startup; not re-measured
        on this chip)."""
        from . import random as rnd
        import jax
        try:
            # process-LOCAL cpu device: jax.devices("cpu")[0] is rank
            # 0's under multi-controller — non-addressable elsewhere
            cpu = jax.local_devices(backend="cpu")[0]
            bits = rnd.next_key_bits(ctx)      # host-only derivation
            with jax.default_device(cpu):
                return jax.random.wrap_key_data(bits), True
        except Exception:
            return rnd.split_key(ctx), False

    @staticmethod
    def _rand_normal(arr, scale):
        import jax
        key, on_cpu = Initializer._cpu_key(arr.context)
        if on_cpu:
            with jax.default_device(jax.local_devices(
                    backend="cpu")[0]):
                vals = jax.random.normal(key, arr.shape)
        else:
            vals = jax.random.normal(key, arr.shape)
        Initializer._fill(arr, _np.asarray(vals) * scale)

    @staticmethod
    def _rand_uniform(arr, low, high):
        import jax
        key, on_cpu = Initializer._cpu_key(arr.context)
        if on_cpu:
            with jax.default_device(jax.local_devices(
                    backend="cpu")[0]):
                vals = jax.random.uniform(key, arr.shape, minval=low,
                                          maxval=high)
        else:
            vals = jax.random.uniform(key, arr.shape, minval=low,
                                      maxval=high)
        Initializer._fill(arr, _np.asarray(vals))

    def dumps(self):
        return json.dumps([self.__class__.__name__.lower(), self._kwargs])

    def __repr__(self):
        return self.__class__.__name__


@register
class Zero(Initializer):
    def _init_weight(self, name, arr):
        self._fill(arr, _np.zeros(arr.shape, dtype=arr.dtype))


@register
class One(Initializer):
    def _init_weight(self, name, arr):
        self._fill(arr, _np.ones(arr.shape, dtype=arr.dtype))


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, name, arr):
        self._fill(arr, _np.full(arr.shape, self.value, dtype=arr.dtype))


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, name, arr):
        self._rand_uniform(arr, -self.scale, self.scale)


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, name, arr):
        self._rand_normal(arr, self.sigma)


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, name, arr):
        from . import random as rnd
        import jax
        key = rnd.split_key(arr.context)
        nout = arr.shape[0]
        nin = int(_np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = _np.asarray(jax.random.uniform(
                key, (nout, nin), minval=-1.0, maxval=1.0))
        else:
            tmp = _np.asarray(jax.random.normal(key, (nout, nin)))
        u, _, v = _np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == tmp.shape else v
        self._fill(arr, self.scale * q.reshape(arr.shape))


@register
class Xavier(Initializer):
    """ref: initializer.py Xavier (gaussian/uniform × avg/in/out)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        shape = arr.shape
        hw_scale = 1.0
        if len(shape) < 2:
            raise MXNetError("Xavier requires ndim >= 2 (got %r)" % (shape,))
        if len(shape) > 2:
            hw_scale = _np.prod(shape[2:])
        fan_in = shape[1] * hw_scale
        fan_out = shape[0] * hw_scale
        factor = {"avg": (fan_in + fan_out) / 2.0,
                  "in": fan_in, "out": fan_out}[self.factor_type]
        scale = math.sqrt(self.magnitude / factor)
        if self.rnd_type == "uniform":
            self._rand_uniform(arr, -scale, scale)
        else:
            self._rand_normal(arr, scale)


@register
class MSRAPrelu(Xavier):
    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    def _init_weight(self, name, arr):
        shape = arr.shape
        weight = _np.zeros(int(_np.prod(shape)), dtype="float32")
        f = _np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(weight.size):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        self._fill(arr, weight.reshape(shape))


@register
class LSTMBias(Initializer):
    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, arr):
        b = _np.zeros(arr.shape, dtype="float32")
        num_hidden = arr.shape[0] // 4
        b[num_hidden:2 * num_hidden] = self.forget_bias
        self._fill(arr, b)


class Mixed:
    """ref: initializer.Mixed — regex-pattern dispatch."""

    def __init__(self, patterns, initializers):
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name, arr):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise MXNetError("no initializer pattern matches %r" % name)
