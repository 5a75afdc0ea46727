"""Gluon Parameter / ParameterDict.

TPU-native re-design of ref: python/mxnet/gluon/parameter.py — Parameter
(deferred shape init, grad_req, per-context copies), ParameterDict.

A Parameter owns one NDArray per context (data-parallel copies, as the
reference kept per-GPU copies); on a sharded mesh the copies collapse to
one sharded array via the parallel/ module.  `attach_grad` wires leaves
into the autograd tape so hybridized (jitted) forwards produce gradients
for them.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional

import numpy as _np

from ..base import MXNetError
from ..context import Context, cpu, current_context
from ..ndarray.ndarray import NDArray
from .. import initializer as init_mod


def _sync_np_class(out):
    """Align a STORED array's class with the current front-end mode.

    np mode (npx.set_np()): hand back the SAME object viewed as an
    mx.np ndarray — identity must be preserved because backward grads
    and trainer updates bind to this instance; ndarray has empty
    __slots__, so the class switch is layout-compatible.  When np mode
    is off again, switch back so legacy semantics (hashability, strict
    operator dispatch) are restored."""
    if out is None:
        return out
    from ..util import is_np_array
    from ..numpy.multiarray import ndarray as _np_ndarray
    if is_np_array():
        if type(out) is NDArray:
            out.__class__ = _np_ndarray
    elif type(out) is _np_ndarray:
        out.__class__ = NDArray
    return out

__all__ = ["Parameter", "Constant", "ParameterDict",
           "DeferredInitializationError", "tensor_types"]

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its (deferred) shape is known."""


class Parameter:
    """ref: gluon.Parameter."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data: Optional[OrderedDict] = None      # ctx -> NDArray
        self._grad: Optional[OrderedDict] = None
        self._deferred_init = ()
        self._trainer = None

    # ------------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 == s2 or s1 in (0, -1)
                         for s1, s2 in zip(self._shape, new_shape)) \
            and len(self._shape) == len(new_shape)
        if not unknown_ok:
            raise MXNetError(
                "cannot reset shape of %s from %s to %s"
                % (self.name, self._shape, new_shape))
        self._shape = tuple(new_shape)

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._grad = None
                for arr in self._data.values():
                    arr._grad, arr._grad_req = None, None
            else:
                self._init_grad()

    @property
    def stype(self):
        return self._stype

    # ------------------------------------------------------------------
    def _shape_known(self):
        return self._shape is not None and all(s > 0 for s in self._shape)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if not self._shape_known():
            if not self.allow_deferred_init:
                raise MXNetError(
                    "cannot initialize parameter %s: shape %s unknown and "
                    "deferred init not allowed" % (self.name, self._shape))
            self._deferred_init = (init, list(ctx), default_init)
            return
        self._finish_init(init, list(ctx), default_init)

    def _finish_init(self, initializer, ctx_list, default_init):
        import jax.numpy as jnp
        # build into a local dict and assign atomically at the end: a
        # failing initializer must not leave _data as a half-filled (or
        # empty) dict that _check_initialized would accept
        new_data = OrderedDict()
        for ctx in ctx_list:
            # HOST zeros: the device buffer is about to be overwritten
            # by the initializer's device_put anyway — a jnp.zeros here
            # costs one compile per distinct shape at startup
            arr = NDArray(_np.zeros(self._shape,
                                    _np.dtype(self.dtype)
                                    if not isinstance(self.dtype, str)
                                    else _np.float32), ctx=ctx,
                          dtype=self.dtype if isinstance(self.dtype, str)
                          else None)
            # fill via initializer chain (ref: Parameter._load_init order)
            chosen = initializer or self.init or default_init
            chosen = init_mod.create(chosen) if not callable(chosen) else chosen
            chosen(init_mod.InitDesc(self.name), arr)
            new_data[ctx] = arr
        self._data = new_data
        self._deferred_init = ()
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        if not self._shape_known():
            raise DeferredInitializationError(
                "parameter %s has unknown shape %s"
                % (self.name, self._shape))
        initializer, ctx_list, default_init = self._deferred_init
        self._finish_init(initializer, ctx_list, default_init)

    def _init_grad(self):
        import jax.numpy as jnp
        self._grad = OrderedDict()
        for ctx, arr in self._data.items():
            arr.attach_grad(self._grad_req, stype=self._grad_stype)
            self._grad[ctx] = arr._grad

    # ------------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is not None:
            if ctx is not None and ctx not in self._data:
                raise MXNetError(
                    "parameter %s not initialized on %r (has %s)"
                    % (self.name, ctx, list(self._data)))
            return
        if self._deferred_init:
            raise DeferredInitializationError(
                "parameter %s deferred (shape unknown)" % self.name)
        raise MXNetError(
            "parameter %s not initialized — call initialize()" % self.name)

    def data(self, ctx=None):
        self._check_initialized()
        if ctx is None or ctx not in self._data:
            # lenient fallback to the primary copy: tracer-backed calls
            # carry a default ctx that need not match the storage ctx
            out = next(iter(self._data.values()))
        else:
            out = self._data[ctx]
        return _sync_np_class(out)

    def list_data(self):
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx=None):
        self._check_initialized()
        if self._grad is None:
            raise MXNetError("parameter %s has grad_req='null'" % self.name)
        # read the LIVE container from the array: sparse backward rebinds
        # arr._grad to a fresh RowSparseNDArray each step
        if ctx is None or ctx not in self._data:
            out = next(iter(self._data.values()))._grad
        else:
            out = self._data[ctx]._grad
        return _sync_np_class(out)

    def list_grad(self):
        self._check_initialized()
        if self._grad is None:
            return []
        return [a._grad for a in self._data.values()
                if a._grad is not None]

    def list_ctx(self):
        if self._data is None and self._deferred_init:
            return self._deferred_init[1]
        self._check_initialized()
        return list(self._data.keys())

    def zero_grad(self):
        if self._grad is None:
            return
        import jax.numpy as jnp
        from ..ndarray.sparse import RowSparseNDArray, zeros_row_sparse
        for arr in self._data.values():
            g = arr._grad
            if g is None:
                continue
            if isinstance(g, RowSparseNDArray):
                arr._grad = zeros_row_sparse(g.shape, g.data._data.dtype,
                                             ctx=arr.context)
            else:
                g._data = jnp.zeros_like(g._data)

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init:
                self._finish_deferred_init()
            else:
                raise MXNetError("parameter %s not initialized" % self.name)
        import jax
        for ctx, arr in self._data.items():
            arr._data = jax.device_put(
                data._data if isinstance(data, NDArray)
                else _np.asarray(data), ctx.jax_device).astype(arr._data.dtype)

    def adopt(self, data):
        """Take `data` (an NDArray already on the device it is to live
        on) as this parameter's storage, as it is: no initializer runs,
        nothing is filled on the host first and nothing is copied.  For
        weights that were made on the device (a loader, a benchmark);
        the parameter takes the array's shape and type."""
        self.shape = data.shape
        self.dtype = data.dtype
        self._data = OrderedDict([(data.context, data)])
        self._deferred_init = ()
        if self._grad_req != "null":
            self._init_grad()

    def release(self):
        """Drop this parameter's storage: its arrays are freed as soon as
        nothing else holds them, without waiting for the block that owns
        the parameter to be collected.  The parameter is uninitialized
        afterwards."""
        self._data = None
        self._grad = None

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = next(iter(self._data.values()))
            self._data = OrderedDict(
                (c, data.as_in_context(c)) for c in ctx)
            if self._grad_req != "null":
                self._init_grad()
        elif self._deferred_init:
            i, _, d = self._deferred_init
            self._deferred_init = (i, list(ctx), d)

    def cast(self, dtype, _convert=True):
        """_convert=False defers the data conversion — Block.cast
        batches every parameter's convert into ONE executable (a
        per-shape eager astype costs a compile each)."""
        self.dtype = dtype
        if self._data is None or not _convert:
            return
        for ctx, arr in self._data.items():
            self._data[ctx] = arr.astype(dtype)
        if self._grad_req != "null":
            self._init_grad()

    def row_sparse_data(self, row_id):
        """Sparse pull path (ref: Parameter.row_sparse_data) — dense-backed
        for now; the Wide&Deep slice specialises it."""
        return self.data()

    def var(self):
        from ..symbol import var
        return var(self.name, shape=self.shape, dtype=self.dtype)

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (
            self.name, self._shape, self.dtype)


class Constant(Parameter):
    """ref: gluon.Constant — non-trainable value parameter."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = NDArray(_np.asarray(value))
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(_self, _name, arr):
                init_mod.Initializer._fill(arr, value.asnumpy())
        init_mod._REGISTRY.setdefault("cinit_%s" % name, _CInit)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=str(value.dtype), init=_CInit())


class ParameterDict:
    """ref: gluon.ParameterDict — prefix-scoped name→Parameter mapping."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __len__(self):
        return len(self._params)

    def get(self, name, **kwargs):
        """Create-or-retrieve `prefix+name` (ref semantics incl. attribute
        merging)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    param.shape = (v,) if isinstance(v, int) else v
                elif k == "dtype" and v is not None:
                    param.dtype = v
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXNetError("constant %s not found" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError("duplicate parameter name %s" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        for param in self.values():
            param.initialize(init=None, ctx=ctx, default_init=init,
                             force_reinit=force_reinit)

    def zero_grad(self):
        for param in self.values():
            param.zero_grad()

    def reset_ctx(self, ctx):
        for param in self.values():
            param.reset_ctx(ctx)

    def setattr(self, name, value):
        for param in self.values():
            setattr(param, name, value)

    def save(self, fname, strip_prefix=""):
        from .. import ndarray as nd
        arg_dict = {}
        for param in self.values():
            block = param.list_data()
            weight = block[0]
            pname = param.name
            if strip_prefix and pname.startswith(strip_prefix):
                pname = pname[len(strip_prefix):]
            arg_dict[pname] = weight
        nd.save(fname, arg_dict)

    def load(self, fname, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from .. import ndarray as nd
        loaded = nd.load(fname, ctx=ctx)
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, param in self.items():
            if name not in loaded:
                if not allow_missing:
                    raise MXNetError("parameter %s missing in file" % name)
                continue
            param._load_and_set(loaded[name], ctx)
        if not ignore_extra:
            extra = set(loaded) - set(self.keys())
            if extra:
                raise MXNetError("extra parameters in file: %s" % extra)

    def __repr__(self):
        return "ParameterDict(%s)" % ", ".join(self.keys())


def _load_and_set(param, data, ctx):
    if param._data is None:
        param.shape = data.shape
        param.initialize(ctx=ctx or [current_context()])
    param.set_data(data)


Parameter._load_and_set = _load_and_set
