"""NDArray — the imperative array type, backed by a PJRT device buffer.

TPU-native re-design of the reference NDArray
(ref: include/mxnet/ndarray.h + src/ndarray/ndarray.cc: Chunk storage on
the pooled allocator, engine variable for async ordering, autograd
`entry_`).  Here the chunk IS a `jax.Array` (PJRT buffer on HBM/host):

- **async semantics for free**: jax dispatch is asynchronous; `asnumpy()`
  / `wait_to_read()` block exactly like `Engine::WaitForVar` did. There is
  no hand-written dependency engine — XLA/PJRT ordering on buffers plays
  that role (SURVEY §7.0 mapping).
- **mutation as rebinding**: `x += y`, `x[i] = v`, optimizer updates etc.
  replace the underlying buffer (`_data`) functionally.  Donation inside
  jitted updates gives in-place behavior at the XLA level.
- **autograd entry**: `_tape_node`/`_out_index` mirror the reference's
  `entry_` (nnvm NodeEntry) linking arrays into the tape.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import MXNetError, dtype_np, numeric_types
from ..context import Context, current_context, cpu
from .. import autograd as _ag
from .. import random as _rnd
from ..ops import registry as _registry

__all__ = ["NDArray", "invoke", "apply_fn", "array", "from_jax", "concat_ctx"]


def _resolve_ctx(arr_inputs, kwargs) -> Context:
    ctx = kwargs.pop("ctx", None) or kwargs.pop("context", None)
    if ctx is not None:
        return ctx
    for a in arr_inputs:
        if isinstance(a, NDArray):
            return a._ctx
    return current_context()


def apply_fn(fn, nd_args, kwargs, *, name="", differentiable=True,
             ctx=None, num_outputs=1, attrs=None):
    """Core imperative dispatch (the analogue of Imperative::Invoke →
    PushFCompute, ref src/imperative/imperative_utils.h).

    `nd_args`: positional args, NDArray items are tensor inputs. The pure
    function is called on unwrapped jax arrays; when autograd is recording
    and any input is tracked, the jax.vjp pullback is recorded on the tape.
    """
    out_nd = kwargs.pop("out", None)
    arr_pos = [i for i, a in enumerate(nd_args) if isinstance(a, NDArray)]
    arr_nds = [nd_args[i] for i in arr_pos]
    arr_data = [a._data for a in arr_nds]
    template = list(nd_args)

    def pure(*arrs):
        full = list(template)
        for p, a in zip(arr_pos, arrs):
            full[p] = a
        return fn(*full, **kwargs)

    ctx = ctx or _resolve_ctx(nd_args, {})
    record = (_ag.is_recording() and differentiable and
              any(_ag._requires_tracking(a) for a in arr_nds))

    def _cost_fn():
        # per-op roofline estimate for fused-program attribution
        # (engine.collect_op_names); runs only at trace time with the
        # profiler listening.  Lowered cost analysis when the backend
        # provides it; else analytic FLOPs for the matmul family +
        # in/out bytes (a backend whose cost_analysis returns None).
        from .. import engine as _eng

        def _n(shape):
            out = 1
            for s in shape:
                out *= int(s)
            return out

        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in arr_data]
        try:
            c = jax.jit(pure).lower(*avals).cost_analysis() or {}
            est = _eng.roofline_estimate(
                float(c.get("flops", 0.0) or 0.0),
                float(c.get("bytes accessed", 0.0) or 0.0))
            if est > 0.0:
                return est
        except Exception:
            pass
        try:
            outs = jax.tree_util.tree_leaves(
                jax.eval_shape(pure, *avals))
            nbytes = float(sum(_n(a.shape) * a.dtype.itemsize
                               for a in list(avals) + outs))
            flops = 0.0
            opn = name or ""
            if opn == "Convolution" and len(arr_data) >= 2 and outs:
                flops = 2.0 * _n(outs[0].shape) * \
                    _n(arr_data[1].shape[1:])       # O,H',W' × I·kh·kw
            elif opn == "FullyConnected" and len(arr_data) >= 2 \
                    and outs:
                # contraction size = weight in_units (the data input
                # may arrive unflattened, e.g. (N, C, H, W))
                k = int(arr_data[1].shape[-1])
                flops = 2.0 * _n(outs[0].shape) * k
            elif opn in ("dot", "batch_dot") and len(arr_data) >= 2 \
                    and outs:
                k = int(arr_data[0].shape[-1])
                flops = 2.0 * _n(outs[0].shape) * k
            return _eng.roofline_estimate(flops, nbytes)
        except Exception:
            nbytes = sum(getattr(a, "size", 0) *
                         getattr(a.dtype, "itemsize", 4)
                         for a in arr_data)
            return _eng.roofline_estimate(0.0, float(nbytes))

    from ..engine import _dispatch_hook
    with _dispatch_hook(name or getattr(fn, "__name__", "op"), ctx,
                        cost_fn=_cost_fn):
        if arr_data:
            if record:
                out, vjp_fn = jax.vjp(pure, *arr_data)
            else:
                out = pure(*arr_data)
        else:
            dev = ctx.jax_device
            with jax.default_device(dev):
                out = pure()
            record = False

    multi = isinstance(out, (tuple, list))
    outs = tuple(out) if multi else (out,)
    from .. import engine as _engine
    if _engine.naive_mode():
        for o in outs:
            o.block_until_ready()
    wrapped = tuple(NDArray(o, ctx=ctx) for o in outs)

    if record:
        # (opname, attrs) only when EVERY positional arg is a tensor —
        # otherwise the symbol stubs could not re-compose this node
        op_attrs = attrs if (attrs is not None and
                             len(arr_pos) == len(nd_args)) else None
        _ag.record_op(vjp_fn, arr_nds, wrapped, name=name,
                      out_is_tuple=multi, raw_fn=pure, op_attrs=op_attrs)

    if out_nd is not None:
        if multi:
            for dst, src in zip(out_nd if isinstance(out_nd, (tuple, list))
                                else (out_nd,), wrapped):
                dst._data = src._data
            return out_nd
        out_nd._data = wrapped[0]._data
        if record:
            out_nd._tape_node = wrapped[0]._tape_node
            out_nd._out_index = wrapped[0]._out_index
        return out_nd
    return wrapped if multi else wrapped[0]


# unary ops cheap enough to defer through a pending cached-op output
# (consumed inside the fused executable; replayed eagerly if forced)
_LAZY_UNARY = frozenset({"reshape", "Flatten", "expand_dims", "squeeze",
                         "transpose", "cast"})


def invoke(opname, *args, **kwargs):
    """Invoke a registered operator imperatively (the generated-stub entry,
    ref: python/mxnet/_ctypes/ndarray.py _imperative_invoke).

    When any argument is a Symbol (export trace through a forward that
    uses the ndarray namespace directly), composition is delegated to the
    symbol front-end instead — one dispatch point makes every model
    symbol-traceable."""
    od = _registry.get(opname)
    from ..symbol.symbol import Symbol as _Sym, apply_stub_args
    if any(isinstance(a, _Sym) for a in args) or \
            any(isinstance(v, _Sym) for v in kwargs.values()):
        return apply_stub_args(opname, args, kwargs)
    if (opname in _LAZY_UNARY and len(args) == 1 and "out" not in kwargs
            and isinstance(args[0], NDArray)
            and args[0]._pending is not None):
        # shape-only op on a deferred cached-op output: stay lazy so the
        # net→reshape→loss chain still fuses into one executable
        from ..gluon.block import try_lazy_unary
        lazy = try_lazy_unary(od, args[0], kwargs)
        if lazy is not None:
            return lazy
    if od.sparse_invoke is not None:
        # FComputeEx analogue: ops with a registered sparse path get
        # first refusal; NotImplemented falls through to dense dispatch
        res = od.sparse_invoke(args, kwargs)
        if res is not NotImplemented:
            return res
    ctx = _resolve_ctx(args, kwargs)
    sym_attrs = (od.name, {k: v for k, v in kwargs.items()
                           if k != "out" and not k.startswith("_")})
    if od.needs_rng and "_rng_key" not in kwargs:
        kwargs["_rng_key"] = _rnd.split_key(ctx)
    if od.needs_training and "_training" not in kwargs:
        kwargs["_training"] = _ag.is_training()
    return apply_fn(od.fn, list(args), kwargs, name=od.name,
                    differentiable=od.differentiable, ctx=ctx,
                    attrs=sym_attrs)


class NDArray:
    """Multi-dimensional array on a Context (ref: mx.nd.NDArray)."""

    __slots__ = ("_data_v", "_pending", "_ctx", "_grad", "_grad_req",
                 "_tape_node", "_out_index", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None, dtype=None):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            d = dtype_np(dtype) if dtype is not None else None
            src_has_dtype = hasattr(data, "dtype")
            npd = _np.asarray(data, dtype=d)
            if dtype is None:
                # ref semantics: python lists/scalars default to float32;
                # float64 narrowed (XLA x64 off by default)
                if not src_has_dtype or npd.dtype == _np.float64:
                    if npd.dtype != _np.bool_:
                        npd = npd.astype(_np.float32)
            ctx = ctx or current_context()
            data = jax.device_put(npd, ctx.jax_device)
        elif dtype is not None:
            data = data.astype(dtype_np(dtype))
        self._pending = None
        self._data_v = data
        self._ctx = ctx or current_context()
        self._grad = None
        self._grad_req = None
        self._tape_node = None
        self._out_index = 0

    # ------------------------------------------------------------------
    # buffer access: lazy (deferred-dispatch) arrays force their pending
    # program on first read — the async-engine WaitForVar analogue
    # ------------------------------------------------------------------
    @property
    def _data(self):
        if self._pending is not None:
            self._pending.force()
        return self._data_v

    @_data.setter
    def _data(self, value):
        self._data_v = value
        self._pending = None

    # ------------------------------------------------------------------
    # properties (answered from the pending program's avals when lazy —
    # shape/dtype queries must not force a dispatch)
    # ------------------------------------------------------------------
    @property
    def shape(self):
        p = self._pending
        if p is not None:
            return tuple(p.aval_of(self)[0])
        return tuple(self._data_v.shape)

    @property
    def dtype(self):
        p = self._pending
        if p is not None:
            return _np.dtype(p.aval_of(self)[1])
        return _np.dtype(self._data_v.dtype)

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return invoke("transpose", self)

    # ------------------------------------------------------------------
    # sync / conversion (ref: NDArray::SyncCopyToCPU / WaitToRead)
    # ------------------------------------------------------------------
    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    def tolist(self):
        return self.asnumpy().tolist()

    def wait_to_read(self):
        self._data.block_until_ready()

    def copy(self):
        return NDArray(self._data, ctx=self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other._ctx.jax_device)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device),
                           ctx=other)
        raise TypeError(type(other))

    def as_in_context(self, ctx: Context):
        if ctx == self._ctx:
            return self
        return NDArray(jax.device_put(self._data, ctx.jax_device), ctx=ctx)

    as_in_ctx = as_in_context

    def as_np_ndarray(self):
        return self

    def astype(self, dtype, copy=True):
        if not copy and _np.dtype(self.dtype) == dtype_np(dtype):
            return self
        return invoke("cast", self, dtype=dtype)

    # ------------------------------------------------------------------
    # autograd (ref: MXNDArrayAttachGrad / MXAutogradBackward)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        # ref MarkVariables replaces the autograd entry with a fresh
        # variable node: attaching a grad makes this array a LEAF, so a
        # recorded history no longer flows through it
        self._tape_node = None
        self._out_index = 0
        if stype == "row_sparse":
            from .sparse import zeros_row_sparse
            self._grad = zeros_row_sparse(self.shape, self._data.dtype,
                                          ctx=self._ctx)
        else:
            # host zeros + device_put: a jnp.zeros here is one remote
            # compile per distinct shape at model-build time
            self._grad = NDArray(_np.zeros(self.shape,
                                           self._data.dtype),
                                 ctx=self._ctx)
        self._grad_req = grad_req

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _ag.backward([self], [out_grad] if out_grad is not None else None,
                     retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # shape ops as methods
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return invoke("reshape", self, shape=shape, **kwargs)

    def reshape_like(self, other):
        return invoke("reshape_like", self, other)

    def expand_dims(self, axis):
        return invoke("expand_dims", self, axis=axis)

    def squeeze(self, axis=None):
        return invoke("squeeze", self, axis=axis)

    def flatten(self):
        return invoke("Flatten", self)

    def transpose(self, axes=None):
        return invoke("transpose", self, axes=axes)

    def swapaxes(self, dim1, dim2):
        return invoke("swapaxes", self, dim1=dim1, dim2=dim2)

    def flip(self, axis):
        return invoke("flip", self, axis=axis)

    def tile(self, reps):
        return invoke("tile", self, reps=reps)

    def repeat(self, repeats, axis=None):
        return invoke("repeat", self, repeats=repeats, axis=axis)

    def broadcast_to(self, shape):
        return invoke("broadcast_to", self, shape=shape)

    def broadcast_like(self, other):
        return invoke("broadcast_like", self, other)

    def slice(self, begin, end, step=None):
        return invoke("slice", self, begin=begin, end=end,
                      step=step or ())

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", self, index, axis=axis, keepdims=keepdims)

    def one_hot(self, depth, **kw):
        return invoke("one_hot", self, depth=depth, **kw)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", self, num_outputs=num_outputs, axis=axis,
                      squeeze_axis=squeeze_axis)

    # ------------------------------------------------------------------
    # math as methods (delegate to ops so autograd records them)
    # ------------------------------------------------------------------
    def sum(self, axis=None, keepdims=False, **kw):
        return invoke("sum", self, axis=axis, keepdims=keepdims, **kw)

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke("mean", self, axis=axis, keepdims=keepdims, **kw)

    def prod(self, axis=None, keepdims=False, **kw):
        return invoke("prod", self, axis=axis, keepdims=keepdims, **kw)

    def max(self, axis=None, keepdims=False, **kw):
        return invoke("max", self, axis=axis, keepdims=keepdims, **kw)

    def min(self, axis=None, keepdims=False, **kw):
        return invoke("min", self, axis=axis, keepdims=keepdims, **kw)

    def norm(self, **kw):
        return invoke("norm", self, **kw)

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", self, axis=axis, keepdims=keepdims)

    def argsort(self, axis=-1, is_ascend=True):
        return invoke("argsort", self, axis=axis, is_ascend=is_ascend)

    def sort(self, axis=-1, is_ascend=True):
        return invoke("sort", self, axis=axis, is_ascend=is_ascend)

    def topk(self, axis=-1, k=1, **kw):
        return invoke("topk", self, axis=axis, k=k, **kw)

    def abs(self):
        return invoke("abs", self)

    def sign(self):
        return invoke("sign", self)

    def sqrt(self):
        return invoke("sqrt", self)

    def square(self):
        return invoke("square", self)

    def exp(self):
        return invoke("exp", self)

    def log(self):
        return invoke("log", self)

    def relu(self):
        return invoke("relu", self)

    def sigmoid(self):
        return invoke("sigmoid", self)

    def tanh(self):
        return invoke("tanh", self)

    def softmax(self, axis=-1):
        return invoke("softmax", self, axis=axis)

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", self, axis=axis)

    def clip(self, a_min, a_max):
        return invoke("clip", self, a_min=a_min, a_max=a_max)

    def dot(self, other, **kw):
        return invoke("dot", self, other, **kw)

    def zeros_like(self):
        return invoke("zeros_like", self)

    def ones_like(self):
        return invoke("ones_like", self)

    def tostype(self, stype):
        if stype == "default":
            return self
        from .sparse import cast_storage
        return cast_storage(self, stype)

    # ------------------------------------------------------------------
    # operators
    # ------------------------------------------------------------------
    def _binary(self, other, op, scalar_op, rscalar_op=None, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke(op, a, b)
        if isinstance(other, numeric_types):
            if reverse and rscalar_op is not None:
                return invoke(rscalar_op, self, scalar=other)
            return invoke(scalar_op, self, scalar=other)
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar",
                            "_rminus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar",
                            "_rdiv_scalar", reverse=True)

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar",
                            "_rmod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar",
                            "_rpower_scalar", reverse=True)

    def __matmul__(self, o):
        return invoke("dot", self, o)

    def __neg__(self):
        return invoke("negative", self)

    def __abs__(self):
        return invoke("abs", self)

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal",
                            "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal",
                            "_lesser_equal_scalar")

    __hash__ = object.__hash__

    # in-place: rebind buffer (donation happens inside jitted updates)
    def __iadd__(self, o):
        r = self.__add__(o)
        self._data, self._tape_node, self._out_index = \
            r._data, r._tape_node, r._out_index
        return self

    def __isub__(self, o):
        r = self.__sub__(o)
        self._data, self._tape_node, self._out_index = \
            r._data, r._tape_node, r._out_index
        return self

    def __imul__(self, o):
        r = self.__mul__(o)
        self._data, self._tape_node, self._out_index = \
            r._data, r._tape_node, r._out_index
        return self

    def __itruediv__(self, o):
        r = self.__truediv__(o)
        self._data, self._tape_node, self._out_index = \
            r._data, r._tape_node, r._out_index
        return self

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    def _conv_index(self, key):
        if isinstance(key, NDArray):
            return key._data
        if isinstance(key, tuple):
            return tuple(k._data if isinstance(k, NDArray) else k
                         for k in key)
        return key

    def __getitem__(self, key):
        jkey = self._conv_index(key)

        def _index(d):
            return d[jkey]
        _index.__name__ = "getitem"
        return apply_fn(_index, [self], {}, name="getitem", ctx=self._ctx)

    def __setitem__(self, key, value):
        jkey = self._conv_index(key)
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = _np.asarray(value)
        self._data = self._data.at[jkey].set(v)
        self._tape_node = None

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        try:
            arr = self.asnumpy()
        except Exception as e:   # pragma: no cover
            return "<NDArray (unrealised: %s)>" % e
        return "%s\n<NDArray %s @%r>" % (
            arr, "x".join(map(str, self.shape)), self._ctx)

    # numpy interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # pickling (optimizer/trainer state serialisation)
    def __reduce__(self):
        return (NDArray, (self.asnumpy(), self._ctx))


def array(source, ctx=None, dtype=None):
    """mx.nd.array — create from any array-like."""
    return NDArray(source, ctx=ctx, dtype=dtype)


def from_jax(a, ctx=None):
    return NDArray(a, ctx=ctx or current_context())


def concat_ctx(arrays):
    return arrays[0]._ctx if arrays else current_context()
