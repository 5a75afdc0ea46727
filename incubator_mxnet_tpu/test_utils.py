"""Test utilities (ref: python/mxnet/test_utils.py).

The cornerstone of the test strategy (SURVEY §4): numeric-gradient
checking against numpy references, cross-backend consistency, random
array/shape generators, tolerance maps.
"""
from __future__ import annotations

import os

import numpy as _np

from .base import MXNetError, dtype_np
from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray
from . import ndarray as nd

__all__ = ["default_context", "set_default_context", "assert_almost_equal",
           "almost_equal", "same", "rand_ndarray", "rand_shape_nd",
           "rand_shape_2d", "rand_shape_3d", "random_arrays",
           "check_numeric_gradient", "numeric_grad", "check_consistency",
           "effective_dtype", "environment", "assert_exception"]

_DEFAULT_RTOL = {
    _np.dtype(_np.float16): 1e-2,
    _np.dtype(_np.float32): 1e-4,
    _np.dtype(_np.float64): 1e-5,
}
_DEFAULT_ATOL = {
    _np.dtype(_np.float16): 1e-3,
    _np.dtype(_np.float32): 1e-5,
    _np.dtype(_np.float64): 1e-7,
}


def default_context() -> Context:
    """ref: test_utils.default_context — the test context."""
    return current_context()


def set_default_context(ctx: Context):
    Context._default.stack = [ctx]


def _as_np(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    return _np.asarray(x)


def same(a, b):
    return _np.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _as_np(a), _as_np(b)
    rtol = rtol if rtol is not None else \
        _DEFAULT_RTOL.get(a.dtype, 1e-5)
    atol = atol if atol is not None else \
        _DEFAULT_ATOL.get(a.dtype, 1e-7)
    return _np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    a_np, b_np = _as_np(a).astype(_np.float64), _as_np(b).astype(_np.float64)
    rtol = rtol if rtol is not None else \
        _DEFAULT_RTOL.get(_as_np(a).dtype, 1e-4)
    atol = atol if atol is not None else \
        _DEFAULT_ATOL.get(_as_np(a).dtype, 1e-5)
    if not _np.allclose(a_np, b_np, rtol=rtol, atol=atol,
                        equal_nan=equal_nan):
        err = _np.abs(a_np - b_np)
        rel = err / (_np.abs(b_np) + atol)
        raise AssertionError(
            "%s and %s differ: max abs err %g, max rel err %g "
            "(rtol=%g atol=%g)\n%r\nvs\n%r"
            % (names[0], names[1], err.max(), rel.max(), rtol, atol,
               a_np.ravel()[:8], b_np.ravel()[:8]))


def rand_shape_nd(ndim, dim=10, allow_zero_size=False):
    low = 0 if allow_zero_size else 1
    return tuple(_np.random.randint(low, dim + 1, size=ndim).tolist())


def rand_shape_2d(dim0=10, dim1=10):
    return (_np.random.randint(1, dim0 + 1), _np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_np.random.randint(1, dim0 + 1), _np.random.randint(1, dim1 + 1),
            _np.random.randint(1, dim2 + 1))


def random_arrays(*shapes):
    arrays = [_np.random.randn(*s).astype(_np.float32) if s else
              _np.asarray(_np.random.randn(), dtype=_np.float32)
              for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def rand_ndarray(shape, stype="default", density=None, dtype="float32",
                 ctx=None):
    """Dense or sparse random array (ref: rand_ndarray incl. densities)."""
    ctx = ctx or default_context()
    a = _np.random.uniform(-1, 1, size=shape).astype(dtype_np(dtype))
    if stype == "default":
        return nd.array(a, ctx=ctx)
    density = 0.5 if density is None else density
    mask = _np.random.rand(*shape) < density
    a = a * mask
    from .ndarray.sparse import cast_storage
    return cast_storage(nd.array(a, ctx=ctx), stype)


def numeric_grad(f, x, eps=1e-4):
    """Central-difference gradient of scalar-valued f at numpy x."""
    x = x.astype(_np.float64)
    grad = _np.zeros_like(x)
    it = _np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + eps
        fp = f(x)
        x[idx] = orig - eps
        fm = f(x)
        x[idx] = orig
        grad[idx] = (fp - fm) / (2 * eps)
        it.iternext()
    return grad


def check_numeric_gradient(fn, inputs, rtol=1e-2, atol=1e-3, eps=1e-3,
                           argnums=None):
    """Compare autograd gradients of `fn` (NDArray→NDArray, scalar-summed)
    against central differences (ref: check_numeric_gradient).

    `fn` takes NDArrays, returns an NDArray (any shape — summed to scalar).
    """
    from . import autograd as ag
    nds = [nd.array(x.astype(_np.float64).astype(_np.float32))
           for x in inputs]
    check = range(len(nds)) if argnums is None else argnums
    for i in check:
        nds[i].attach_grad()
    with ag.record():
        out = fn(*nds)
        loss = out.sum()
    loss.backward()

    for i in check:
        def scalar_f(x_np, i=i):
            args = [n.asnumpy().astype(_np.float64) for n in nds]
            args[i] = x_np
            vals = [nd.array(a.astype(_np.float32)) for a in args]
            return float(fn(*vals).sum().asscalar())
        num = numeric_grad(scalar_f, inputs[i].astype(_np.float64), eps)
        sym = nds[i].grad.asnumpy()
        assert_almost_equal(sym, num, rtol=rtol, atol=atol,
                            names=("autograd", "numeric"))


def check_consistency(fn, inputs, ctx_list=None, rtol=1e-4, atol=1e-5):
    """Run `fn` on multiple contexts and compare outputs (ref:
    check_consistency cpu/gpu/cudnn cross-check; here cpu vs tpu)."""
    ctx_list = ctx_list or [cpu()]
    outs = []
    for ctx in ctx_list:
        args = [nd.array(x, ctx=ctx) for x in inputs]
        outs.append(_as_np(fn(*args)))
    for o in outs[1:]:
        assert_almost_equal(outs[0], o, rtol=rtol, atol=atol)


def effective_dtype(dtype):
    return dtype_np(dtype)


class environment:
    """ref: test_utils.environment — temporary env var scope."""

    def __init__(self, *args):
        if len(args) == 2:
            self._kwargs = {args[0]: args[1]}
        else:
            self._kwargs = args[0]
        self._saved = {}

    def __enter__(self):
        for k, v in self._kwargs.items():
            self._saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        return self

    def __exit__(self, *exc):
        for k, v in self._saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def assert_exception(f, exception_type, *args, **kwargs):
    try:
        f(*args, **kwargs)
    except exception_type:
        return
    raise AssertionError("did not raise %s" % exception_type)
