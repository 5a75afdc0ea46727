"""Imperative autograd.

TPU-native re-design of the reference autograd
(ref: src/imperative/imperative.cc — Imperative::RecordOp/Backward, the
nnvm tape over AGInfo nodes; python/mxnet/autograd.py — record/pause/
train_mode/backward/grad).

Design: instead of building an nnvm graph and running a `Gradient` pass,
every recorded op captures a **jax.vjp pullback** at forward time (the
residuals play the role of the reference's saved forward buffers).
`backward()` walks the Python-level tape in reverse topological order and
applies pullbacks; each pullback executes as XLA computations, and for
hybridized blocks the whole block is ONE pullback whose transpose is a
single compiled executable (ref CachedOp::Backward equivalence).

Thread-local `is_recording`/`is_training` flags mirror the reference's
(`Imperative::is_recording_`/`is_np_shape_` TLS).
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax
import numpy as _np

from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "backward", "grad", "mark_variables",
           "set_recording", "set_training", "get_symbol", "Function",
           "flush_pending"]


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False


_STATE = _State()


# ---------------------------------------------------------------------------
# deferred dispatch (the async-engine analogue, ref: threaded_engine.cc op
# queue): cached-op forwards and single-program backwards may defer their
# XLA dispatch so the NEXT consumer can compose with them into ONE
# executable (loss fused into the net's fwd+vjp; optimizer fused into the
# backward).  Pendings register here per-thread; reading any lazy
# NDArray's buffer forces the underlying program.
# ---------------------------------------------------------------------------


class _PendingTL(threading.local):
    def __init__(self):
        self.fwd = []       # deferred cached-op forwards (_PendingCall)
        self.bwd = []       # deferred backward grads (_PendingGrads)


_PENDINGS = _PendingTL()


def _register_pending(p, kind="fwd"):
    (_PENDINGS.fwd if kind == "fwd" else _PENDINGS.bwd).append(p)


def _unregister_pending(p):
    for lst in (_PENDINGS.fwd, _PENDINGS.bwd):
        try:
            lst.remove(p)
        except ValueError:
            pass


def flush_pending(kind="fwd"):
    """Force deferred programs: 'fwd' = pending cached-op forwards (their
    tape nodes + aux-state writebacks must exist before backward / scope
    exit); 'all' additionally forces deferred backward grads (waitall
    barrier semantics).  A forward pending CLAIMED by a deferred
    backward is skipped at 'fwd' flushes — the claim guarantees a later
    step/force materialises it (or the 'all' flush does, through the
    backward pending)."""
    for p in list(_PENDINGS.fwd):
        if not getattr(p, "claimed", False):
            p.force()
    if kind == "all":
        for p in list(_PENDINGS.bwd):
            p.force()
        for p in list(_PENDINGS.fwd):
            p.force()


# one shared residual-consuming backward executable applier: jit caches
# per closure-treedef, so every cached-op / fused program reuses this
_BWD_APPLY = None


def _bwd_apply():
    global _BWD_APPLY
    if _BWD_APPLY is None:
        _BWD_APPLY = jax.jit(lambda v, cots: v(cots))
    return _BWD_APPLY


class _JitVjp:
    """Pullback of a (possibly fused) cached-op program.

    Applies the jitted residual-consuming backward in ONE executable and
    keeps only the gradient positions that correspond to tape inputs
    (rng key-bits / fused-interior grads are dropped).  Exposing the
    closure lets backward() defer the whole application so the optimizer
    step can compose with it (ref: CachedOp::Backward feeding the
    update ops in one bulked segment, SURVEY §3.3)."""

    __slots__ = ("closure", "keep")

    def __init__(self, closure, keep):
        self.closure = closure
        self.keep = keep

    def __call__(self, cots):
        g = _bwd_apply()(self.closure, tuple(cots))
        return tuple(g[i] for i in self.keep)


class _PendingGrads:
    """A deferred single-program backward: holds the vjp closure + seed
    cotangents; forcing runs ONE executable and writes every leaf grad.
    The aggregated optimizer update recognises it and composes backward +
    update into one program instead (optimizer/optimizer.py)."""

    will_record = False

    def __init__(self, vjp, cots, items, producer=None):
        # items: list of (grad_nd, full_grad_index, shape, np_dtype)
        # producer: a still-deferred fused forward (gluon block layer) —
        # force() runs it first; the fused optimizer path composes
        # forward+backward+update into ONE executable instead
        self.vjp = vjp
        self.cots = cots
        self.items = items
        self.producer = producer
        self.done = False
        # O(1) lookups — the aggregated optimizer queries every grad
        # every step (items hold strong nd refs, so id() stays valid)
        self._by_id = {id(nd): (i, s, dt) for nd, i, s, dt in items}
        for nd, _i, _s, _dt in items:
            nd._data_v = None
            nd._pending = self
        _register_pending(self, "bwd")

    def aval_of(self, nd):
        i, s, dt = self._by_id[id(nd)]
        return (s, dt)

    def index_for(self, nd):
        return self._by_id[id(nd)][0]

    def covers(self, grad_nds):
        ids = {id(g) for g in grad_nds}
        return all(id(nd) in ids for nd, _i, _s, _dt in self.items)

    def force(self):
        if self.done:
            return
        self.done = True
        _unregister_pending(self)
        if self.producer is not None:
            self.producer.force()           # fwd program + tape + states
            closure = self.producer.vjp_closure
        else:
            closure = self.vjp.closure
        g = _bwd_apply()(closure, self.cots)
        for nd, i, _s, dt in self.items:
            if nd._pending is self:
                nd._data = g[i].astype(dt)

    def detach_target(self, g):
        """A newer backward overwrites this grad (grad_req=write): drop
        it here.  If nothing is left to produce, release the claim on
        the deferred forward so normal flushes materialise its
        aux-state writebacks."""
        self.items = [it for it in self.items if it[0] is not g]
        self._by_id.pop(id(g), None)
        g._pending = None
        if not self.items and not self.done:
            self.done = True
            _unregister_pending(self)
            if self.producer is not None:
                self.producer.claimed = False

    def fulfill(self, pairs):
        """Called by the fused backward+optimizer program: grads came out
        of that executable; write them through by identity."""
        self.done = True
        _unregister_pending(self)
        for nd, val in pairs:
            if nd._pending is self:
                nd._data = val


def is_recording() -> bool:
    return _STATE.recording


def is_training() -> bool:
    return _STATE.training


def set_recording(flag: bool) -> bool:
    prev = _STATE.recording
    _STATE.recording = bool(flag)
    return prev


def set_training(flag: bool) -> bool:
    prev = _STATE.training
    _STATE.training = bool(flag)
    return prev


class _RecordingStateScope:
    def __init__(self, recording: Optional[bool], training: Optional[bool]):
        self._rec, self._train = recording, training
        self._prev_rec = self._prev_train = None

    def __enter__(self):
        if self._rec is not None:
            self._prev_rec = set_recording(self._rec)
        if self._train is not None:
            self._prev_train = set_training(self._train)
        return self

    def __exit__(self, *exc):
        if self._rec is True and (not exc or exc[0] is None):
            # leaving a record scope: deferred forwards must materialise
            # (tape nodes + aux-state writebacks) while their logical
            # execution context still holds
            flush_pending("fwd")
        if self._rec is not None:
            set_recording(self._prev_rec)
        if self._train is not None:
            set_training(self._prev_train)


def record(train_mode: bool = True):
    """`with autograd.record():` — turn on recording + training mode."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode: bool = False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# tape
# ---------------------------------------------------------------------------


class Node:
    """One recorded op application (ref: nnvm node + AGInfo).

    Holds the vjp pullback (with residuals), references to input NDArrays
    (for graph connectivity) and output array metadata (to synthesise zero
    cotangents for unused outputs).
    """

    __slots__ = ("vjp_fn", "inputs", "n_out", "out_shapes", "out_dtypes",
                 "name", "out_is_tuple", "raw_fn", "op_attrs")

    def __init__(self, vjp_fn, inputs, outputs, name="", out_is_tuple=False,
                 raw_fn=None, op_attrs=None):
        self.vjp_fn = vjp_fn
        self.inputs = list(inputs)          # NDArray refs (graph edges)
        self.n_out = len(outputs)
        self.out_shapes = [o.shape for o in outputs]
        self.out_dtypes = [o.dtype for o in outputs]
        self.name = name
        self.out_is_tuple = out_is_tuple
        # the pure forward fn on raw arrays (attrs closed over): kept so
        # create_graph backward can RE-RECORD the pullback application
        # as a differentiable op (jax re-linearizes at the saved inputs)
        self.raw_fn = raw_fn
        # (registry opname, attr kwargs) for ops invoked through the op
        # registry — enough to rebuild this node symbolically
        # (get_symbol); None for opaque pullbacks
        self.op_attrs = op_attrs


def _is_float0(x):
    return getattr(x, "dtype", None) == jax.dtypes.float0


# Zero/one cotangent constants are recreated every backward (one per
# unused output — e.g. each BatchNorm's aux stats).  Each jnp.zeros is a
# device dispatch (a cost chosen around on an earlier setup; not
# re-measured on this chip).  They
# are immutable and never donated, so cache per (shape, dtype).
_CONST_CACHE = {}


def _zeros_const(shape, dtype):
    from .engine import host_const
    key = ("z", tuple(shape), str(dtype))
    v = _CONST_CACHE.get(key)
    if v is None or v.is_deleted():
        v = host_const(shape, dtype)
        _CONST_CACHE[key] = v
    return v


def _ones_const(shape, dtype):
    from .engine import host_const
    key = ("o", tuple(shape), str(dtype))
    v = _CONST_CACHE.get(key)
    if v is None or v.is_deleted():
        v = host_const(shape, dtype, fill=1.0)
        _CONST_CACHE[key] = v
    return v


def _requires_tracking(nd) -> bool:
    if nd is None:
        return False
    if nd._tape_node is not None or nd._grad_req not in (None, "null"):
        return True
    # a lazy cached-op output records its tape node at force time — it
    # WILL be tracked, so consumers must record too
    p = getattr(nd, "_pending", None)
    return p is not None and getattr(p, "will_record", False)


def _is_rsp(x):
    from .ndarray.sparse import RowSparseNDArray
    return isinstance(x, RowSparseNDArray)


def _accum_cot(a, b):
    """Accumulate two cotangents, either of which may be a
    RowSparseNDArray (sparse Embedding grads) or a jax array."""
    if _is_rsp(a) or _is_rsp(b):
        from .ndarray.sparse import add as sparse_add
        if _is_rsp(a) and _is_rsp(b):
            return sparse_add(a, b)
        dense = a if not _is_rsp(a) else b
        rsp = a if _is_rsp(a) else b
        return rsp.tostype("default")._data + dense
    return a + b


def _densify_cot(c):
    return c.tostype("default")._data if _is_rsp(c) else c


def record_op(vjp_fn, input_nds, output_nds, name="", out_is_tuple=False,
              raw_fn=None, op_attrs=None):
    """Attach a tape node linking inputs → outputs. Called by the NDArray
    dispatch layer when recording is on and ≥1 input is tracked."""
    node = Node(vjp_fn, input_nds, output_nds, name, out_is_tuple,
                raw_fn=raw_fn, op_attrs=op_attrs)
    for i, o in enumerate(output_nds):
        o._tape_node = node
        o._out_index = i
    return node


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _seed_cotangents(heads, head_grads, default_grad, unwrap, api):
    """Normalise heads/head_grads, validate lengths, and build the root
    node list plus the initial cotangent map keyed by
    (id(node), out_index). `default_grad(h)` makes the ones-cotangent
    for a bare head; `unwrap(hg)` adapts a user-given gradient."""
    if not isinstance(heads, (list, tuple)):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif not isinstance(head_grads, (list, tuple)):
        head_grads = [head_grads]
    if len(head_grads) != len(heads):
        raise MXNetError(
            "%s: %d head gradients for %d heads"
            % (api, len(head_grads), len(heads)))
    root_nodes, cot = [], {}
    for h, hg in zip(heads, head_grads):
        p = getattr(h, "_pending", None)
        if p is not None:
            # a still-deferred head (e.g. a lazy reshape consumed by a
            # fused program): materialise it so its tape node exists
            p.force()
        node = h._tape_node
        if node is None:
            raise MXNetError(
                "cannot differentiate: output was not computed while "
                "recording (is autograd.record() active?)")
        root_nodes.append(node)
        g = default_grad(h) if hg is None else unwrap(hg)
        key = (id(node), h._out_index)
        cot[key] = cot[key] + g if key in cot else g
    return root_nodes, cot


def _topo_order(root_nodes):
    order, seen = [], set()
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, done = stack.pop()
        if done:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for inp in node.inputs:
            pn = inp._tape_node
            if pn is not None and id(pn) not in seen:
                stack.append((pn, False))
    return order   # parents before children


def _try_defer_backward(node, cot):
    """Single-tape-node backward (the steady-state hybridized step):
    instead of dispatching the backward executable now, park the vjp
    closure + seed cotangents as a _PendingGrads.  Returns False when the
    eager path must run (sparse/add grads, float0 outputs, duplicate
    inputs, missing grad buffers)."""
    import jax.numpy as jnp
    cots = []
    for i in range(node.n_out):
        c = cot.get((id(node), i))
        if c is None:
            if not jnp.issubdtype(node.out_dtypes[i], jnp.inexact):
                return False        # float0 cots can't ride through jit args
            c = _zeros_const(node.out_shapes[i], node.out_dtypes[i])
        elif _is_rsp(c):
            return False
        cots.append(c)
    targets = []
    seen = set()
    for j, inp in enumerate(node.inputs):
        if inp is None or inp._grad_req in (None, "null"):
            continue
        if (inp._grad_req != "write" or inp._grad is None or
                _is_rsp(inp._grad) or id(inp) in seen):
            return False
        seen.add(id(inp))
        targets.append((j, inp))
    if not targets:
        return False
    for i in range(node.n_out):
        cot.pop((id(node), i), None)
    vjp = node.vjp_fn
    items = []
    for j, inp in targets:
        g = inp._grad
        shp, dt = tuple(g.shape), g.dtype   # aval-aware: no forcing
        stale = g._pending
        if stale is not None:           # grad_req=write overwrites: detach
            stale.detach_target(g)
        items.append((g, vjp.keep[j], shp, dt))
    _PendingGrads(vjp, tuple(cots), items)
    node.vjp_fn = None                  # retain_graph=False contract
    return True


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             variables=None):
    """Run backward from `heads`.

    If `variables` is given, returns their gradients (autograd.grad
    semantics, ref: MXAutogradBackwardEx w/ var handles); otherwise
    accumulates into leaves' `.grad` per their grad_req.
    """
    import jax.numpy as jnp
    from . import config as _cfg
    fusion_on = _cfg.get("MXNET_CACHEDOP_FUSION") == "1"

    if variables is None and not retain_graph and fusion_on:
        hs = heads if isinstance(heads, (list, tuple)) else [heads]
        if len(hs) == 1:
            p = getattr(hs[0], "_pending", None)
            if p is not None and hasattr(p, "defer_backward"):
                hg = None
                if head_grads is not None:
                    hg = head_grads[0] if isinstance(
                        head_grads, (list, tuple)) else head_grads
                if p.defer_backward(hs[0], hg):
                    # forward AND backward both deferred: Trainer.step
                    # composes fwd+vjp+update into ONE executable
                    return None

    flush_pending("fwd")
    root_nodes, cot = _seed_cotangents(
        heads, head_grads,
        default_grad=lambda h: _ones_const(h.shape, h.dtype),
        unwrap=lambda hg: hg._data, api="backward")

    order = _topo_order(root_nodes)

    if (variables is None and not retain_graph and len(order) == 1
            and isinstance(order[0].vjp_fn, _JitVjp)
            and fusion_on
            and _try_defer_backward(order[0], cot)):
        # whole backward is ONE deferred program: grads materialise on
        # first read, or fuse into the optimizer update (Trainer.step)
        return None

    var_ids = None
    var_grads = {}
    if variables is not None:
        if not isinstance(variables, (list, tuple)):
            variables = [variables]
        var_ids = {id(v): i for i, v in enumerate(variables)}

    leaf_updates = {}       # id(nd) -> (nd, jax array)

    for node in reversed(order):
        cots = []
        any_c = False
        for i in range(node.n_out):
            c = cot.pop((id(node), i), None)
            if c is None:
                dt = node.out_dtypes[i]
                if not jnp.issubdtype(dt, jnp.inexact):
                    # integer/bool outputs take float0 cotangents
                    c = _np.zeros(node.out_shapes[i], jax.dtypes.float0)
                else:
                    c = _zeros_const(node.out_shapes[i], dt)
            else:
                any_c = True
            cots.append(c)
        if not any_c:
            continue
        if node.vjp_fn is None:
            raise MXNetError(
                "graph already freed — pass retain_graph=True to backward "
                "to call it twice (ref: same contract as MXNet autograd)")
        arg = tuple(cots) if node.out_is_tuple else cots[0]
        in_cots = node.vjp_fn(arg)
        for inp, ic in zip(node.inputs, in_cots):
            if inp is None or _is_float0(ic):
                continue
            pn = inp._tape_node
            if pn is not None:
                # only leaves keep sparse grads; interior flow densifies
                # (ref: storage-type inference falls back to dense)
                key = (id(pn), inp._out_index)
                icd = _densify_cot(ic)
                cot[key] = cot[key] + icd if key in cot else icd
            if var_ids is not None:
                if id(inp) in var_ids and pn is None:
                    k = id(inp)
                    var_grads[k] = _accum_cot(var_grads[k], ic) \
                        if k in var_grads else ic
            if pn is None and inp._grad_req not in (None, "null"):
                k = id(inp)
                if k in leaf_updates:
                    leaf_updates[k] = (inp, _accum_cot(leaf_updates[k][1],
                                                       ic))
                else:
                    leaf_updates[k] = (inp, ic)

    if not retain_graph:
        for node in order:
            node.vjp_fn = None

    if variables is not None:
        from .ndarray import NDArray
        out = []
        for v in variables:
            g = var_grads.get(id(v))
            if g is None:
                g = jnp.zeros(v.shape, v.dtype)
            out.append(g if _is_rsp(g) else NDArray(g, ctx=v.context))
        return out

    # accumulate into leaf .grad per grad_req
    for nd, g in leaf_updates.values():
        if nd._grad is None:
            continue
        grad_is_sparse = _is_rsp(nd._grad)
        if _is_rsp(g) and not grad_is_sparse:
            g = g.tostype("default")._data       # dense grad buffer
        if grad_is_sparse:
            # row_sparse grad container (grad_stype='row_sparse'):
            # 'write' replaces the stored rows, 'add' merges them
            if not _is_rsp(g):
                from .ndarray.sparse import cast_storage
                from .ndarray import NDArray as _ND
                g = cast_storage(_ND(g, ctx=nd.context), "row_sparse")
            if nd._grad_req == "add" and nd._grad.indices.shape[0] > 0:
                from .ndarray.sparse import add as sparse_add
                nd._grad = sparse_add(nd._grad, g)
            else:
                nd._grad = g
            continue
        if nd._grad_req == "add":
            nd._grad._data = nd._grad._data + g.astype(nd._grad._data.dtype)
        else:   # write
            nd._grad._data = g.astype(nd._grad._data.dtype)
    return None


def _backward_create_graph(heads, head_grads, variables, train_mode,
                           retain_graph=True):
    """Differentiable backward (ref: autograd.grad(create_graph=True)).

    The pullback of each tape node is RE-APPLIED as a recorded op: the
    node's saved `raw_fn` is re-linearised (jax.vjp) at its original
    inputs inside a fresh dispatch, so the returned gradients are
    themselves tape-tracked NDArrays whose graph reaches back through
    BOTH the cotangent path and the original inputs — exactly what a
    second `backward()` needs."""
    import jax.numpy as jnp
    from .ndarray import NDArray
    from .ndarray.ndarray import apply_fn

    flush_pending("fwd")
    if not isinstance(variables, (list, tuple)):
        variables = [variables]
    root_nodes, cot = _seed_cotangents(
        heads, head_grads,
        default_grad=lambda h: NDArray(_ones_const(h.shape, h.dtype)),
        unwrap=lambda hg: hg, api="grad")

    order = _topo_order(root_nodes)
    var_ids = {id(v) for v in variables}
    var_grads = {}

    with _RecordingStateScope(True, train_mode):
        for node in reversed(order):
            active = [i for i in range(node.n_out)
                      if (id(node), i) in cot and
                      jnp.issubdtype(node.out_dtypes[i], jnp.inexact)]
            if not active:
                for i in range(node.n_out):
                    cot.pop((id(node), i), None)
                continue
            if node.raw_fn is None:
                raise NotImplementedError(
                    "create_graph=True through %r: this node recorded "
                    "only an opaque pullback (hybridized block or custom "
                    "Function); run the forward unhybridized" % node.name)
            active_cots = [cot.pop((id(node), i)) for i in active]
            float_in = [k for k, inp in enumerate(node.inputs)
                        if jnp.issubdtype(inp.dtype, jnp.inexact)]
            raw_fn = node.raw_fn
            n_in = len(node.inputs)
            n_out, shapes, dtypes = (node.n_out, node.out_shapes,
                                     node.out_dtypes)
            multi = node.out_is_tuple

            def bwd_composite(*arrs, _raw=raw_fn, _n_in=n_in,
                              _n_out=n_out, _shapes=shapes,
                              _dtypes=dtypes, _active=tuple(active),
                              _float_in=tuple(float_in), _multi=multi):
                xs, cs = arrs[:_n_in], arrs[_n_in:]
                _, pb = jax.vjp(_raw, *xs)
                full, j = [], 0
                for i in range(_n_out):
                    if i in _active:
                        full.append(cs[j])
                        j += 1
                    elif not jnp.issubdtype(_dtypes[i], jnp.inexact):
                        full.append(_np.zeros(_shapes[i],
                                              jax.dtypes.float0))
                    else:
                        full.append(jnp.zeros(_shapes[i], _dtypes[i]))
                in_cots = pb(tuple(full) if _multi else full[0])
                return tuple(in_cots[k] for k in _float_in)

            outs = apply_fn(bwd_composite,
                            list(node.inputs) + active_cots, {},
                            name=(node.name or "op") + "_backward")
            if not isinstance(outs, tuple):
                outs = (outs,)
            for k, icnd in zip(float_in, outs):
                inp = node.inputs[k]
                pn = inp._tape_node
                if pn is not None:
                    key = (id(pn), inp._out_index)
                    cot[key] = cot[key] + icnd if key in cot else icnd
                elif id(inp) in var_ids:
                    key = id(inp)
                    var_grads[key] = (var_grads[key] + icnd
                                      if key in var_grads else icnd)

    if not retain_graph:
        # honour an explicit retain_graph=False: free the forward
        # residuals now; a later backward() through the returned grads
        # will fail loudly instead of silently pinning device memory
        for node in order:
            node.vjp_fn = None
            node.raw_fn = None

    out = []
    for v in variables:
        g = var_grads.get(id(v))
        if g is None:
            g = NDArray(_zeros_const(v.shape, v.dtype))
        out.append(g)
    return out


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """ref: python/mxnet/autograd.py grad(). With create_graph=True the
    returned gradients are tape-tracked, so a second backward() through
    them yields higher-order gradients."""
    if retain_graph is None:
        retain_graph = create_graph
    if create_graph:
        return _backward_create_graph(heads, head_grads, variables,
                                      train_mode, retain_graph)
    return backward(heads, head_grads, retain_graph=retain_graph,
                    train_mode=train_mode, variables=variables)


def mark_variables(variables, gradients, grad_reqs="write"):
    """ref: autograd.mark_variables — attach explicit grad buffers."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, r in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = r


def get_symbol(x):
    """Rebuild the recorded imperative computation reaching `x` as a
    Symbol graph (ref: python/mxnet/autograd.py get_symbol /
    MXAutogradGetSymbol — there every imperative op IS an nnvm node, so
    the tape is already a graph; here registry ops record their
    (opname, attrs) and the tape re-composes through the symbol stubs).

    Supported for chains of registry ops — the reference's own scope.
    Opaque pullbacks (hybridized cached-op segments, custom
    autograd.Function, raw getitem) raise with guidance: run the forward
    unhybridized, or use HybridBlock.export for whole-block graphs."""
    from .symbol import symbol as _sym
    flush_pending("fwd")
    p = getattr(x, "_pending", None)
    if p is not None:
        p.force()
    node = getattr(x, "_tape_node", None)
    if node is None:
        raise MXNetError(
            "get_symbol: array was not computed under autograd.record()")
    order = _topo_order([node])     # parents before children
    memo = {}
    var_syms = {}
    counter = [0]

    def leaf_sym(nd):
        k = id(nd)
        if k not in var_syms:
            var_syms[k] = _sym.var("var%d" % counter[0],
                                   shape=tuple(nd.shape))
            counter[0] += 1
        return var_syms[k]

    for n in order:
        if n.op_attrs is None:
            raise NotImplementedError(
                "autograd.get_symbol through %r: this tape node is an "
                "opaque pullback (hybridized block / custom Function / "
                "indexing); run the forward unhybridized with registry "
                "ops, or use HybridBlock.export" % (n.name or "op"))
        opname, attrs = n.op_attrs
        ins = []
        for inp in n.inputs:
            pn = inp._tape_node
            ins.append(memo[(id(pn), inp._out_index)]
                       if pn is not None else leaf_sym(inp))
        s = _sym.apply_stub_args(opname, ins, dict(attrs))
        if n.n_out > 1:
            for i in range(n.n_out):
                memo[(id(n), i)] = s[i]
        else:
            memo[(id(n), 0)] = s
    return memo[(id(node), x._out_index)]


class Function:
    """User-defined differentiable operation (ref: python/mxnet/
    autograd.py Function + src/operator/custom/custom.cc CustomOp).

    Subclass, implement `forward(*inputs)` and
    `backward(*output_grads)`, then call the instance like a function::

        class sigmoid(autograd.Function):
            def forward(self, x):
                y = 1 / (1 + nd.exp(-x))
                self.save_for_backward(y)
                return y
            def backward(self, dy):
                y, = self.saved_tensors
                return dy * y * (1 - y)

    Both methods run with autograd paused (the reference runs CustomOp
    bodies outside the recording scope); the instance is recorded on the
    tape as ONE node whose pullback calls `backward`.  `backward` must
    return one gradient per NDArray input (None for non-differentiable
    inputs)."""

    def __init__(self):
        self.saved_tensors = ()

    def save_for_backward(self, *args):
        self.saved_tensors = args

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import NDArray
        nd_inputs = [a for a in inputs if isinstance(a, NDArray)]
        with pause():
            outputs = self.forward(*inputs)
        multi = isinstance(outputs, (list, tuple))
        outs = tuple(outputs) if multi else (outputs,)
        if is_recording() and any(_requires_tracking(a)
                                  for a in nd_inputs):
            ctx = nd_inputs[0].context if nd_inputs else None

            def vjp_fn(cot, _self=self, _n=len(nd_inputs)):
                cots = cot if isinstance(cot, tuple) else (cot,)
                ograds = [NDArray(c, ctx=ctx) for c in cots]
                with pause():
                    igrads = _self.backward(*ograds)
                if not isinstance(igrads, (list, tuple)):
                    igrads = [igrads]
                if len(igrads) != _n:
                    raise MXNetError(
                        "%s.backward returned %d gradients for %d "
                        "array inputs" % (type(_self).__name__,
                                          len(igrads), _n))
                raw = []
                for g, inp in zip(igrads, nd_inputs):
                    if g is None:       # non-differentiable input
                        raw.append(_np.zeros(inp.shape,
                                             jax.dtypes.float0))
                    else:
                        raw.append(g._data if isinstance(g, NDArray)
                                   else g)
                return raw

            record_op(vjp_fn, nd_inputs, outs,
                      name=type(self).__name__, out_is_tuple=multi)
        return outputs
