"""Optimizers.

TPU-native re-design of the reference optimizer layer
(ref: python/mxnet/optimizer/optimizer.py — Optimizer registry, SGD/Adam/
... classes picking fused native update ops from src/operator/optimizer_op.cc).

The key design point is carried over: **the update is an op, not Python
arithmetic**.  Each `update()` call dispatches one jit-compiled XLA
computation per parameter with donated input buffers, so weight + state
are updated in place at the XLA level.  Scalars (lr/wd/…) are passed as
traced 0-d arrays so lr schedules don't trigger recompilation.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from ..ops import registry as _registry

__all__ = ["Optimizer", "SGD", "NAG", "Adam", "AdaGrad", "AdaDelta",
           "RMSProp", "Ftrl", "Signum", "SignSGD", "LAMB", "Adamax",
           "Nadam", "SGLD", "Test", "register", "create", "get_updater",
           "Updater"]


# ---------------------------------------------------------------------------
# jitted fused-update cache
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jit_update(opname: str, static_kv: tuple, donate_idx: tuple = (),
                out_ref_idx: tuple = None):
    """Jit a fused update op with per-position donation.  Arrays are
    passed as separate positional args (scalars dict last) so
    `donate_argnums` can donate weight/state buffers while leaving the
    gradient untouched — `Parameter._grad` still references it after the
    step (donating it dereferences a dead buffer on real TPU, where
    donation is enforced; CPU ignores it and hid the bug)."""
    fn = _registry.get(opname).fn

    def f(*args):
        arrs, scalars = args[:-1], args[-1]
        out = fn(*arrs, **scalars, **dict(static_kv))
        # scalars ride in as f32 arrays (avoids per-value recompiles),
        # which promotes low-precision weights — cast each output back
        # to its buffer's dtype (reference updates are dtype-preserving,
        # and donation needs matching dtypes to reuse the buffer).
        # out_ref_idx maps output position -> input position; the
        # default fits weight-first update ops fn(w, g, *states) ->
        # (new_w, *new_states)
        if out_ref_idx is not None:
            refs = tuple(arrs[i] for i in out_ref_idx)
        else:
            refs = (arrs[0],) + tuple(arrs[2:])  # weight, *states
        if isinstance(out, tuple):
            return tuple(o.astype(r.dtype) for o, r in zip(out, refs))
        return out.astype(refs[0].dtype)
    return jax.jit(f, donate_argnums=donate_idx)


def _fused(opname, arrays, scalars, static, donate=True,
           out_ref_idx=None):
    """Run a fused update op `fn(weight, grad, *states, ...)`: donates the
    weight/state buffers (positions != 1), never the grad, returns new
    buffers."""
    donate_idx = tuple(i for i in range(len(arrays)) if i != 1) \
        if donate else ()
    jf = _jit_update(opname, tuple(sorted(static.items())), donate_idx,
                     out_ref_idx)
    scal = {k: jnp.asarray(v, jnp.float32) for k, v in scalars.items()}
    return jf(*(a._data for a in arrays), scal)


def _zeros_state(weight):
    """Fresh zero state buffer.  Each state gets its OWN buffer — fused
    updates donate their inputs, and donating one buffer through two
    arguments is an error on real TPU (CPU ignores donation, which hid
    this until hardware runs)."""
    # host zeros + NDArray device_put (see engine.host_const rationale)
    import numpy as _nph
    return NDArray(_nph.zeros(weight.shape, weight._data.dtype),
                   ctx=weight.context)


# ---------------------------------------------------------------------------
# aggregated (multi-tensor) fused update
# ---------------------------------------------------------------------------

def _update_one(fn, w, g, sargs, lr, wd, scalars, static_kv):
    """One parameter's fused update, dtype-preserving (f32 hyper arrays
    must not promote low-precision weight/state buffers).  Shared by
    every aggregated-update executable so cast/donation semantics can't
    diverge between the fused and unfused paths."""
    out = fn(w, g, *sargs, lr=lr, wd=wd, **scalars, **dict(static_kv))
    if sargs:
        return (out[0].astype(w.dtype),
                tuple(o.astype(s.dtype) for o, s in zip(out[1:], sargs)))
    return out.astype(w.dtype), ()


def _transpose_states(per_param, nstates):
    return tuple(tuple(p[j] for p in per_param) for j in range(nstates))


def _rebind_updated(weights, new_ws, state_cols, new_sts):
    for w, nw in zip(weights, new_ws):
        w._data = nw
    for col, ncol in zip(state_cols, new_sts):
        for s, ns in zip(col, ncol):
            s._data = ns


@functools.lru_cache(maxsize=None)
def _jit_multi_update(opname: str, static_kv: tuple, nparam: int,
                      nstates: int):
    """ONE executable updating every parameter (ref: multi_sgd_mom_update /
    multi-tensor apply, src/operator/optimizer_op.cc).  Weights and states
    are donated; grads are not.  Per-param lr/wd ride in as (n,) vectors so
    schedules don't recompile."""
    fn = _registry.get(opname).fn

    def f(ws, gs, states, lrs, wds, scalars):
        new_ws, per_param = [], []
        for i in range(nparam):
            sargs = tuple(states[j][i] for j in range(nstates))
            nw, ns = _update_one(fn, ws[i], gs[i], sargs, lrs[i],
                                 wds[i], scalars, static_kv)
            new_ws.append(nw)
            per_param.append(ns)
        return tuple(new_ws), _transpose_states(per_param, nstates)
    return jax.jit(f, donate_argnums=(0, 2))


@functools.lru_cache(maxsize=None)
def _jit_bwd_multi_update(opname: str, static_kv: tuple, nparam: int,
                          nstates: int, gidx: tuple, gdtypes: tuple):
    """Backward + aggregated update as ONE executable: applies the parked
    vjp closure (the whole model backward) and feeds its gradients
    straight into every parameter's update — the reference's bulked
    backward segment flowing into multi_sgd_mom_update without touching
    HBM-to-dispatch boundaries in between (SURVEY §3.3, §7.1 stage 4).

    Weights are NOT donated: the same buffers appear inside the vjp
    residuals, and donating a buffer that is also read elsewhere voids
    the alias on real TPU.  States are safely donated.  The raw grads are
    returned as outputs so Parameter.grad() keeps reference semantics."""
    fn = _registry.get(opname).fn

    def f(vjp_closure, cots, ws, states, lrs, wds, scalars):
        g_all = vjp_closure(cots)
        new_ws, per_param, gouts = [], [], []
        for i in range(nparam):
            g = g_all[gidx[i]].astype(gdtypes[i])
            gouts.append(g)
            sargs = tuple(states[j][i] for j in range(nstates))
            nw, ns = _update_one(fn, ws[i], g, sargs, lrs[i], wds[i],
                                 scalars, static_kv)
            new_ws.append(nw)
            per_param.append(ns)
        return (tuple(new_ws), _transpose_states(per_param, nstates),
                tuple(gouts))
    return jax.jit(f, donate_argnums=(3,))


def _build_train_step(raw, opname, static_kv, nparam, nstates, gidx,
                      gdtypes, n_leaves):
    """Whole imperative step as ONE executable: forward, vjp, and every
    parameter's update — the residuals never leave the program, and the
    parameter/state buffers are donated for in-place updates.  This is
    ShardedTrainer's one-program structure (SURVEY §3.3) reached from
    the user-facing record()/backward()/step() loop via the deferred
    fused forward (gluon/block.py _PendingFused)."""
    fn = _registry.get(opname).fn

    def f(*args):
        leaves = args[:n_leaves]
        cots, states, lrs, wds, scalars = args[n_leaves:]
        outs, vjp = jax.vjp(raw, *leaves)
        g_all = vjp(tuple(cots))
        new_ws, per_param, gouts = [], [], []
        for i in range(nparam):
            li = gidx[i]
            g = g_all[li].astype(gdtypes[i])
            gouts.append(g)
            sargs = tuple(states[j][i] for j in range(nstates))
            nw, ns = _update_one(fn, leaves[li], g, sargs, lrs[i],
                                 wds[i], scalars, static_kv)
            new_ws.append(nw)
            per_param.append(ns)
        return (tuple(outs), tuple(new_ws),
                _transpose_states(per_param, nstates), tuple(gouts))

    # donate the parameter leaves (updated in place) and the optimizer
    # states; NOT the input/cotangent leaves (reused across steps)
    donate = tuple(gidx) + (n_leaves + 1,)
    from ..telemetry import costs as _costs
    # the fused imperative train step (fwd+vjp+update, ONE program) —
    # the headline row in the cost registry's train family
    return _costs.metered_jit(f, donate_argnums=donate,
                              label="gluon.train_step", kind="train",
                              role="gluon_train_step")


def _train_step_dispatch(prod, pending, opname, static_kv, weights,
                         grads, sts, state_cols, lrs, wds, scal):
    """Compose the deferred forward + deferred backward + this update
    into one program.  Returns False when identity guards fail (a param
    buffer was rebound between forward and step) — callers then force
    the pending chain and take the eager path."""
    prog = prod.prog
    try:
        gidx = tuple(pending.index_for(g) for g in grads)
    except KeyError:
        return False
    if len(set(gidx)) != len(gidx):
        return False
    for w, li in zip(weights, gidx):
        if w._data_v is not prod.leaves[li]:
            return False
    gdt = tuple(str(_np.dtype(g.dtype)) for g in grads)
    n_leaves = len(prod.leaves)
    key = (opname, static_kv, len(weights), len(state_cols), gidx, gdt,
           n_leaves)
    jf = prog.train_step_jits.get(key)
    if jf is None:
        jf = _build_train_step(prog.raw, opname, static_kv,
                               len(weights), len(state_cols), gidx,
                               gdt, n_leaves)
        prog.train_step_jits[key] = jf
    from .. import engine as _engine
    with _engine._dispatch_hook(opname + "_train_step",
                                weights[0].context):
        outs, new_ws, new_sts, gouts = jf(*prod.leaves, pending.cots,
                                          sts, lrs, wds, scal)
    if _engine.has_listeners():
        _engine.emit_fused_ops(
            opname + "_train_step", weights[0].context,
            prog.net_graph._trace_ops.get(prog.net_fkey, []) +
            prog.loss_graph._trace_ops.get(prog.loss_fkey, []) +
            [opname] * len(weights))
    prod.finish_from_train_step(outs)
    pending.fulfill(zip(grads, gouts))
    _rebind_updated(weights, new_ws, state_cols, new_sts)
    return True


_HYPER_CACHE = {}


def _hyper_array(values):
    """Device array of hypers (vector or scalar), cached by value — lr/wd
    rarely change step-to-step and each jnp.asarray is a host→device
    transfer."""
    key = tuple(values) if isinstance(values, (list, tuple)) \
        else float(values)
    v = _HYPER_CACHE.get(key)
    if v is None or v.is_deleted():
        if len(_HYPER_CACHE) >= 512:
            # bound the cache: per-step-unique keys (e.g. Adam's
            # bias-corrected lr vector) would otherwise leak one device
            # buffer per training step forever
            _HYPER_CACHE.clear()
        # host build + device_put (see engine.host_const: a jnp.asarray
        # of a host list is a compile per length)
        import numpy as _nph
        import jax as _jax
        v = _jax.device_put(_nph.asarray(key, _nph.float32))
        _HYPER_CACHE[key] = v
    return v


def _fused_multi(opname, weights, grads, state_cols, lr_list, wd_list,
                 scalars, static, bwd_pending=None):
    """Run the aggregated update.  `state_cols`: one list per state slot
    (e.g. adam: [means, vars]), each parallel to `weights`.

    When `bwd_pending` (a deferred autograd._PendingGrads) is given, the
    whole model backward composes into the SAME executable as the update
    — the imperative step's last two dispatches become one."""
    lrs = _hyper_array(lr_list)
    wds = _hyper_array(wd_list)
    scal = {k: _hyper_array(v) for k, v in scalars.items()}
    sts = tuple(tuple(s._data for s in col) for col in state_cols)
    static_kv = tuple(sorted(static.items()))
    if bwd_pending is not None and not bwd_pending.done:
        prod = getattr(bwd_pending, "producer", None)
        if prod is not None and not prod.done:
            # forward still deferred too: the WHOLE step becomes one
            # executable (fwd + vjp + update, params donated)
            if _train_step_dispatch(prod, bwd_pending, opname,
                                    static_kv, weights, grads, sts,
                                    state_cols, lrs, wds, scal):
                return
            bwd_pending.force()
        else:
            closure = (bwd_pending.vjp.closure
                       if bwd_pending.vjp is not None
                       else prod.vjp_closure)
            gidx = tuple(bwd_pending.index_for(g) for g in grads)
            gdt = tuple(str(_np.dtype(g.dtype)) for g in grads)
            jf = _jit_bwd_multi_update(opname, static_kv, len(weights),
                                       len(state_cols), gidx, gdt)
            ws = tuple(w._data for w in weights)
            new_ws, new_sts, gouts = jf(closure, bwd_pending.cots, ws,
                                        sts, lrs, wds, scal)
            bwd_pending.fulfill(zip(grads, gouts))
            _rebind_updated(weights, new_ws, state_cols, new_sts)
            return
    jf = _jit_multi_update(opname, static_kv, len(weights),
                           len(state_cols))
    ws = tuple(w._data for w in weights)
    gs = tuple(g._data for g in grads)
    new_ws, new_sts = jf(ws, gs, sts, lrs, wds, scal)
    _rebind_updated(weights, new_ws, state_cols, new_sts)


# ---------------------------------------------------------------------------
# base class + registry
# ---------------------------------------------------------------------------

_OPT_REGISTRY = {}


def register(klass):
    _OPT_REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    key = name.lower()
    if key not in _OPT_REGISTRY:
        raise MXNetError("unknown optimizer %r" % name)
    return _OPT_REGISTRY[key](**kwargs)


class Optimizer:
    """ref: mx.optimizer.Optimizer."""

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01,
                 lr_scheduler=None, sym=None, begin_num_update=0,
                 multi_precision=False, param_dict=None,
                 aggregate_num=0):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[int, int] = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = aggregate_num
        self.param_idx2name = dict(param_idx2name or {})
        self.param_dict = dict(param_dict or {})
        self.idx2name = self.param_idx2name

    create_optimizer = staticmethod(create)

    # -- learning rate ----------------------------------------------------
    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is set; cannot set lr directly")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            self._index_update_count.setdefault(idx, self.begin_num_update)
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx],
                                  self.num_update)

    def _get_lr(self, index):
        lr = self.learning_rate
        if index in self.param_dict:
            p = self.param_dict[index]
            lr *= getattr(p, "lr_mult", 1.0)
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            p = self.param_dict[index]
            wd *= getattr(p, "wd_mult", 1.0)
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- subclass interface ----------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and weight.dtype == _np.float16:
            w32 = NDArray(weight._data.astype(jnp.float32), ctx=weight.context)
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and weight.dtype == _np.float16:
            inner_state, w32 = state
            g32 = NDArray(grad._data.astype(jnp.float32), ctx=grad.context)
            self.update(index, w32, g32, inner_state)
            weight._data = w32._data.astype(weight._data.dtype)
        else:
            self.update(index, weight, grad, state)

    # aggregated update: True on subclasses providing an update_multi
    # that batches every parameter into one executable
    aggregatable = False
    # True on subclasses whose update_multi can compose a deferred
    # backward (autograd._PendingGrads) into the update executable
    supports_bwd_fusion = False

    def update_multi(self, indices, weights, grads, states,
                     bwd_pending=None):
        """Update many parameters at once (ref: aggregate_num /
        multi_sgd_* ops).  Default: per-param loop."""
        if bwd_pending is not None:
            bwd_pending.force()
        for i, w, g, s in zip(indices, weights, grads, states):
            self.update_multi_precision(i, w, g, s)

    def _split_sparse(self, indices, weights, grads, states):
        """Partition the batch into (dense positions, sparse positions) —
        row_sparse grads take the per-param FComputeEx-style path."""
        from ..ndarray.sparse import RowSparseNDArray
        dense, sparse = [], []
        for k, g in enumerate(grads):
            (sparse if isinstance(g, RowSparseNDArray) else dense).append(k)
        return dense, sparse

    def __repr__(self):
        return "%s(lr=%s)" % (self.__class__.__name__, self.lr)

    def __getstate__(self):
        # param_dict holds live Parameters (and through them the Trainer);
        # optimizer state files only need the hyper-state
        state = self.__dict__.copy()
        state["param_dict"] = {}
        return state


# ---------------------------------------------------------------------------
# concrete optimizers (fused-op backed)
# ---------------------------------------------------------------------------

@register
class SGD(Optimizer):
    """ref: optimizer.SGD → sgd_update / sgd_mom_update fused ops."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros(weight.shape, weight._data.dtype),
                       ctx=weight.context)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        from ..ndarray.sparse import RowSparseNDArray, sparse_sgd_update
        if isinstance(grad, RowSparseNDArray):
            # lazy row_sparse path (ref: sgd_update FComputeEx)
            sparse_sgd_update(weight, grad, lr, wd, self.rescale_grad,
                              self.clip_gradient, self.lazy_update)
            return
        scal = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        if state is None:
            weight._data = _fused("sgd_update", (weight, grad), scal, static)
        else:
            scal["momentum"] = self.momentum
            new_w, new_m = _fused("sgd_mom_update", (weight, grad, state),
                                  scal, static)
            weight._data, state._data = new_w, new_m

    aggregatable = True
    supports_bwd_fusion = True

    def update_multi(self, indices, weights, grads, states,
                     bwd_pending=None):
        dense, sparse = self._split_sparse(indices, weights, grads, states)
        if sparse and bwd_pending is not None:
            bwd_pending.force()
            bwd_pending = None
        for k in sparse:
            self.update(indices[k], weights[k], grads[k], states[k])
        if not dense:
            return
        for k in dense:
            self._update_count(indices[k])
        lrs = [self._get_lr(indices[k]) for k in dense]
        wds = [self._get_wd(indices[k]) for k in dense]
        scal = dict(rescale_grad=self.rescale_grad)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        ws = [weights[k] for k in dense]
        gs = [grads[k] for k in dense]
        if self.momentum == 0.0:
            _fused_multi("sgd_update", ws, gs, [], lrs, wds, scal, static,
                         bwd_pending=bwd_pending)
        else:
            scal["momentum"] = self.momentum
            _fused_multi("sgd_mom_update", ws, gs,
                         [[states[k] for k in dense]], lrs, wds, scal,
                         static, bwd_pending=bwd_pending)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros(weight.shape, weight._data.dtype),
                       ctx=weight.context)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        scal = dict(lr=lr, wd=wd, rescale_grad=self.rescale_grad,
                    momentum=self.momentum)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        if state is None:
            weight._data = _fused("sgd_update", (weight, grad),
                                  dict(lr=lr, wd=wd,
                                       rescale_grad=self.rescale_grad),
                                  static)
        else:
            new_w, new_m = _fused("nag_mom_update", (weight, grad, state),
                                  scal, static)
            weight._data, state._data = new_w, new_m


@register
class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index)
        lr *= math.sqrt(1.0 - self.beta2 ** t) / (1.0 - self.beta1 ** t)
        mean, var = state
        from ..ndarray.sparse import RowSparseNDArray, sparse_adam_update
        if isinstance(grad, RowSparseNDArray):
            sparse_adam_update(weight, grad, mean, var, lr, self.beta1,
                               self.beta2, self.epsilon,
                               self._get_wd(index), self.rescale_grad,
                               self.clip_gradient, self.lazy_update)
            return
        scal = dict(lr=lr, wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad,
                    beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        new_w, new_m, new_v = _fused("adam_update",
                                     (weight, grad, mean, var), scal, static)
        weight._data, mean._data, var._data = new_w, new_m, new_v

    aggregatable = True
    supports_bwd_fusion = True

    def update_multi(self, indices, weights, grads, states,
                     bwd_pending=None):
        dense, sparse = self._split_sparse(indices, weights, grads, states)
        if sparse and bwd_pending is not None:
            bwd_pending.force()
            bwd_pending = None
        for k in sparse:
            self.update(indices[k], weights[k], grads[k], states[k])
        if not dense:
            return
        lrs = []
        for k in dense:
            self._update_count(indices[k])
            t = self._index_update_count[indices[k]]
            lrs.append(self._get_lr(indices[k]) *
                       math.sqrt(1.0 - self.beta2 ** t) /
                       (1.0 - self.beta1 ** t))
        wds = [self._get_wd(indices[k]) for k in dense]
        scal = dict(rescale_grad=self.rescale_grad, beta1=self.beta1,
                    beta2=self.beta2, epsilon=self.epsilon)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        _fused_multi("adam_update",
                     [weights[k] for k in dense],
                     [grads[k] for k in dense],
                     [[states[k][0] for k in dense],
                      [states[k][1] for k in dense]],
                     lrs, wds, scal, static,
                     bwd_pending=bwd_pending)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return NDArray(jnp.zeros(weight.shape, weight._data.dtype),
                       ctx=weight.context)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        from ..ndarray.sparse import RowSparseNDArray, \
            sparse_adagrad_update
        if isinstance(grad, RowSparseNDArray):
            sparse_adagrad_update(weight, grad, state, self._get_lr(index),
                                  self.float_stable_eps,
                                  self._get_wd(index), self.rescale_grad,
                                  self.clip_gradient)
            return
        scal = dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad,
                    epsilon=self.float_stable_eps)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        new_w, new_h = _fused("adagrad_update", (weight, grad, state),
                              scal, static)
        weight._data, state._data = new_w, new_h


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        acc_g, acc_delta = state
        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight._data
        new_acc_g = self.rho * acc_g._data + (1 - self.rho) * jnp.square(g)
        delta = jnp.sqrt(acc_delta._data + self.epsilon) / \
            jnp.sqrt(new_acc_g + self.epsilon) * g
        new_acc_delta = self.rho * acc_delta._data + \
            (1 - self.rho) * jnp.square(delta)
        weight._data = weight._data - delta
        acc_g._data, acc_delta._data = new_acc_g, new_acc_delta


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_state(weight), _zeros_state(weight),
                    _zeros_state(weight))
        return (_zeros_state(weight),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        scal = dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad, gamma1=self.gamma1,
                    epsilon=self.epsilon)
        static = dict(
            clip_gradient=self.clip_gradient
            if self.clip_gradient is not None else -1.0,
            clip_weights=self.clip_weights
            if self.clip_weights is not None else -1.0)
        if self.centered:
            n, g, delta = state
            scal["gamma2"] = self.gamma2
            new = _fused("rmspropalex_update",
                         (weight, grad, n, g, delta), scal, static)
            weight._data, n._data, g._data, delta._data = new
        else:
            (n,) = state
            new_w, new_n = _fused("rmsprop_update", (weight, grad, n),
                                  scal, static)
            weight._data, n._data = new_w, new_n


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        zed, n = state
        scal = dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad, lamda1=self.lamda1,
                    beta=self.beta)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        new_w, new_z, new_n = _fused("ftrl_update", (weight, grad, zed, n),
                                     scal, static)
        weight._data, zed._data, n._data = new_w, new_z, new_n


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros(weight.shape, weight._data.dtype),
                       ctx=weight.context)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        scal = dict(lr=self._get_lr(index), wd=self._get_wd(index),
                    rescale_grad=self.rescale_grad)
        static = dict(clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        if state is None:
            weight._data = _fused("signsgd_update", (weight, grad),
                                  scal, static)
        else:
            scal.update(momentum=self.momentum, wd_lh=self.wd_lh)
            new_w, new_m = _fused("signum_update", (weight, grad, state),
                                  scal, static)
            weight._data, state._data = new_w, new_m


@register
class SignSGD(Signum):
    def __init__(self, **kwargs):
        kwargs.setdefault("momentum", 0.0)
        super().__init__(**kwargs)


@register
class LAMB(Optimizer):
    """ref: lamb_update_phase1/2 (layer-adaptive large-batch optimizer)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        mean, var = state
        scal = dict(wd=self._get_wd(index), rescale_grad=self.rescale_grad,
                    beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon)
        static = dict(t=t, bias_correction=self.bias_correction,
                      clip_gradient=self.clip_gradient
                      if self.clip_gradient is not None else -1.0)
        # no donation: the weight buffer is read again in phase2.
        # outputs are (g', m, v) — g' mirrors the GRAD's dtype (f32
        # phase-1 math feeds phase 2's trust ratio), not the weight's
        g, new_m, new_v = _fused("lamb_update_phase1",
                                 (weight, grad, mean, var), scal, static,
                                 donate=False, out_ref_idx=(1, 2, 3))
        mean._data, var._data = new_m, new_v
        r1 = jnp.linalg.norm(weight._data)
        r2 = jnp.linalg.norm(g)
        w_nd = weight
        scal2 = dict(lr=self._get_lr(index))
        static2 = dict(
            lower_bound=self.lower_bound
            if self.lower_bound is not None else -1.0,
            upper_bound=self.upper_bound
            if self.upper_bound is not None else -1.0)
        # donate only the weight; g/r1/r2 are fresh phase1 outputs
        jf = _jit_update("lamb_update_phase2", tuple(sorted(static2.items())),
                         donate_idx=(0,))
        new_w = jf(w_nd._data, g, r1, r2,
                   {k: jnp.asarray(v, jnp.float32)
                    for k, v in scal2.items()})
        weight._data = new_w


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        m, u = state
        g = grad._data * self.rescale_grad + \
            self._get_wd(index) * weight._data
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        new_m = self.beta1 * m._data + (1 - self.beta1) * g
        new_u = jnp.maximum(self.beta2 * u._data, jnp.abs(g))
        weight._data = weight._data - lr * new_m / (new_u + 1e-8)
        m._data, u._data = new_m, new_u


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (_zeros_state(weight), _zeros_state(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        m, v = state
        g = grad._data * self.rescale_grad + wd * weight._data
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 **
                                     ((t + 1) * self.schedule_decay))
        self.m_schedule *= momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        g_prime = g / (1.0 - self.m_schedule)
        new_m = self.beta1 * m._data + (1.0 - self.beta1) * g
        new_v = self.beta2 * v._data + (1.0 - self.beta2) * jnp.square(g)
        m_prime = new_m / (1.0 - m_schedule_next)
        v_prime = new_v / (1.0 - self.beta2 ** t)
        m_bar = (1.0 - momentum_t) * g_prime + momentum_t_1 * m_prime
        weight._data = weight._data - lr * m_bar / \
            (jnp.sqrt(v_prime) + self.epsilon)
        m._data, v._data = new_m, new_v


@register
class SGLD(Optimizer):
    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = grad._data * self.rescale_grad + wd * weight._data
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        from .. import random as rnd
        key = rnd.split_key(weight.context)
        noise = jax.random.normal(key, weight.shape, weight._data.dtype) * \
            math.sqrt(lr)
        weight._data = weight._data - lr / 2 * g + noise


@register
class Test(Optimizer):
    """ref: optimizer.Test — plain sgd used by unit tests."""

    def create_state(self, index, weight):
        return NDArray(jnp.zeros(weight.shape, weight._data.dtype),
                       ctx=weight.context)

    def update(self, index, weight, grad, state):
        weight._data = weight._data - self.lr * grad._data * self.rescale_grad


# ---------------------------------------------------------------------------
# Updater (kvstore server-side optimizer hook, ref: get_updater)
# ---------------------------------------------------------------------------

class Updater:
    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict = {}
        self.states_synced: Dict = {}

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index, weight)
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def get_states(self, dump_optimizer=False):
        import pickle
        return pickle.dumps((self.states, self.optimizer)
                            if dump_optimizer else self.states)

    def set_states(self, states):
        import pickle
        obj = pickle.loads(states)
        if isinstance(obj, tuple):
            self.states, self.optimizer = obj
        else:
            self.states = obj


def get_updater(optimizer: Optimizer) -> Updater:
    return Updater(optimizer)
