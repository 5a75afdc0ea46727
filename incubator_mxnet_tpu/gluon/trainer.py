"""Gluon Trainer.

TPU-native re-design of ref: python/mxnet/gluon/trainer.py.

API preserved: Trainer(params, optimizer, optimizer_params, kvstore,
update_on_kvstore) with `step(batch_size)`, `allreduce_grads()`,
`update()`, `save_states`/`load_states`.

Realisation (SURVEY §5.8): with params on one chip the step is a chain of
fused jitted update ops (buffers donated).  With per-device copies the
gradient reduce goes through the KVStore facade whose reduce is an XLA
collective.  The pod-scale path — params *sharded* over a Mesh with
in-executable psum — lives in parallel/ and is what bench.py uses; this
Trainer is the imperative-parity surface.
"""
from __future__ import annotations

from typing import Optional

from ..base import MXNetError
from ..ndarray.ndarray import NDArray
from .. import optimizer as opt_mod
from ..kvstore import create as kv_create, KVStore
from ..telemetry import spans as _spans
from .parameter import Parameter, ParameterDict

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None,
                 kvstore="device", compression_params=None,
                 update_on_kvstore=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise MXNetError("params must be dict/ParameterDict/list")
        self._params = []
        self._param2idx = {}
        for i, p in enumerate(params):
            if not isinstance(p, Parameter):
                raise MXNetError("invalid parameter %r" % p)
            self._param2idx[p.name] = i
            self._params.append(p)
            p._trainer = self
        self._compression_params = compression_params
        self._contexts = self._check_contexts()
        optimizer_params = optimizer_params or {}
        self._init_optimizer(optimizer, optimizer_params)
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._kvstore_type = kvstore
        self._kvstore: Optional[KVStore] = None
        self._update_on_kvstore = update_on_kvstore
        self._kv_initialized = False
        self._params_to_init = []
        self._n_step = 0

    # ------------------------------------------------------------------
    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            if contexts is not None and contexts != ctx:
                raise MXNetError(
                    "all Parameters must live on the same contexts")
            contexts = ctx
        return contexts or []

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt_mod.Optimizer):
            if optimizer_params and set(optimizer_params) != {"rescale_grad"}:
                raise MXNetError(
                    "optimizer_params must be None if optimizer is an "
                    "Optimizer instance")
            self._optimizer = optimizer
        else:
            self._optimizer = opt_mod.create(optimizer, **optimizer_params)
        self._optimizer.param_dict = param_dict
        self._updaters = [opt_mod.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _init_kvstore(self):
        is_dist = isinstance(self._kvstore_type, str) and \
            self._kvstore_type.startswith("dist")
        if self._kvstore_type is None or \
                (len(self._contexts) <= 1 and not is_dist):
            # single device, single process: updates run locally
            self._kvstore = None
            self._update_on_kvstore = False
        else:
            self._kvstore = kv_create(self._kvstore_type
                                      if isinstance(self._kvstore_type, str)
                                      else "device")
            if self._compression_params:
                self._kvstore.set_gradient_compression(
                    self._compression_params)
            if self._update_on_kvstore is None:
                self._update_on_kvstore = False
            if self._update_on_kvstore:
                self._kvstore.set_optimizer(self._optimizer)
            for i, param in enumerate(self._params):
                if param._data is not None:
                    self._kvstore.init(i, param.data())
        self._kv_initialized = True

    # ------------------------------------------------------------------
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    @learning_rate.setter
    def learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def optimizer(self):
        return self._optimizer

    # ------------------------------------------------------------------
    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + fused update (ref: Trainer.step → push/pull +
        optimizer update ops).  One `gluon.step` row of the phase log
        (ident = step number): the host's cost of dispatching it."""
        with _spans.phase("gluon.step", self._n_step):
            self._n_step += 1
            if not self._kv_initialized:
                self._init_kvstore()
            self._optimizer.rescale_grad = self._scale / batch_size
            if not self._update_on_kvstore:
                # update_on_kvstore: update() pushes raw grads and pulls
                # weights — aggregation happens IN the store; a prior
                # allreduce would double-count by num_workers (ref:
                # Trainer.step's _allreduce_grads/_update split)
                self.allreduce_grads()
            self.update(batch_size, ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._kvstore is None:
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            grads = param.list_grad()
            self._kvstore.pushpull(i, grads, out=grads)

    def _grads_pending(self, gs):
        """The common deferred-backward pending shared by EVERY gradient,
        or None when any grad is concrete / foreign (then the eager
        aggregated path runs unchanged)."""
        if not gs or not getattr(self._optimizer, "supports_bwd_fusion",
                                 False):
            return None
        from .. import autograd as _ag
        p0 = getattr(gs[0], "_pending", None)
        if not isinstance(p0, _ag._PendingGrads) or p0.done:
            return None
        if not all(getattr(g, "_pending", None) is p0 for g in gs):
            return None
        if not p0.covers(gs):
            return None
        return p0

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        if self._update_on_kvstore and self._kvstore is not None:
            for i, param in enumerate(self._params):
                if param.grad_req == "null" or param._data is None:
                    continue
                self._kvstore.push(i, param.list_grad())
                self._kvstore.pull(i, param.list_data())
            return
        if (len(self._contexts) == 1 and self._kvstore is None and
                getattr(self._optimizer, "aggregatable", False) and
                not self._optimizer.multi_precision):
            # aggregated fast path: ONE executable updates every param
            # (ref: multi_sgd_mom_update; cuts ~n-params dispatches to 1)
            updater = self._updaters[0]
            idxs, ws, gs, sts = [], [], [], []
            for i, param in enumerate(self._params):
                if param.grad_req == "null" or param._data is None:
                    continue
                if i not in updater.states:
                    updater.states[i] = \
                        self._optimizer.create_state_multi_precision(
                            i, param.data())
                idxs.append(i)
                ws.append(param.data())
                gs.append(param.grad())
                sts.append(updater.states[i])
            if idxs:
                pend = self._grads_pending(gs)
                if pend is not None:
                    # steady-state hybridized step: backward + update run
                    # as ONE executable (the deferred vjp closure feeds
                    # the aggregated update directly)
                    self._optimizer.update_multi(idxs, ws, gs, sts,
                                                 bwd_pending=pend)
                else:
                    self._optimizer.update_multi(idxs, ws, gs, sts)
            return
        for i, param in enumerate(self._params):
            if param.grad_req == "null" or param._data is None:
                continue
            for updater, w, g in zip(self._updaters, param.list_data(),
                                     param.list_grad()):
                updater(i, g, w)

    # ------------------------------------------------------------------
    def save_states(self, fname):
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as f:
                f.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore and self._kvstore is not None:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
            self._optimizer = self._updaters[0].optimizer
