"""Sharded training step — the pod-scale path.

TPU-native replacement for the reference's NCCL/ps-lite data-parallel
training (ref: kvstore_nccl.h grouped allreduce + optimizer update ops;
SURVEY §5.8 "TPU-native equivalent"): the WHOLE train step — forward,
backward, gradient reduction, fused optimizer update — is ONE jitted XLA
executable over a device Mesh.  Gradient allreduce is not a separate
push/pull: with batch sharded on the 'data' axis and params replicated
(or sharded for tensor parallel), XLA inserts the ICI collectives
automatically.  Buffer donation makes updates in-place in HBM.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import config as _config
from .functional import functionalize, extract_params, load_params
from .mesh import make_mesh, mesh_devices
from .zero import BucketPlan, overlap_schedule, record_plan, \
    zero_level_default
from ..monitor import events
from ..telemetry import costs as _costs
from ..telemetry import flightrec as _bb
from ..telemetry import spans as _tele
from ..telemetry.stepstats import StepTelemetry

__all__ = ["ShardedTrainer", "softmax_ce_loss", "sgd_momentum_tree",
           "adam_tree"]


def softmax_ce_loss(logits, labels):
    """Mean softmax cross-entropy with integer labels (pure jax)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                             axis=-1)
    return -jnp.mean(ll)


def sgd_momentum_tree(lr, momentum=0.9, wd=0.0):
    """Fused tree-wide SGD+momentum (ref: multi_sgd_mom_update semantics —
    one executable updates every tensor)."""

    def init(params):
        # zeros from shape/dtype metadata (NOT zeros_like: the params
        # may be multi-controller global arrays, and the state is
        # re-placed onto its own shardings anyway)
        return jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, w.dtype), params)

    def update(params, grads, state, scale=1.0):
        def upd(w, g, m):
            g = g.astype(jnp.float32) * scale + wd * w.astype(jnp.float32)
            new_m = momentum * m - lr * g
            return (w.astype(jnp.float32) + new_m).astype(w.dtype), new_m
        flat = jax.tree_util.tree_map(upd, params, grads, state)
        new_p = jax.tree_util.tree_map(lambda t: t[0], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
        new_s = jax.tree_util.tree_map(lambda t: t[1], flat,
                                       is_leaf=lambda t: isinstance(t, tuple))
        return new_p, new_s

    return init, update


def adam_tree(lr, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0):
    def init(params):
        z = jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, jnp.float32), params)
        z2 = jax.tree_util.tree_map(
            lambda w: jnp.zeros(w.shape, jnp.float32), params)
        return {"m": z, "v": z2, "t": jnp.zeros((), jnp.int32)}

    def update(params, grads, state, scale=1.0):
        t = state["t"] + 1
        b1t = 1.0 - beta1 ** t.astype(jnp.float32)
        b2t = 1.0 - beta2 ** t.astype(jnp.float32)

        def upd(w, g, m, v):
            g = g.astype(jnp.float32) * scale + wd * w.astype(jnp.float32)
            new_m = beta1 * m + (1 - beta1) * g
            new_v = beta2 * v + (1 - beta2) * jnp.square(g)
            mhat = new_m / b1t
            vhat = new_v / b2t
            new_w = w.astype(jnp.float32) - lr * mhat / \
                (jnp.sqrt(vhat) + eps)
            return new_w.astype(w.dtype), new_m, new_v
        flat = jax.tree_util.tree_map(upd, params, grads, state["m"],
                                      state["v"])
        leaf = lambda t_: isinstance(t_, tuple)
        return (jax.tree_util.tree_map(lambda x: x[0], flat, is_leaf=leaf),
                {"m": jax.tree_util.tree_map(lambda x: x[1], flat,
                                             is_leaf=leaf),
                 "v": jax.tree_util.tree_map(lambda x: x[2], flat,
                                             is_leaf=leaf),
                 "t": t})

    return init, update


class ShardedTrainer:
    """One-executable train step over a Mesh.

    block: a Gluon (Hybrid)Block (params already initialized)
    loss_fn: pure (outputs, labels) → scalar
    optimizer: "sgd" | "adam" | (init, update) pair
    mesh: jax Mesh (default: 1-d data mesh over all devices)
    param_spec_fn: name, shape → PartitionSpec for tensor-parallel layouts
        (default: fully replicated — pure DP)
    zero: ZeRO stage, or None = MXNET_ZERO_LEVEL.
        0 — fully replicated.
        1 — optimizer state sharded along the data axis via sharding
        constraints (the legacy WSC path: XLA's partitioner picks the
        collectives; bit-compatible with earlier releases; the
        reference's server-side-optimizer semantic, SURVEY §5.8).
        2 — + gradients reduce-scattered in size-capped buckets and
        the update computed shard-locally (parallel/zero.py: explicit
        overlap-first collectives, local BN statistics).
        3 — + parameters STORED sharded (gathered on demand at step
        start; persistent per-replica param memory ~1/N).
        Levels 2-3 need a 1-d data mesh and replicated param specs;
        combine tensor parallelism with zero<=1.
    preprocess: pure jnp fn applied to the batch INSIDE the jitted
        step (e.g. `io.device_feed.make_normalizer` — uint8 wire
        batches are normalized/cast on device, fused with the step)
    amp: mixed-precision compute dtype, or None = MXNET_AMP_DTYPE
        (empty = off).  'bfloat16' turns the op-registry cast policy
        on (`contrib.amp.init`): matmul/conv ops compute in bf16,
        numerically-sensitive ops stay f32, and because the policy
        sits below `invoke`, the SAME casts land inside this
        trainer's jitted step executables — ZeRO-2/3's shard_map
        bodies included.  Master weights and optimizer state stay
        f32 (grads arrive f32 at the update).  'float16' is the
        parity path: bare ShardedTrainer runs it unscaled (bf16-range
        models only); wrap in ResilientTrainer(amp='float16') for the
        dynamic LossScaler backed by the NaN-guard.  The policy is
        process-wide — `contrib.amp.turn_off()` reverts it.
    """

    def __init__(self, block, loss_fn=softmax_ce_loss, optimizer="sgd",
                 lr=0.01, momentum=0.9, wd=0.0, mesh: Optional[Mesh] = None,
                 batch_axis="data", param_spec_fn=None, donate=True,
                 zero=None, preprocess=None, amp=None):
        from ..contrib import amp as _amp_mod
        self.amp = _amp_mod.normalize_dtype(
            amp if amp is not None else _config.get("MXNET_AMP_DTYPE"))
        if self.amp:
            # BEFORE the first trace: the wrapped registry fns are what
            # the lazily-built step executable captures
            _amp_mod.init(self.amp)
            events.incr("amp.trainer_init")
            _bb.record("amp", "init", target=self.amp,
                       trainer="sharded")
        self.block = block
        self.mesh = mesh or make_mesh()
        self.batch_axis = batch_axis
        self.loss_fn = loss_fn
        self.zero = zero_level_default(zero)
        self._preprocess = preprocess
        if optimizer == "sgd":
            self._opt_init, self._opt_update = sgd_momentum_tree(
                lr, momentum, wd)
        elif optimizer == "adam":
            self._opt_init, self._opt_update = adam_tree(lr, wd=wd)
        else:
            self._opt_init, self._opt_update = optimizer

        self._fwd = functionalize(block, training=True)
        self.params = extract_params(block)
        # ZeRO-2/3: explicit bucketed collectives over a pure-DP mesh
        # (parallel/zero.py).  The plan decides which params
        # reduce-scatter solo along a divisible axis and which join
        # size-capped concat buckets; zero=3 additionally STORES the
        # solo params sharded.
        self._zero_plan = None
        self._zero_host_gather = False
        self._zero_ndev = int(self.mesh.shape[self.batch_axis])
        if self.zero >= 2:
            if len(self.mesh.axis_names) != 1 or \
                    self.mesh.axis_names[0] != self.batch_axis:
                raise ValueError(
                    "zero=%d needs a 1-d %r data mesh (got axes %s); "
                    "combine tensor parallelism with zero<=1"
                    % (self.zero, self.batch_axis,
                       tuple(self.mesh.axis_names)))
            if param_spec_fn is not None:
                raise ValueError(
                    "zero=%d shards params itself — param_spec_fn "
                    "(tensor parallel) requires zero<=1" % self.zero)
            self._zero_plan = BucketPlan(
                {n: tuple(v.shape) for n, v in self.params.items()},
                self._zero_ndev, order=list(self.params),
                label="sharded.zstep")
            self._zero_schedule = overlap_schedule(
                mesh_devices(self.mesh))
            # host-bridged broadcast (zero=2, CPU meshes): the updated
            # solo shards gather to ONE host buffer per param and
            # device_put back as zero-copy ALIASES on every replica —
            # all replicas then read the same physical pages in
            # forward (shared cache lines) instead of N private
            # copies, and the in-executable all-gather disappears.
            # CPU-backend device_put aliasing is the verified behavior
            # the decode-service hardening works around; here it is
            # the feature.  Real accelerators keep the in-executable
            # all-gather (H2D per step would be a regression).
            self._zero_host_gather = (
                self.zero == 2 and self._zero_ndev > 1
                and jax.process_count() == 1
                and all(getattr(d, "platform", "") == "cpu"
                        for d in mesh_devices(self.mesh)))
            self._zero_plan.register_cost_rows("sharded.zstep")
            record_plan("sharded.zstep", self._zero_plan, self.zero,
                        self._zero_schedule)
        pspec = param_spec_fn or (lambda name, shape: P())
        self._param_shardings = {
            n: NamedSharding(self.mesh, pspec(n, v.shape))
            for n, v in self.params.items()}
        if self.zero >= 3 and self._zero_ndev > 1:
            # persistent param memory ~1/N: the solo set lives sharded
            # on its plan axis; the concat/indivisible set replicates
            for n, ax in self._zero_plan.solo.items():
                spec = [None] * len(self.params[n].shape)
                spec[ax] = self.batch_axis
                self._param_shardings[n] = NamedSharding(self.mesh,
                                                         P(*spec))
        self.params = {
            n: self._place_value(v, self._param_shardings[n])
            for n, v in self.params.items()}
        # ZeRO stage 1 (zero=1): per-param optimizer state lives SHARDED
        # along the data axis — the TPU-native form of the reference's
        # server-side optimizer (SURVEY §5.8: ps-lite servers each hold
        # a key shard and update it; here each mesh slice holds a state
        # shard and XLA's partitioner turns the gradient all-reduce into
        # reduce-scatter + sharded update + param all-gather).
        self._opt_shardings = {
            n: NamedSharding(self.mesh, self._zero_spec(n, v.shape))
            for n, v in self.params.items()}
        # zeros are created DIRECTLY on their shardings (jit with
        # out_shardings): no full-size host materialisation, so zero=1
        # init never needs the unsharded state to fit one device
        opt_shapes = jax.eval_shape(self._opt_init, self.params)
        opt_out_sh = self._place_opt_tree(opt_shapes,
                                          lambda leaf, sh: sh)
        self.opt_state = jax.jit(
            self._opt_init, out_shardings=opt_out_sh)(self.params)
        self._batch_sharding = NamedSharding(self.mesh, P(batch_axis))
        self._step = None
        self._n_step = 0
        self._tele = None           # StepTelemetry, lazy on enabled()
        self._trace_count = 0       # this trainer's executable traces
        # per-replica dispatch fan-out (ISSUE 10 tentpole c): batch
        # shards upload from a worker pool, one thread per replica,
        # timed into train.dispatch_replica_us{replica=}.  1-d
        # single-process meshes only — elsewhere the shard/device
        # mapping is not row-per-replica
        self._dispatch = None
        if len(self.mesh.axis_names) == 1 and jax.process_count() == 1:
            from .dispatch import DispatchPool
            self._dispatch = DispatchPool(mesh_devices(self.mesh))
        # memory observatory (ISSUE 20): weak-track this trainer so
        # the attribution join can price its parameter placement (and
        # ZeRO plan) against measured device bytes — a WeakSet add,
        # nothing on the step path
        try:
            from ..telemetry import memwatch as _mw
            _mw.track_trainer(self)
        except Exception:           # noqa: BLE001 — observability
            pass                    # must never block construction

    def _place_value(self, value, sharding):
        """Host value → global array on `sharding`.  Multi-controller:
        device_put would need cross-host transfers (unsupported on some
        backends); instead every process fills only its ADDRESSABLE
        shards from the (identical) host value."""
        import numpy as _np
        if jax.process_count() > 1:
            arr = _np.asarray(value)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        # CPU backend only: its device_put zero-copy ALIASES the source
        # buffer (verified in the decode-service hardening), so placing
        # the block's own param array and then DONATING it in the fused
        # step would free the block's buffer out from under it — fatal
        # the moment anything re-reads the block (a second trainer on
        # the same net, an elastic mesh rebuild).  One host copy per
        # param per trainer build is the price there.  Real
        # accelerators H2D-copy anyway — forcing _np.array() on them
        # would turn a device-resident `value` into a D2H round trip
        # per param on every (elastic re)build.
        if any(d.platform == "cpu" for d in sharding.device_set):
            return jax.device_put(_np.array(value, copy=True), sharding)
        return jax.device_put(jnp.asarray(value), sharding)

    def _zero_spec(self, name, shape):
        """PartitionSpec for this param's optimizer-state leaves: the
        param's own spec (TP axes follow the weight layout), plus —
        under zero=1 — the first free axis divisible by the data-mesh
        size sharded on the batch axis.  Under zero>=2 the bucket
        plan's solo axes decide: solo params' state shards with them,
        concat-bucket params update replicated (their state too)."""
        if self.zero >= 2:
            base = [None] * len(shape)
            ax = self._zero_plan.solo.get(name) \
                if self._zero_plan is not None else None
            if ax is not None and self._zero_ndev > 1:
                base[ax] = self.batch_axis
            return P(*base)
        base = list(self._param_shardings[name].spec)
        base += [None] * (len(shape) - len(base))
        if not self.zero:
            return P(*base)
        ndata = self.mesh.shape[self.batch_axis]
        if ndata <= 1 or self.batch_axis in base:
            # a mesh axis may map to only one tensor dim; if the param
            # spec already uses the batch axis, the state follows it
            return P(*base)
        for i, dim in enumerate(shape):
            if base[i] is None and dim % ndata == 0 and dim >= ndata:
                base[i] = self.batch_axis
                return P(*base)
        return P(*base)             # indivisible (biases): replicated

    def _place_opt_tree(self, tree, place):
        """Walk an optimizer-state tree, applying `place(leaf, sharding)`
        — param-name-keyed dicts take the matching state shardings,
        scalars/step counters replicate."""
        rep = NamedSharding(self.mesh, P())
        def walk(sub):
            if isinstance(sub, dict):
                if set(sub) == set(self.params):
                    return {n: place(v, self._opt_shardings[n])
                            for n, v in sub.items()}
                return {k: walk(v) for k, v in sub.items()}
            return place(sub, rep)
        return walk(tree)

    def _build_step(self, donate=True):
        fwd = self._fwd
        loss_fn = self.loss_fn
        opt_update = self._opt_update
        preprocess = self._preprocess
        constrain = functools.partial(self._place_opt_tree,
                                      place=jax.lax.with_sharding_constraint) \
            if self.zero else (lambda tree, **_: tree)

        def step(params, opt_state, batch, labels, rng_bits):
            # trace-time side effect only (the serve.traces pattern):
            # meters train-step recompiles; cache hits never run this.
            # The per-trainer count keeps steps_compiling attribution
            # correct when several trainers share the process ledger
            events.incr("train.traces")
            self._trace_count += 1
            if preprocess is not None:
                # on-device normalize/cast fused into this executable
                # (uint8 stays the wire format — device_feed contract)
                batch = preprocess(batch)

            def lf(p):
                out, states = fwd(p, batch, rng_bits=rng_bits)
                return loss_fn(out, labels), states
            (loss, states), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            new_params, new_opt = opt_update(params, grads, opt_state)
            # keep optimizer state on its ZeRO shards: the constraint is
            # what makes XLA compute the update on the shard (and lower
            # the gradient sum to reduce-scatter where profitable)
            # instead of re-replicating
            new_opt = constrain(new_opt)
            # fold running-stat updates (BatchNorm) back into params
            for k, v in states.items():
                if k in new_params:
                    new_params[k] = v.astype(new_params[k].dtype)
            new_params = {
                n: jax.lax.with_sharding_constraint(
                    v, self._param_shardings[n])
                for n, v in new_params.items()}
            return new_params, new_opt, loss

        # metered: one cost-registry row per input signature
        # (FLOPs/bytes-accessed + cumulative invocation counts) — the
        # pod-path train step's line in a black-box dump's cost table.
        # expect_donated arms the donation audit: a step built with
        # donate=False warns once by label (params + opt state are
        # donatable by construction — the update consumes them)
        return _costs.metered_jit(
            step, donate_argnums=(0, 1) if donate else (),
            kind="train", label="sharded.step",
            expect_donated=(0, 1), role="sharded_step")

    def _build_step_zero(self, donate=True):
        """The overlap-first ZeRO-2/3 step (ISSUE 10 tentpole): ONE
        jitted shard_map over the data mesh.

        Per device: local forward/backward (BatchNorm batch statistics
        stay replica-local — the reference's DP semantics, and no
        mid-backward rendezvous), then the bucket plan's collectives —
        per-solo-param reduce-scatter, one psum per concat bucket —
        either interleaved with backward ('bwd') or coalesced behind
        one optimization barrier ('trail', the oversubscribed-host
        default: a staggered-arrival rendezvous convoy measured ~10x
        the isolated collective cost).  The optimizer update then runs
        on SHARDS (1/N of the work per replica instead of N redundant
        full updates), and the updated solo shards all-gather back to
        full params (zero=2) or stay sharded (zero=3, which instead
        gathered params on demand at step start).  Running-stat
        updates (BN) are pmean'd across replicas before folding back.

        Everything donates: params + optimizer state alias in place.
        """
        import jax
        from jax import shard_map
        fwd = self._fwd
        loss_fn = self.loss_fn
        opt_update = self._opt_update
        preprocess = self._preprocess
        plan = self._zero_plan
        zero = self.zero
        axis = self.batch_axis
        ndev = self._zero_ndev
        schedule = self._zero_schedule
        host_gather = self._zero_host_gather
        param_dtypes = {n: v.dtype for n, v in self.params.items()}

        def body(params, opt_state, batch, labels, rng_bits):
            events.incr("train.traces")
            self._trace_count += 1
            if preprocess is not None:
                batch = preprocess(batch)
            # decorrelate per-replica RNG (dropout masks must differ
            # across replicas, as they do across rows of the global
            # batch on the single-executable path)
            idx = jax.lax.axis_index(axis)
            key = jax.random.wrap_key_data(rng_bits)
            rbits = jax.random.key_data(jax.random.fold_in(key, idx))
            # zero=3: gather-on-demand — solo params arrive as shards,
            # forward needs them whole; XLA frees the gathered copies
            # after their last use
            full = plan.gather_params(params, axis) if zero >= 3 \
                else dict(params)

            def lf(p):
                out, states = fwd(p, batch, rng_bits=rbits)
                return loss_fn(out, labels), states
            (loss, states), grads = jax.value_and_grad(
                lf, has_aux=True)(full)

            if schedule == "trail":
                # coalesce every bucket collective behind backward:
                # all devices arrive together, no convoy
                grads = jax.lax.optimization_barrier(grads)
            solo_g, bucket_flats = plan.reduce_scatter_grads(grads,
                                                            axis)
            # shard trees for the update: solo params update 1/N
            # locally, concat-bucket params update replicated
            w_sh, g_sh = {}, {}
            for n in plan.solo:
                w_sh[n] = params[n] if zero >= 3 \
                    else plan.shard_slice(full[n], n, idx)
                g_sh[n] = solo_g[n]
            for names, flat in zip(plan.buckets, bucket_flats):
                parts = plan.split_bucket(flat, names)
                for n in names:
                    w_sh[n] = full[n]
                    g_sh[n] = parts[n]
            new_w, new_opt = opt_update(w_sh, g_sh, opt_state)
            new_params = {}
            solo_new = {n: new_w[n] for n in plan.solo}
            if zero >= 3 or host_gather:
                # stay sharded: zero=3 by contract (persistent memory
                # 1/N), host_gather because step() broadcasts the
                # shards through one aliased host buffer instead
                new_params.update(solo_new)
            else:
                new_params.update(
                    plan.all_gather_updated(solo_new, axis))
            for names in plan.buckets:
                for n in names:
                    new_params[n] = new_w[n]
            # fold running-stat updates (BatchNorm) back into params,
            # averaged across replicas (batch stats stayed local)
            for k, v in states.items():
                if k in new_params:
                    u = jax.lax.pmean(v.astype(jnp.float32), axis)
                    if (zero >= 3 or host_gather) and k in plan.solo:
                        u = plan.shard_slice(u, k, idx)
                    new_params[k] = u.astype(param_dtypes[k])
            return new_params, new_opt, jax.lax.pmean(loss, axis)

        pspecs_in = {n: self._param_shardings[n].spec
                     for n in self.params}
        pspecs_out = dict(pspecs_in)
        if host_gather:
            # inputs replicated (aliased host buffers), outputs the
            # updated SHARDS — step() turns them back into aliases
            for n, ax in plan.solo.items():
                spec = [None] * len(self.params[n].shape)
                spec[ax] = axis
                pspecs_out[n] = P(*spec)
        opt_specs = self._place_opt_tree(
            self.opt_state, lambda leaf, sh: sh.spec)
        # donate-everything — EXCEPT the params under host_gather,
        # whose buffers are zero-copy aliases of one shared host
        # allocation (donating one replica's view would free the
        # pages under the other seven)
        if host_gather:
            donate_argnums = (1,) if donate else ()
            expect = (1,)
        else:
            donate_argnums = (0, 1) if donate else ()
            expect = (0, 1)
        smapped = shard_map(
            body, mesh=self.mesh,
            in_specs=(pspecs_in, opt_specs, P(axis), P(axis), P()),
            out_specs=(pspecs_out, opt_specs, P()),
            check_vma=False)
        return _costs.metered_jit(
            smapped, donate_argnums=donate_argnums,
            kind="train", label="sharded.zstep",
            expect_donated=expect)

    def _place_batch(self, arr, sharding):
        """Single-controller: the full global batch device_puts onto the
        mesh.  Multi-controller (jax.distributed, mesh spanning
        processes): each process passes only ITS rows — the per-process
        shard of the global batch — and the global array is assembled
        from the process-local data (SURVEY §5.8: multi-host workers
        each feed their slice, as reference workers read disjoint
        RecordIO partitions)."""
        import numpy as _np
        if isinstance(arr, jax.Array) and \
                getattr(arr, "sharding", None) == sharding:
            # already feed-placed on this mesh (device_feed()): no
            # re-upload, the background transfer was the upload
            return arr
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                sharding, _np.asarray(arr))
        if self._dispatch is not None and sharding == \
                self._batch_sharding and self._dispatch.eligible(
                    arr, sharding):
            # per-replica fan-out: each replica's rows upload from
            # their own worker thread (bit-identical placement,
            # parallel wire time, per-replica µs attribution)
            return self._dispatch.place(arr, sharding)
        return jax.device_put(jnp.asarray(arr), sharding)

    def _ensure_step(self):
        if self._step is None:
            # zero>=2 on a real multi-replica mesh takes the explicit
            # overlap-first path; a 1-replica mesh degenerates to the
            # single-executable step (identical math, no collectives)
            if self.zero >= 2 and self._zero_ndev > 1:
                self._step = self._build_step_zero()
            else:
                self._step = self._build_step()

    def lower_step(self, batch, labels):
        """The `jax.stages.Lowered` of the step `step(batch, labels)`
        runs, lowered with the REAL shardings (sharded batch, placed
        params and optimizer state) — for an audit that reads the
        compiled program (`.compile().as_text()`: which collectives did
        XLA emit?).  Unsharded avals would compile a single-device
        program with none.  Nothing executes and nothing is donated."""
        from .. import random as _rnd
        self._ensure_step()
        return self._step.lower(
            self.params, self.opt_state,
            self._place_batch(batch, self._batch_sharding),
            self._place_batch(labels, self._batch_sharding),
            jax.random.key_data(_rnd.split_key()))

    def step(self, batch, labels, rng_bits=None):
        """batch/labels: jax or numpy arrays (global batch; in
        multi-controller runs, this process's rows of it). Returns loss
        (device scalar — don't block on it every step)."""
        from .. import random as _rnd
        self._ensure_step()
        # telemetry: one bool read when disabled; enabled, the step
        # records data-wait (placement) vs dispatch wall.  The loss
        # deliberately stays on device (async dispatch), so compute
        # wall is NOT observed here — ResilientTrainer's guarded step,
        # which syncs anyway, records it
        tele = self._tele
        if tele is None and _tele.enabled():
            # baseline on THIS trainer's trace count: enabling
            # telemetry mid-run must not count old compiles as a
            # compiling first step
            tele = self._tele = StepTelemetry(
                own_traces=self._trace_count)
        # global-step stamp (ISSUE 11): spans completed during this
        # step (dispatch fan-out, kvstore, feed) carry the step id —
        # the cross-process correlation key
        _tele.set_global_step(self._n_step)
        # one sharded.step row of the phase log (ident = step number):
        # the host's cost of placing the batch and dispatching the step;
        # the meters below take the phase's own stamps
        with _tele.phase("sharded.step", self._n_step) as ph:
            batch = self._place_batch(batch, self._batch_sharding)
            labels = self._place_batch(
                labels, NamedSharding(self.mesh, P(self.batch_axis)))
            if rng_bits is None:
                rng_bits = jax.random.key_data(_rnd.split_key())
            t1 = time.monotonic() if tele is not None else 0.0
            try:
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, batch, labels, rng_bits)
            except Exception as e:
                # allocator OOM at dispatch: dump committed-vs-measured
                # per tenant before the unwind frees the evidence
                # (ISSUE 20); zero-cost until an exception actually raises
                from ..telemetry import memwatch as _mw
                _mw.guard_oom("train.step", e)
                raise
            self._n_step += 1
            if self._zero_plan is not None:
                # bytes-on-wire attribution: bump every bucket collective's
                # registry row once per step (gated on the recorder inside)
                self._zero_plan.invoke_cost_rows()
                if getattr(self, "_zero_host_gather", False):
                    self._broadcast_solo_params()
        t0, t2 = ph.t0, ph.t1
        # always-on flight-recorder step record (loss stays on device —
        # forcing it here would forfeit dispatch/compute overlap); AMP
        # runs tag their records AND feed a labeled step-wall ring, so
        # /metrics and dumps answer "bf16 step wall vs f32" directly
        _bb.record("step", "sharded", step=self._n_step - 1,
                   us=int((t2 - t0) * 1e6),
                   **({"amp": self.amp} if self.amp else {}))
        if self.amp:
            events.observe_time("train.step_us", t2 - t0,
                                labels={"amp": self.amp})
        if tele is not None:
            tele.record_step(wall_s=t2 - t0, data_wait_s=t1 - t0,
                             dispatch_s=t2 - t1,
                             traces=self._trace_count)
        # autotune probe from the trainer's OWN measured wall (ISSUE
        # 19 satellite: probe writers outside bench/): per-example
        # step wall at THIS batch size, durable evidence for every
        # later run's suggest_batch_size.  Cadence-gated (history is
        # never a per-step cost) and past the compiling first step.
        if self._n_step % 128 == 2:
            try:
                from ..compile import autotune as _autotune
                rows = int(batch.shape[0]) if batch.shape else 1
                _autotune.note_probe(
                    "batch_size", "sharded.step", rows,
                    (t2 - t0) * 1e6 / max(1, rows),
                    source="trainer.step", step=self._n_step - 1)
            except Exception:       # noqa: BLE001
                pass
        return loss

    def _broadcast_solo_params(self):
        """Host-bridged all-gather (zero=2 on CPU meshes): pull each
        updated solo param's shards into ONE host buffer and
        device_put it back as a zero-copy alias on every replica.
        Every replica's forward then reads the SAME physical pages —
        one cache-resident copy of the weights instead of N — and the
        ring all-gather leaves the executable entirely.  Bit-identical
        values; the executable deliberately does not donate params so
        the shared pages can never be freed under a sibling alias."""
        import numpy as _np
        devs = mesh_devices(self.mesh)
        rep = NamedSharding(self.mesh, P())
        plan = self._zero_plan

        def bcast(name):
            t0 = time.perf_counter()
            full = _np.asarray(self.params[name])   # shard gather
            pieces = [jax.device_put(full, d) for d in devs]
            out = jax.make_array_from_single_device_arrays(
                full.shape, rep, pieces)
            events.observe_time("zero.host_gather_us",
                                time.perf_counter() - t0)
            return name, out

        if self._dispatch is not None and self._dispatch.enabled:
            done = self._dispatch.run(bcast, list(plan.solo))
        else:
            done = [bcast(n) for n in plan.solo]
        for name, arr in done:
            self.params[name] = arr

    def device_feed(self, source, depth=None, transform=None):
        """Async feed onto this trainer's mesh: a background thread
        `device_put`s the NEXT (batch, labels) pair — batch sharded on
        the data axis, ONE batched transfer per pytree — while the
        current step executes.  `step()` recognizes the placed arrays
        and skips its own upload.  Pair with `preprocess=` for
        uint8-on-wire feeding (normalize/cast runs inside the step).

        source yields host (batch, labels) pairs (numpy); returns an
        `io.device_feed.DeviceFeed` (per-stage counters on
        `monitor.events` under `feed.*`)."""
        from ..io.device_feed import DeviceFeed
        # one batch-axis sharding, broadcast over every leaf of the
        # batch pytree by DeviceFeed._place_sharded
        return DeviceFeed(source, sharding=self._batch_sharding,
                          depth=depth, transform=transform)

    @property
    def data_parallel_size(self) -> int:
        """Replicas along the batch axis (the elastic supervisor's
        batch/LR scaling denominator)."""
        return int(self.mesh.shape[self.batch_axis])

    def release(self):
        """Drop this trainer's device state — params, optimizer state,
        compiled step.  An elastic supervisor calls this on the OLD
        trainer before materializing its successor on a different
        mesh, so the old copies free before the new ones allocate (at
        pod scale, holding both generations of a ZeRO-sharded state
        doubles the HBM bill exactly when a replica just died).  The
        trainer is unusable afterwards; the state lives on in the
        checkpoint the successor restores."""
        self.params = {}
        self.opt_state = None
        self._step = None
        # the process-global step stamp this trainer was feeding is
        # stale the moment training ends: a span emitted later (a
        # serving request, a checkpoint verify) must not carry the
        # dead run's step id into a cross-process (trace_id, step)
        # join — the false-correlation failure mode of ISSUE 11
        _tele.set_global_step(None)
        if self._dispatch is not None:
            self._dispatch.shutdown()

    def sync_to_block(self):
        """Write trained params back into the Gluon block."""
        load_params(self.block, self.params)

    def serve(self, **kwargs):
        """Train→serve handoff: sync the trained params back into the
        block and build a `serving.InferenceEngine` whose replica set is
        THIS trainer's mesh devices (round-robin bucket dispatch, one
        full parameter copy per device — the inference-side mirror of
        the DP training mesh).  Pass `devices=` to override; all other
        kwargs forward to `InferenceEngine` (buckets, max_batch,
        example_shape, handle_sigterm, ...)."""
        from ..serving import InferenceEngine
        from .mesh import replica_contexts
        self.sync_to_block()
        kwargs.setdefault("devices", replica_contexts(self.mesh))
        return InferenceEngine(self.block, **kwargs)

    # ------------------------------------------------------------------
    # sharded checkpoint/resume (ref: Trainer.save_states/load_states —
    # at pod scale the states are sharded over the mesh, so the
    # checkpoint is written/read distributed via orbax instead of the
    # 0x112 single-host container)
    # ------------------------------------------------------------------
    def save_checkpoint(self, path):
        """Write params + optimizer state + step to `path` (a directory;
        sharded arrays are gathered/written by orbax per host)."""
        import os
        import orbax.checkpoint as ocp
        path = os.path.abspath(path)
        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(path, {"params": self.params,
                         "opt_state": self.opt_state,
                         "n_step": self._n_step},
                  force=True)

    def load_checkpoint(self, path):
        """Restore params/opt_state/step saved by save_checkpoint,
        re-placing every leaf on this trainer's mesh shardings (works
        across restarts and across a different mesh shape — leaves are
        restored to host memory first, so the saved device layout does
        not constrain the restoring topology)."""
        import os
        import numpy as _np
        import orbax.checkpoint as ocp
        path = os.path.abspath(path)
        ckpt = ocp.PyTreeCheckpointer()
        # restore to host numpy against this trainer's tree template:
        # restoring with the layout recorded at save time would fail on
        # any topology change
        template = {"params": dict(self.params),
                    "opt_state": self.opt_state,
                    "n_step": self._n_step}
        restore_args = jax.tree_util.tree_map(
            lambda _: ocp.RestoreArgs(restore_type=_np.ndarray), template)
        if not os.path.exists(path):
            raise FileNotFoundError("no checkpoint at %s" % path)
        try:
            restored = ckpt.restore(path, item=template,
                                    restore_args=restore_args)
        except OSError:
            raise                       # I/O problems are not mismatches
        except Exception as e:
            raise ValueError(
                "checkpoint at %s does not match this trainer's "
                "param/opt-state tree (%s)" % (path, e)) from e
        params = restored["params"]
        if set(params) != set(self.params):
            raise ValueError(
                "checkpoint/trainer param name mismatch: only in "
                "checkpoint %s; only in trainer %s"
                % (sorted(set(params) - set(self.params))[:5],
                   sorted(set(self.params) - set(params))[:5]))
        for n, v in params.items():
            if tuple(v.shape) != tuple(self.params[n].shape):
                raise ValueError(
                    "checkpoint param %s has shape %s but trainer "
                    "expects %s" % (n, tuple(v.shape),
                                    tuple(self.params[n].shape)))
            if jnp.dtype(v.dtype) != jnp.dtype(self.params[n].dtype):
                raise ValueError(
                    "checkpoint param %s has dtype %s but trainer "
                    "expects %s (mixed-precision config drift?)"
                    % (n, jnp.dtype(v.dtype).name,
                       jnp.dtype(self.params[n].dtype).name))
        self.params = {
            n: self._place_value(v, self._param_shardings[n])
            for n, v in params.items()}

        # optimizer-state subtrees keyed by param name take the matching
        # state shardings (ZeRO shards under zero=1, else the param
        # shardings); scalars (step counters) replicate
        self.opt_state = self._place_opt_tree(
            restored["opt_state"], self._place_value)
        self._n_step = int(restored["n_step"])
        self._step = None          # rebuild with the restored layouts
