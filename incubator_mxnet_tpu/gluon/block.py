"""Gluon Block / HybridBlock.

TPU-native re-design of ref: python/mxnet/gluon/block.py (Block,
HybridBlock, SymbolBlock) + src/imperative/cached_op.{h,cc} (CachedOp).

The north-star mapping (SURVEY §3.2): `hybridize()` no longer builds an
nnvm graph + CachedOp — it wraps the block's forward in **one jitted XLA
executable**:

  - first call per (shapes, dtypes, training-mode): trace `hybrid_forward`
    with jax tracers flowing through the same NDArray stubs → XLA HLO →
    compiled executable (≙ CachedOp's nnvm passes + bulked engine segments,
    with XLA fusion playing the bulking role);
  - steady state: ONE dispatch per forward (≙ `static_alloc+static_shape`
    whole-segment push);
  - under `autograd.record()`, the tape stores the jax.vjp pullback of the
    jitted function, so `backward()` is one compiled transpose executable
    (≙ CachedOp::Backward).

Mutable layer state (BatchNorm running stats) uses an explicit
state-update channel: during tracing the new stats become extra outputs
and are written back after execution — the functional analogue of the
reference kernels mutating aux arrays in place.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray, apply_fn
from ..ops import registry as _registry
from .. import autograd as _ag
from .. import random as _rnd
from ..telemetry import costs as _costs
from .parameter import (Parameter, ParameterDict,
                        DeferredInitializationError)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "nameless_scope"]


# ---------------------------------------------------------------------------
# name scoping (ref: block.py _BlockScope + name_manager.py NameManager)
# ---------------------------------------------------------------------------

class _NameCounter(threading.local):
    def __init__(self):
        self.counts = {}
        self.prefix_stack = []


_NAMES = _NameCounter()


def _gen_prefix(hint):
    n = _NAMES.counts.get(hint, 0)
    _NAMES.counts[hint] = n + 1
    return "%s%d_" % (hint, n)


@contextlib.contextmanager
def nameless_scope():
    counts = _NAMES.counts
    _NAMES.counts = {}
    try:
        yield
    finally:
        _NAMES.counts = counts


# ---------------------------------------------------------------------------
# state-update channel (BatchNorm running stats etc.)
# ---------------------------------------------------------------------------

class _StateChannel(threading.local):
    def __init__(self):
        self.active = None      # None or list of (param, new_jax_value)


_STATE = _StateChannel()


def record_state_update(param, new_value_nd):
    """Called by layers whose op updates auxiliary state (running stats).
    Imperatively: writes through immediately. Under a cached-op trace:
    queued as an extra executable output, written back post-call."""
    if _STATE.active is not None:
        _STATE.active.append((param, new_value_nd._data))
        return
    _write_state_all_ctx(param, new_value_nd._data)


def _write_state_all_ctx(param, value, pending=None):
    """Write an updated aux-state value to EVERY per-context copy of the
    parameter (running stats must stay in sync across devices in
    multi-context training), keeping each copy's dtype and device.
    When ``pending`` is given, release its writer claim on the param
    (see ``_flush_state_writers``)."""
    import jax as _jax
    for ctx, arr in param._data.items():
        arr._data = _jax.device_put(value.astype(arr._data.dtype),
                                    ctx.jax_device)
    if pending is not None and \
            getattr(param, "_pending_writer", None) is pending:
        param._pending_writer = None


def _mark_state_writers(state_params, pending):
    """Claim aux-state params for a deferred program: until it
    dispatches and writes back, these params' device buffers are STALE
    relative to program order."""
    for p in state_params:
        p._pending_writer = pending


def _flush_state_writers(params):
    """Sequential consistency for mutable aux state (BatchNorm running
    stats): a still-pending earlier call that WRITES one of this call's
    params must dispatch — and write back — before this call snapshots
    buffers.  Without this, the second of two calls of a stateful block
    inside one record scope (GAN discriminator on real+fake, siamese
    nets) reads pre-update statistics."""
    for p in params:
        w = getattr(p, "_pending_writer", None)
        if w is not None and not w.done:
            w.force()


# ---------------------------------------------------------------------------
# symbol tracing (HybridBlock.export / SymbolBlock round-trip)
# ---------------------------------------------------------------------------

class _SymbolTraceState(threading.local):
    def __init__(self):
        self.vars = None        # None or {param_name: Symbol var}


_SYMTRACE = _SymbolTraceState()


class _ShapePassState(threading.local):
    def __init__(self):
        self.active = False     # inside an abstract infer_shape pass


_SHAPEPASS = _ShapePassState()


def _param_symbol(param):
    """Symbol variable for a Parameter; deduped per trace so shared
    parameters map to ONE arg node in the exported graph."""
    if _SYMTRACE.vars is not None and param.name in _SYMTRACE.vars:
        return _SYMTRACE.vars[param.name]
    v = param.var()
    if _SYMTRACE.vars is not None:
        _SYMTRACE.vars[param.name] = v
    return v


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

def _batch_cast_params(pd, dtype):
    """Convert every initialized parameter to `dtype` in ONE jitted
    executable.  The per-param eager astype it replaces costs one
    compile per distinct shape (batched on an earlier setup where each
    cost seconds; not re-measured on this chip)."""
    import jax
    import jax.numpy as jnp
    from collections import OrderedDict
    tgt = jnp.dtype(dtype)
    # grouped by context: one batched convert EXECUTABLE PER DEVICE —
    # mixing leaves committed to different devices in one jit call is a
    # committed-devices conflict (split_and_load-style nets initialize
    # params on several contexts); the per-shape compile saving is
    # preserved per device
    groups = OrderedDict()
    for p in pd.values():
        if p._data is None:
            continue
        for ctx, arr in p._data.items():
            if arr._data.dtype != tgt:
                groups.setdefault(ctx, []).append(p)
    if not groups:
        return

    def convert(*ls):
        return tuple(l.astype(tgt) for l in ls)

    touched = []
    for ctx, ps in groups.items():
        leaves = tuple(p._data[ctx]._data for p in ps)
        outs = jax.jit(convert)(*leaves)
        for p, o in zip(ps, outs):
            p._data[ctx] = NDArray(o, ctx=ctx)
        touched.extend(ps)
    for p in touched:
        if p._grad_req != "null":
            p._init_grad()


class Block:
    """ref: gluon.Block — composable, imperative-first layer."""

    def __init__(self, prefix=None, params=None):
        hint = re.sub(r"(?<!^)(?=[A-Z])", "", self.__class__.__name__).lower()
        self._prefix = prefix if prefix is not None else _gen_prefix(hint)
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    # -- scoping (API compat: `with self.name_scope():`) ------------------
    @contextlib.contextmanager
    def name_scope(self):
        _NAMES.prefix_stack.append(self._prefix)
        try:
            yield
        finally:
            _NAMES.prefix_stack.pop()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_")

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret.update(OrderedDict((k, v) for k, v in self._params.items()
                                   if pattern.match(k)))
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    # -- child / param registration (ref: Block.__setattr__) --------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
                self._params._params.setdefault(value.name, value)
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- lifecycle ---------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        # metadata/caches first (recursive), then ONE batched data
        # conversion over the whole tree — public single-arg signature
        # preserved for subclass overrides
        self._cast_meta(dtype)
        _batch_cast_params(self.collect_params(), dtype)

    def _cast_meta(self, dtype):
        for child in self._children.values():
            child._cast_meta(dtype)
        for param in self._params.values():
            param.cast(dtype, _convert=False)

    def zero_grad(self):
        self.collect_params().zero_grad()

    # -- persistence (ref: save_parameters/load_parameters) ----------------
    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        from .. import ndarray as nd
        nd.save(filename, {k: v.data() for k, v in params.items()
                           if v._data is not None})

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from .. import ndarray as nd
        loaded = nd.load(filename, ctx=ctx)
        # reference checkpoints key arrays as "arg:name"/"aux:name"
        loaded = {(k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                   else k): v for k, v in loaded.items()}
        params = self._collect_params_with_prefix()
        if not allow_missing:
            for name in params:
                if name not in loaded and params[name]._data is not None:
                    raise MXNetError("parameter %s missing in file" % name)
        for name, data in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise MXNetError("parameter %s not in block" % name)
                continue
            params[name]._load_and_set(data, ctx)

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- call --------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return _np_mode_out(out)

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary table (ref: Block.summary —
        layer name, output shape, param count) by running a hooked
        forward on `inputs`."""
        rows = []
        hooks = []
        seen_params = set()

        def _count_params(block, trainable_only=False):
            n = 0
            for p in block._reg_params.values():
                if p._data is None and not p._shape_known():
                    continue
                if trainable_only and p.grad_req == "null":
                    continue
                size = 1
                for d in (p.shape or ()):
                    size *= d
                n += size
            return n

        def _register(block, prefix):
            def hook(blk, args, out, _name=prefix or
                     block.__class__.__name__):
                first = out[0] if isinstance(out, (list, tuple)) else out
                shape = tuple(getattr(first, "shape", ()))
                rows.append((_name, blk.__class__.__name__, shape,
                             _count_params(blk)))
            hooks.append(block.register_forward_hook(hook))
            for name, child in block._children.items():
                _register(child, (prefix + "." if prefix else "") + name)

        _register(self, "")
        # force the imperative path: the cached-graph executable would
        # bypass every child's forward hooks (upstream raises on active
        # hybridized blocks; deactivate-and-restore is strictly better)
        deactivated = []

        def _deactivate(b):
            if getattr(b, "_active", False):
                b._active = False
                deactivated.append(b)
            for c in b._children.values():
                _deactivate(c)

        _deactivate(self)
        try:
            with _ag.pause():
                self(*inputs)
        finally:
            for h in hooks:
                h.detach()
            for b in deactivated:
                b._active = True

        lines = ["%s" % ("-" * 68),
                 "%-28s %-14s %14s %8s" % ("Layer", "Type",
                                           "Output Shape", "Params"),
                 "=" * 68]
        total = 0
        for name, typ, shape, n in rows:
            lines.append("%-28s %-14s %14s %8d"
                         % (name[:28] or "(self)", typ[:14],
                            str(shape), n))
        for p in self.collect_params().values():
            if id(p) in seen_params:
                continue
            seen_params.add(id(p))
            if p.shape and all(d > 0 for d in p.shape):
                size = 1
                for d in p.shape:
                    size *= d
                total += size
        lines.append("=" * 68)
        lines.append("Total params: %d" % total)
        lines.append("-" * 68)
        out = "\n".join(lines)
        print(out)
        return out

    def __repr__(self):
        s = "{name}(\n{modstr}\n)" if self._children else "{name}()"
        modstr = "\n".join("  (%s): %s" % (k, _indent(repr(v)))
                           for k, v in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)


def _indent(s):
    return s.replace("\n", "\n  ")


class _HookHandle:
    """Removable hook registration (ref: mxnet.gluon.utils.HookHandle)."""

    def __init__(self, hooks_list, hook):
        self._list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hook in self._list:
            self._list.remove(self._hook)

    remove = detach


def _np_mode_out(out):
    """np mode (npx.set_np()): blocks hand back mx.np ndarrays (ref:
    gluon blocks return np arrays when the np flag is on)."""
    from ..util import is_np_array
    if is_np_array():
        from ..numpy.multiarray import from_nd
        return from_nd(out)
    return out


def _flat_symbols(out):
    if isinstance(out, (list, tuple)):
        flat = []
        for o in out:
            flat.extend(_flat_symbols(o))
        return flat
    return [out]


# ---------------------------------------------------------------------------
# deferred dispatch + cross-block fusion
# ---------------------------------------------------------------------------

class _PendingCall:
    """A cached-op forward whose XLA dispatch is deferred.

    The async-engine analogue (ref: threaded_engine.cc op queue /
    cached_op.cc bulked segments, SURVEY §3.2-3.3): deferral exists so
    the NEXT cached-op call — typically the hybridized loss applied to
    this block's output — composes with this program into ONE jitted
    fwd+vjp executable before anything reaches the device.  Any other
    consumer (``.asnumpy()``, an eager op, scope exit) forces the
    original single-block program, which is exactly the round-2 path."""

    __slots__ = ("graph", "skey", "leaf_data", "flat_inputs", "ctx",
                 "out_nds", "done")

    will_record = True

    def __init__(self, graph, skey, leaf_data, flat_inputs, ctx):
        self.graph = graph
        self.skey = skey            # (fkey, input avals) — shape-exact
        self.leaf_data = leaf_data
        self.flat_inputs = flat_inputs
        self.ctx = ctx
        self.done = False
        avals = graph._out_avals[skey]
        outs = []
        for i in range(len(avals)):
            nd = NDArray.__new__(NDArray)
            nd._data_v = None
            nd._pending = self
            nd._ctx = ctx
            nd._grad = None
            nd._grad_req = None
            nd._tape_node = None
            nd._out_index = i
            outs.append(nd)
        self.out_nds = outs
        _ag._register_pending(self, "fwd")

    @property
    def fkey(self):
        return self.skey[0]

    def aval_of(self, nd):
        return self.graph._out_avals[self.skey][nd._out_index]

    def force(self):
        if self.done:
            return
        self.done = True
        _ag._unregister_pending(self)
        self.graph._dispatch_deferred(self)


class _FusedProgram:
    """One producer→consumer composition (net+loss), cached on the
    producer graph.  Holds the raw composed pure function, its jitted
    fwd+vjp, result avals (via jax.eval_shape — no dispatch needed), and
    the jitted whole-train-step executables the optimizer layer builds
    over it (fwd+vjp+update in ONE program, ref: SURVEY §3.3 bulked
    segments ≙ ShardedTrainer's step assembled from the imperative
    tape)."""

    __slots__ = ("raw", "fwd_jit", "keep", "n_net", "n_loss",
                 "loss_graph", "loss_fkey", "net_graph", "net_fkey",
                 "avals", "train_step_jits")

    def __init__(self, raw, keep, n_net_leaves, loss_graph, loss_fkey,
                 net_graph, net_fkey, avals, n_loss):
        import jax
        self.raw = raw

        def fwd(*leaves):
            return jax.vjp(raw, *leaves)
        self.fwd_jit = _costs.metered_jit(
            fwd, label="gluon.fused_fwd_vjp", kind="train")
        self.keep = keep
        self.n_net = n_net_leaves
        self.n_loss = n_loss
        self.loss_graph = loss_graph
        self.loss_fkey = loss_fkey
        self.net_graph = net_graph
        self.net_fkey = net_fkey
        self.avals = avals          # ((shape, np_dtype), ...) full result
        self.train_step_jits = {}


class _PendingFused:
    """A deferred net+loss fused forward.  Three consumers:

    - ``backward()`` on its loss head defers too (``defer_backward``),
      letting ``Trainer.step`` compose forward+backward+update into ONE
      executable — residuals never round-trip through HBM as program
      outputs, matching the pure-jax fused trainer;
    - any buffer read forces the fwd+vjp program (tape recorded, aux
      states written) — the stage-A behaviour;
    - scope-exit flush skips it only while a deferred backward claims it
      (the claim guarantees a later force/step materialises it)."""

    __slots__ = ("prog", "leaves", "inputs", "ctx", "out_nds", "done",
                 "claimed", "vjp_closure")

    will_record = True

    def __init__(self, prog, leaves, inputs, ctx):
        self.prog = prog
        self.leaves = leaves
        self.inputs = inputs        # tape inputs (no key-bits)
        self.ctx = ctx
        self.done = False
        self.claimed = False
        self.vjp_closure = None
        outs = []
        for i in range(len(prog.avals)):
            nd = NDArray.__new__(NDArray)
            nd._data_v = None
            nd._pending = self
            nd._ctx = ctx
            nd._grad = None
            nd._grad_req = None
            nd._tape_node = None
            nd._out_index = i
            outs.append(nd)
        self.out_nds = outs
        _ag._register_pending(self, "fwd")

    def aval_of(self, nd):
        return self.prog.avals[nd._out_index]

    def force(self):
        if self.done:
            return
        self.done = True
        _ag._unregister_pending(self)
        prog = self.prog
        from .. import engine as _engine
        with _engine._dispatch_hook(
                prog.net_graph.block.name + "+" +
                prog.loss_graph.block.name + "_fused", self.ctx):
            result, vjp_closure = prog.fwd_jit(*self.leaves)
        if _engine.has_listeners():
            _engine.emit_fused_ops(
                "fused_fwd", self.ctx,
                prog.net_graph._trace_ops.get(prog.net_fkey, []) +
                prog.loss_graph._trace_ops.get(prog.loss_fkey, []))
        if _engine.naive_mode():
            for o in result:
                o.block_until_ready()
        self.vjp_closure = vjp_closure
        for nd, val in zip(self.out_nds, result):
            nd._data_v = val
            nd._pending = None
        vjp = _ag._JitVjp(vjp_closure, prog.keep)
        _ag.record_op(vjp, self.inputs, tuple(self.out_nds),
                      name=(prog.net_graph.block.name + "+" +
                            prog.loss_graph.block.name + "_fused"),
                      out_is_tuple=True)
        self._writeback_states()

    def _writeback_states(self):
        prog = self.prog
        _, lsp = prog.loss_graph._trace_meta[prog.loss_fkey]
        if lsp:
            tail = self.out_nds[prog.n_loss - len(lsp):prog.n_loss]
            for p, nd in zip(lsp, tail):
                _write_state_all_ctx(p, nd._data_v, pending=self)
        _, nsp = prog.net_graph._trace_meta[prog.net_fkey]
        if nsp:
            for p, nd in zip(nsp, self.out_nds[len(self.out_nds) -
                                               len(nsp):]):
                _write_state_all_ctx(p, nd._data_v, pending=self)

    def finish_from_train_step(self, result):
        """The whole-step executable already ran fwd+bwd+update: fill
        the outputs and write aux states; no tape node (the step is
        complete — a second backward through it would be a freed-graph
        error in eager semantics too)."""
        self.done = True
        _ag._unregister_pending(self)
        for nd, val in zip(self.out_nds, result):
            nd._data_v = val
            nd._pending = None
        self._writeback_states()

    def defer_backward(self, head, head_grad):
        """backward() on the (still-deferred) loss head: park the seed
        cotangents as a producer-linked _PendingGrads.  Returns False
        when the eager path must run."""
        import jax.numpy as jnp
        if self.done or head._pending is not self:
            return False
        prog = self.prog
        cots = []
        for i, (shape, dt) in enumerate(prog.avals):
            if not jnp.issubdtype(jnp.dtype(dt), jnp.inexact):
                return False
            if i == head._out_index:
                cots.append(_ag._ones_const(shape, dt)
                            if head_grad is None else head_grad._data)
            else:
                cots.append(_ag._zeros_const(shape, dt))
        targets = []
        seen = set()
        for j, inp in enumerate(self.inputs):
            if inp is None:
                continue
            p_in = getattr(inp, "_pending", None)
            if inp._tape_node is not None or (
                    p_in is not None and getattr(p_in, "will_record",
                                                 False)):
                # upstream recorded history: gradients must flow PAST
                # this program — only the full tape walk does that
                return False
            if inp._grad_req in (None, "null"):
                continue
            if (inp._grad_req != "write" or inp._grad is None or
                    getattr(inp._grad, "stype", "default") != "default"
                    or id(inp) in seen):
                return False
            seen.add(id(inp))
            targets.append((j, inp))
        if not targets:
            return False
        items = []
        for j, inp in targets:
            g = inp._grad
            shp, dt = tuple(g.shape), g.dtype
            stale = g._pending
            if stale is not None:
                if not hasattr(stale, "detach_target"):
                    return False
                stale.detach_target(g)
            items.append((g, prog.keep[j], shp, dt))
        self.claimed = True
        _ag._PendingGrads(None, tuple(cots), items, producer=self)
        return True


class _XformPending:
    """A shape-only unary op (reshape/transpose/cast/...) applied to a
    lazy cached-op output: carries the (op, kwargs) chain so a consuming
    cached-op's fused trace applies it inline; forcing replays it through
    the normal recorded dispatch on the materialised source."""

    __slots__ = ("base", "src", "nd", "base_index", "chain", "_aval",
                 "done")

    will_record = True

    def __init__(self, base, src, base_index, chain, aval):
        self.base = base            # originating _PendingCall
        self.src = src              # immediate source NDArray
        self.base_index = base_index
        self.chain = chain          # ((opname, frozen_kwargs), ...)
        self._aval = aval
        self.nd = None              # target, set by try_lazy_unary
        self.done = False

    def aval_of(self, nd):
        return self._aval

    def force(self):
        if self.done:
            return
        self.done = True
        _ag._unregister_pending(self)
        from ..ndarray.ndarray import invoke
        self.src._data              # materialise the producer chain first
        opname, fkw = self.chain[-1]
        # replay under recording regardless of the CURRENT flag: the op
        # logically executed inside the record scope that deferred it,
        # so its tape node must exist (backward-head / re-use cases)
        prev = _ag.set_recording(True)
        try:
            out = invoke(opname, self.src, **dict(fkw))
        finally:
            _ag.set_recording(prev)
        nd = self.nd
        nd._data_v = out._data_v
        nd._tape_node = out._tape_node
        nd._out_index = out._out_index
        nd._pending = None


def try_lazy_unary(od, nd, kwargs):
    """Called from ndarray.invoke for shape-only unary ops whose input is
    a lazy cached-op output: return a derived lazy NDArray (keeping the
    net→reshape→loss chain fusable) or None to dispatch normally."""
    if not _ag.is_recording():
        return None
    p = nd._pending
    if isinstance(p, _PendingCall):
        if p.done:
            return None
        base, base_index, chain = p, nd._out_index, ()
    elif isinstance(p, _XformPending):
        if p.done or p.base.done:
            return None
        base, base_index, chain = p.base, p.base_index, p.chain
    else:
        return None
    try:
        fkw = tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in kwargs.items()))
        hash(fkw)
    except TypeError:
        return None
    import jax
    try:
        aval = jax.eval_shape(lambda x: od.fn(x, **dict(fkw)),
                              jax.ShapeDtypeStruct(nd.shape, nd.dtype))
    except Exception:
        return None
    if not hasattr(aval, "shape"):      # multi-output op: dispatch normally
        return None
    xp = _XformPending(base, nd, base_index, chain + ((od.name, fkw),),
                       (tuple(aval.shape), _np.dtype(aval.dtype)))
    out = NDArray.__new__(NDArray)
    out._data_v = None
    out._pending = xp
    out._ctx = nd._ctx
    out._grad = None
    out._grad_req = None
    out._tape_node = None
    out._out_index = 0
    xp.nd = out
    # registered so an xform used as a backward head (or left dangling)
    # materialises with its tape node at flush points; a consuming fused
    # call deregisters it instead (value only needed on later reads)
    _ag._register_pending(xp, "fwd")
    return out


# ---------------------------------------------------------------------------
# HybridBlock + cached-op machinery
# ---------------------------------------------------------------------------

class _CachedGraph:
    """The CachedOp equivalent: jitted pure function of
    (param leaves, input leaves, rng key bits) → (out leaves, state leaves).

    ref: src/imperative/cached_op.cc CachedOp — here nnvm passes + memory
    planning + bulking are all jax.jit/XLA; the jit cache keyed by input
    avals replaces the bucketing executors' shared-memory rebinds.
    """

    def __init__(self, block, flags):
        import jax
        self.block = block
        self.flags = flags
        self.param_names = None     # ordered param names (stable)
        self.params = None          # ordered Parameter objects
        self._jitted = {}           # fkey -> jitted forward (inference)
        self._raw = {}              # fkey -> unjitted pure
        self._jit_fwdvjp = {}       # fkey -> jitted fwd returning vjp
        self._out_avals = {}        # fkey -> ((shape, dtype), ...) per leaf
        self._fused = {}            # (fkey, producer, ...) -> jitted fused
        # fkey -> (out_treedef, state_params): BatchNorm-style state
        # outputs exist only in training mode, so trace metadata MUST be
        # keyed by the same (training, np_, ni_) signature as the jitted
        # executables — a single global copy mis-slices outputs when a
        # hybridized net switches between train and eval
        self._trace_meta = {}
        self._trace_ops = {}        # fkey -> [op names] (profiler)
        self._jax = jax

    def _collect_params(self):
        pd = self.block.collect_params()
        self.param_names = list(pd.keys())
        self.params = [pd[n] for n in self.param_names]

    def _make_pure(self, training, fkey):
        import jax
        block = self.block

        def pure(pvals, ivals, key_bits):
            from .. import engine as _engine
            holder = _rnd.KeyHolder(jax.random.wrap_key_data(key_bits))
            # temporarily rebind param data to tracer-backed arrays; restore
            # after tracing (leaking tracers into Parameters would poison
            # later imperative use)
            saved = []
            for p, v in zip(self.params, pvals):
                ctx0 = next(iter(p._data))
                saved.append((p, ctx0, p._data[ctx0]))
                p._data[ctx0] = NDArray(v, ctx=ctx0)
            states = []
            prev_state, _STATE.active = _STATE.active, states
            prev_rec = _ag.set_recording(False)
            prev_train = _ag.set_training(training)
            _rnd.push_trace_key(holder)
            try:
                nd_in = [NDArray(v) for v in ivals]
                with _engine.collect_op_names() as traced_ops:
                    # input transform (uint8→normalized-dtype etc.)
                    # traced here: it becomes part of THIS fused
                    # executable, not a separate dispatch
                    nd_in = list(block._apply_input_transform(nd_in))
                    out = block.forward(*nd_in)
                # op composition of the (fused) executable, for the
                # profiler's aggregate table (per-op times inside ONE
                # XLA program need XPlane — engine.emit_fused_ops)
                self._trace_ops[fkey] = list(traced_ops)
            finally:
                _rnd.pop_trace_key()
                _ag.set_training(prev_train)
                _ag.set_recording(prev_rec)
                _STATE.active = prev_state
                for p, ctx0, orig in saved:
                    p._data[ctx0] = orig
            out_flat, treedef = _flatten_out(out)
            # unconditional: a retrace with the same signature yields the
            # same structure; a NEW signature records its own metadata
            self._trace_meta[fkey] = (treedef, [p for p, _ in states])
            return (tuple(o._data for o in out_flat),
                    tuple(v for _, v in states))
        return pure

    def _get_flat(self, training, np_, ni_):
        """pure_flat(*leaves) -> flat tuple(outs + states); leaves =
        params + inputs + key_bits."""
        fkey = (training, np_, ni_)
        if fkey not in self._raw:
            self._raw[fkey] = self._make_pure(training, fkey)
        pure = self._raw[fkey]

        def pure_flat(*leaves):
            pv = leaves[:np_]
            iv = leaves[np_:np_ + ni_]
            kb = leaves[-1]
            outs, states = pure(pv, iv, kb)
            return tuple(outs) + tuple(states)

        if self.flags.get("remat"):
            import jax
            policy = None
            name = self.flags.get("remat_policy")
            if name:
                policy = getattr(jax.checkpoint_policies, name)
            pure_flat = jax.checkpoint(pure_flat, policy=policy)
        return pure_flat

    def _get_fwd_vjp(self, training, np_, ni_):
        """Jitted forward that ALSO returns the vjp residual closure (a
        jax pytree of arrays).  Backward then consumes the residuals in
        one executable with NO forward recompute — the
        CachedOp::Forward/Backward pair sharing cached intermediates
        (ref: cached_op.cc forward graph feeding the backward graph)."""
        import jax
        fkey = (training, np_, ni_)
        if fkey in self._jit_fwdvjp:
            return self._jit_fwdvjp[fkey]
        pure_flat = self._get_flat(training, np_, ni_)

        def fwd(*leaves):
            outs, vjp_fn = jax.vjp(pure_flat, *leaves)
            return outs, vjp_fn
        self._jit_fwdvjp[fkey] = _costs.metered_jit(
            fwd, label=self.block.name + ".fwd_vjp", kind="train")
        return self._jit_fwdvjp[fkey]

    def __call__(self, args):
        import jax
        if self.param_names is None:
            self._collect_params()
        training = _ag.is_training()
        ctx = args[0].context if args and isinstance(args[0], NDArray) \
            else current_context()

        param_nds = [p.data(ctx) for p in self.params]
        _flush_state_writers(self.params)
        # key bits derived host-side (zero device ops) and fed as a plain
        # numpy jit input; the executable wraps them into a typed key
        key_bits = _rnd.next_key_bits(ctx)
        flat_inputs = list(param_nds) + list(args)
        np_, ni_ = len(param_nds), len(args)

        fkey = (training, np_, ni_)
        record = _ag.is_recording() and any(
            _ag._requires_tracking(a) for a in flat_inputs)

        from .. import config as _cfg
        fusion_on = _cfg.get("MXNET_CACHEDOP_FUSION") == "1"
        if record and fusion_on:
            # an input produced by a still-pending cached-op: compose the
            # two programs into ONE fwd+vjp executable (net+loss fusion)
            out = self._try_fused_call(args, param_nds, key_bits, fkey,
                                       ctx)
            if out is not NotImplemented:
                return out

        # shape-exact signature: out avals depend on input shapes, so the
        # deferred path must never serve avals recorded for another batch
        skey = (fkey, tuple((tuple(a.shape), str(a.dtype))
                            for a in args))

        # reading ._data forces any unfusable pending producers
        leaf_data = [a._data for a in flat_inputs] + [key_bits]

        if record and fusion_on and fkey in self._trace_meta \
                and skey in self._out_avals:
            # steady state: defer dispatch so a following cached-op call
            # (the hybridized loss) can fuse with this one; any other
            # consumer forces the single-block program unchanged
            pending = _PendingCall(self, skey, leaf_data, flat_inputs,
                                   ctx)
            treedef, state_params = self._trace_meta[fkey]
            _mark_state_writers(state_params, pending)
            n_outs = len(pending.out_nds) - len(state_params)
            return _unflatten_out(list(pending.out_nds[:n_outs]), treedef)

        from .. import engine as _engine
        with _engine._dispatch_hook(self.block.name + "_cachedop", ctx):
            if record:
                # forward keeps vjp residuals on device: backward is one
                # executable, no forward recompute
                result, vjp_closure = self._get_fwd_vjp(
                    training, np_, ni_)(*leaf_data)
            else:
                if fkey not in self._jitted:
                    self._jitted[fkey] = _costs.metered_jit(
                        self._get_flat(training, np_, ni_),
                        label=self.block.name + ".fwd", kind="infer")
                result = self._jitted[fkey](*leaf_data)
        if _engine.naive_mode():
            for o in result:
                o.block_until_ready()
        wrapped = tuple(NDArray(o, ctx=ctx) for o in result)

        if record:
            self._out_avals[skey] = tuple(
                (tuple(o.shape), _np.dtype(o.dtype)) for o in result)
            # drop the trailing key-bits grad position
            vjp = _ag._JitVjp(vjp_closure,
                              tuple(range(len(leaf_data) - 1)))
            _ag.record_op(vjp, flat_inputs, wrapped,
                          name=self.block.name + "_cachedop",
                          out_is_tuple=True)

        out_treedef, state_params = self._trace_meta[fkey]
        n_states = len(state_params)
        outs = wrapped[:len(wrapped) - n_states]
        states = wrapped[len(wrapped) - n_states:]
        for p, s in zip(state_params, states):
            # every ctx copy, kept in the param's stored dtype (stats
            # compute in f32)
            _write_state_all_ctx(p, s._data)
        return _unflatten_out(list(outs), out_treedef)

    def _dispatch_deferred(self, pending):
        """Force a deferred forward: dispatch the single-block fwd+vjp
        executable, fill the lazy outputs, record the tape node, write
        aux state — byte-identical to the eager record path."""
        from .. import engine as _engine
        fkey = pending.fkey
        with _engine._dispatch_hook(self.block.name + "_cachedop",
                                    pending.ctx):
            result, vjp_closure = self._get_fwd_vjp(*fkey)(
                *pending.leaf_data)
        if _engine.has_listeners():
            _engine.emit_fused_ops(self.block.name + "_cachedop",
                                   pending.ctx,
                                   self._trace_ops.get(fkey, []))
        if _engine.naive_mode():
            for o in result:
                o.block_until_ready()
        for nd, val in zip(pending.out_nds, result):
            nd._data_v = val
            nd._pending = None
        vjp = _ag._JitVjp(vjp_closure,
                          tuple(range(len(pending.leaf_data) - 1)))
        _ag.record_op(vjp, pending.flat_inputs, tuple(pending.out_nds),
                      name=self.block.name + "_cachedop",
                      out_is_tuple=True)
        _, state_params = self._trace_meta[fkey]
        n_states = len(state_params)
        tail = pending.out_nds[len(pending.out_nds) - n_states:] \
            if n_states else []
        for p, s in zip(state_params, tail):
            _write_state_all_ctx(p, s._data_v, pending=pending)

    def _try_fused_call(self, args, param_nds, key_bits, fkey, ctx):
        """Compose this cached-op with ONE pending producer into a single
        jitted fwd+vjp executable (ref: cached_op.cc builds one graph for
        the whole hybridized segment; here the segment grows across
        user-level block calls — net(x) then loss(net_out, y) become one
        program, and their shared backward one more)."""
        base = None
        specs = []
        consumed_xforms = []
        for a in args:
            p = getattr(a, "_pending", None) if isinstance(a, NDArray) \
                else None
            if p is None:
                specs.append(None)
                continue
            if isinstance(p, _PendingCall) and not p.done:
                b, idx, chain = p, a._out_index, ()
            elif isinstance(p, _XformPending) and not p.done \
                    and not p.base.done:
                b, idx, chain = p.base, p.base_index, p.chain
                consumed_xforms.append(p)
            else:
                return NotImplemented   # unfusable pending: force path
            if base is None:
                base = b
            elif base is not b:
                return NotImplemented   # two producers: force path
            specs.append((idx, chain))
        if base is None or base.graph is self:
            return NotImplemented

        import jax
        training, np_, ni_ = fkey
        concrete_nds = list(param_nds) + [a for a, s in zip(args, specs)
                                          if s is None]
        concrete_leaves = [a._data for a in concrete_nds] + [key_bits]
        n_net = len(base.leaf_data)
        n_lc = len(concrete_leaves)

        # cache lives on the PRODUCER graph: in rebuild loops (hyperparam
        # search) nets die while the loss block lives on — a consumer-side
        # cache would pin every dead net's params/executables forever.
        # Keyed by the consumer OBJECT (not id(): a collected graph's id
        # can be recycled) and by input avals (out avals are shape-exact).
        store = base.graph._fused
        cavals = tuple((tuple(a.shape), str(a.dtype))
                       for a in concrete_leaves)
        cache_key = (self, fkey, base.skey, tuple(specs), cavals)
        prog = store.get(cache_key)
        if prog is None:
            net_flat = base.graph._get_flat(*base.fkey)
            loss_flat = self._get_flat(training, np_, ni_)
            # consumer leaf t ∈ [params..., inputs..., key] sourced from
            # either a concrete leaf or a producer output (+xform chain)
            src_map = [("c", j) for j in range(np_)]
            nc = np_
            for s in specs:
                if s is None:
                    src_map.append(("c", nc))
                    nc += 1
                else:
                    src_map.append(("n",) + s)
            src_map.append(("c", n_lc - 1))     # key bits
            src_map = tuple(src_map)

            def fused(*leaves):
                net_res = net_flat(*leaves[:n_net])
                loss_leaves = []
                for s in src_map:
                    if s[0] == "c":
                        loss_leaves.append(leaves[n_net + s[1]])
                    else:
                        v = net_res[s[1]]
                        for opname, fkw in s[2]:
                            v = _registry.get(opname).fn(v, **dict(fkw))
                        loss_leaves.append(v)
                loss_res = loss_flat(*loss_leaves)
                return tuple(loss_res) + tuple(net_res)

            # result avals via abstract eval — zero device work; the
            # same trace populates the loss graph's _trace_meta
            in_avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in list(base.leaf_data) + concrete_leaves]
            res_avals = jax.eval_shape(fused, *in_avals)
            avals = tuple((tuple(v.shape), _np.dtype(v.dtype))
                          for v in res_avals)
            n_loss = len(avals) - len(base.out_nds)
            # key-bit grad positions dropped, fused-interior grads never
            # materialise
            keep = tuple(range(n_net - 1)) + \
                tuple(range(n_net, n_net + n_lc - 1))
            prog = _FusedProgram(fused, keep, n_net, self, fkey,
                                 base.graph, base.fkey, avals, n_loss)
            store[cache_key] = prog

        # defer: nothing dispatches until something reads a value — the
        # usual consumer is backward()+Trainer.step, which compose the
        # WHOLE step (fwd+vjp+update) into one executable
        inputs = list(base.flat_inputs) + concrete_nds
        pending = _PendingFused(prog,
                                list(base.leaf_data) + concrete_leaves,
                                inputs, ctx)
        # absorb the producer pending: its user-held outputs re-point
        # into the fused result
        base.done = True
        _ag._unregister_pending(base)
        for i, nd in enumerate(base.out_nds):
            if nd._pending is base:
                nd._pending = pending
                nd._out_index = prog.n_loss + i
                pending.out_nds[prog.n_loss + i] = nd
        for xp in consumed_xforms:
            # value computed inside the fused program; a later read
            # replays cheaply off the materialised source instead of
            # re-dispatching at scope exit
            _ag._unregister_pending(xp)

        # the fused program now owns BOTH blocks' aux-state writebacks
        # (the absorbed producer's claims re-point here)
        _mark_state_writers(self._trace_meta[fkey][1], pending)
        _mark_state_writers(base.graph._trace_meta[base.fkey][1],
                            pending)

        ltd, lsp = self._trace_meta[fkey]
        skey = (fkey, tuple((tuple(a.shape), str(a.dtype))
                            for a in args))
        self._out_avals[skey] = prog.avals[:prog.n_loss]
        outs = pending.out_nds[:prog.n_loss - len(lsp)]
        return _unflatten_out(list(outs), ltd)


def _flatten_out(out):
    """Flatten nested tuple/list of NDArray into (leaves, treedef)."""
    if isinstance(out, NDArray):
        return [out], None
    if isinstance(out, (tuple, list)):
        leaves, defs = [], []
        for o in out:
            sub, d = _flatten_out(o)
            defs.append((len(sub), d))
            leaves.extend(sub)
        return leaves, (type(out), defs)
    raise MXNetError("hybrid_forward must return NDArray or (nested) "
                     "tuple/list, got %r" % type(out))


def _unflatten_out(leaves, treedef):
    if treedef is None:
        return leaves[0]
    typ, defs = treedef
    out, i = [], 0
    for n, d in defs:
        sub = leaves[i:i + n]
        out.append(_unflatten_out(sub, d))
        i += n
    return typ(out)


class HybridBlock(Block):
    """ref: gluon.HybridBlock — dual imperative/traced execution."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = None
        self._flags = {}
        self._input_transform = None

    def set_input_transform(self, fn):
        """Install a pure on-device preprocessing function applied to
        the FIRST positional input (e.g. uint8 pixels → normalized
        compute dtype, `io.device_feed.normalize_transform`).  On a
        hybridized block it is traced INTO the cached forward
        executable, so the cast+normalize fuses with the train step:
        uint8 stays the wire format and the float tensor only ever
        exists on device.  Eager calls apply it before forward (same
        numerics); the Symbol/export path ignores it.  Pass None to
        remove."""
        self._input_transform = fn
        self._cached_graph = None

    def _apply_input_transform(self, args):
        tr = getattr(self, "_input_transform", None)
        if tr is not None and args and isinstance(args[0], NDArray):
            return (tr(args[0]),) + tuple(args[1:])
        return args

    def inference_engine(self, **kwargs):
        """Build a `serving.InferenceEngine` over this block: concurrent
        request API, shape-bucketed dynamic batching, pre-compiled
        executables (ISSUE 3).  Any installed `set_input_transform`
        (e.g. `io.device_feed.normalize_transform`) is traced into every
        bucket executable, so uint8-on-wire inference matches the
        training feed path byte-for-byte.  Keyword args are forwarded to
        `InferenceEngine` (ctx/devices, buckets, max_batch, queue_cap,
        example_shape, wire_dtype, handle_sigterm, ...)."""
        from ..serving import InferenceEngine
        return InferenceEngine(self, **kwargs)

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None,
                  backward_bulk_size=None, remat=False, remat_policy=None):
        """static_alloc/static_shape accepted for API parity; XLA buffer
        assignment + donation already provide them (SURVEY §7.0).

        remat=True enables rematerialisation (SURVEY §5.7: the
        reference's memonger/grad-mirroring role): backward recomputes
        this block's forward instead of storing residuals, trading FLOPs
        for HBM — the standard long-context lever on TPU.  remat_policy
        names a jax.checkpoint_policies member (e.g.
        'dots_with_no_batch_dims_saveable') for selective saving."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, remat=remat,
                           remat_policy=remat_policy)
        self._cached_graph = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes WITHOUT
        executing any compute (ref: HybridBlock's _deferred_infer_shape
        runs symbolic InferShape; here the forward runs abstractly under
        jax.eval_shape — XLA abstract eval IS the shape pass).

        Parametrised leaf layers override this with a direct rule
        (e.g. Dense sets weight from x.shape); this default drives the
        whole composite: each child materialises its params when the
        abstract trace reaches it."""
        if _SHAPEPASS.active:
            # re-entered from a leaf layer that has no shape rule while
            # already inside the abstract pass: nothing more to infer
            return
        import jax
        _SHAPEPASS.active = True
        # swallow state updates (running stats) — values are tracers here
        prev_state, _STATE.active = _STATE.active, []
        # sandbox RNG: ops like Dropout split keys during the trace; the
        # stateful per-ctx key must not be overwritten with a tracer
        _rnd.push_trace_key(_rnd.KeyHolder(jax.random.PRNGKey(0)))
        try:
            def f(*ivals):
                nd_in = [NDArray(v) for v in ivals]
                with _ag.pause():
                    self.forward(*nd_in)
                return 0
            jax.eval_shape(f, *[
                jax.ShapeDtypeStruct(a.shape, a._data.dtype) if
                isinstance(a, NDArray) else a for a in args])
        finally:
            _rnd.pop_trace_key()
            _STATE.active = prev_state
            _SHAPEPASS.active = False

    def _finish_deferred(self, *args):
        try:
            self.infer_shape(*args)
        except NotImplementedError:
            raise
        for p in self._reg_params.values():
            if p._deferred_init:
                if _SHAPEPASS.active:
                    # abstract pass: shapes are now known; real
                    # initialization (RNG on concrete buffers) must not
                    # run inside the eval_shape trace — it happens on
                    # the first real forward / in __call__'s pre-pass
                    continue
                p._finish_deferred_init()

    def _cast_meta(self, dtype):
        self._cached_graph = None
        super()._cast_meta(dtype)

    def __call__(self, *args, **kwargs):
        from ..symbol.symbol import Symbol as _Sym
        if args and isinstance(args[0], _Sym):
            # symbol trace (export path): bypass the cached executable
            return Block.__call__(self, *args, **kwargs)
        # _STATE.active is not None ⇔ some ancestor cached-op is tracing:
        # children must trace inline (ref: CachedOp inlines the whole
        # subgraph; nested CachedOps are not re-entered)
        if self._active and not kwargs and _STATE.active is None:
            if self._cached_graph is None:
                # materialise deferred params before tracing (ref:
                # CachedOp created after first forward's shape inference).
                # Abstract pass first (no FLOPs); full imperative pass as
                # fallback for forwards eval_shape can't abstract
                try:
                    pd = self.collect_params()
                    deferred = any(p._deferred_init for p in pd.values())
                except Exception:
                    deferred = False
                if deferred:
                    # shape/init pre-passes see POST-transform inputs
                    # (the dtype the traced forward will compute in)
                    pre = self._apply_input_transform(args)
                    try:
                        self.infer_shape(*pre)
                        for p in pd.values():
                            if p._deferred_init:
                                p._finish_deferred_init()
                    except Exception:
                        with _ag.pause():
                            Block.__call__(self, *pre)
                self._cached_graph = _CachedGraph(self, self._flags)
            return _np_mode_out(self._cached_graph(list(args)))
        return Block.__call__(self, *self._apply_input_transform(args),
                              **kwargs)

    def forward(self, x, *args):
        """Gathers this block's params and calls hybrid_forward with the
        `F` namespace: the ndarray stubs normally (tracing happens at the
        jax level), or the symbol stubs when `x` is a Symbol (export
        path — params become named variable nodes)."""
        from ..symbol.symbol import Symbol as _Sym
        if isinstance(x, _Sym):
            from .. import symbol as F_sym
            params = {k: _param_symbol(p)
                      for k, p in self._reg_params.items()}
            return self.hybrid_forward(F_sym, x, *args, **params)
        from .. import ndarray as F
        ctx = x.context if isinstance(x, NDArray) else None

        def _gather():
            if _SHAPEPASS.active:
                # abstract pass: deferred-but-shape-known params stand in
                # as zeros tracers (values irrelevant, shapes flow)
                import jax.numpy as jnp
                out = {}
                for k, p in self._reg_params.items():
                    if p._data is None and p._deferred_init and \
                            p._shape_known():
                        out[k] = NDArray(jnp.zeros(tuple(p.shape), p.dtype))
                    else:
                        out[k] = p.data(ctx)
                return out
            return {k: p.data(ctx) for k, p in self._reg_params.items()}

        try:
            params = _gather()
        except DeferredInitializationError:
            self._finish_deferred(x, *args)
            params = _gather()
        return self.hybrid_forward(F, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path, epoch=0, remove_amp_cast=True):
        """ref: HybridBlock.export → `path-symbol.json` + epoch params.

        Traces `hybrid_forward` with Symbol inputs (inference mode) into a
        portable graph over the shared op registry, writes its JSON, and
        saves the parameters keyed by their symbol arg names — the two
        artifacts `SymbolBlock.imports` reloads for identical prediction
        (SURVEY §5.4).  Requires initialized parameters with known shapes
        (call the block once first)."""
        from .. import symbol as sym_ns
        from .. import ndarray as nd

        pd = self.collect_params()
        uninit = [p.name for p in pd.values()
                  if p._data is None or not p._shape_known()]
        if uninit:
            raise MXNetError(
                "export requires initialized parameters with known shapes "
                "(run a forward pass first); missing: %s" % uninit)

        # input arity: taken from the traced cache when available,
        # else a single 'data' input
        n_in = 1
        if self._cached_graph is not None and self._cached_graph._raw:
            n_in = next(iter(self._cached_graph._raw))[2]
        in_names = ["data"] if n_in == 1 else \
            ["data%d" % i for i in range(n_in)]
        in_syms = [sym_ns.var(n) for n in in_names]

        prev_vars, _SYMTRACE.vars = _SYMTRACE.vars, {}
        prev_train = _ag.set_training(False)
        try:
            out = self(*in_syms)
        finally:
            _ag.set_training(prev_train)
            _SYMTRACE.vars = prev_vars
        if isinstance(out, (list, tuple)):
            out = sym_ns.Group(_flat_symbols(out))

        sym_file = "%s-symbol.json" % path
        out.save(sym_file)
        nd.save("%s-%04d.params" % (path, epoch),
                {p.name: p.data() for p in pd.values()
                 if p._data is not None})
        return sym_file


class SymbolBlock(HybridBlock):
    """ref: gluon.SymbolBlock — wrap a Symbol graph as a Block.

    Every non-input argument of the graph becomes a Parameter named by
    its variable node (shape recovered from the exported `__shape__`
    attr when present), so `load_parameters` on an `export()`ed params
    file restores them by name."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="symbolblock_", params=params)
        from ..symbol.symbol import Symbol as _Sym, Group as _Group
        if isinstance(outputs, (list, tuple)):
            outputs = _Group(_flat_symbols(outputs))
        if isinstance(inputs, _Sym):
            inputs = [inputs]
        self._outputs = outputs
        self._inputs = list(inputs)
        input_names = {i.name for i in self._inputs}
        arg_nodes = [n for n in outputs._topo() if n.op is None]
        for node in arg_nodes:
            if node.name in input_names or node.name in self._params:
                continue
            shape = node.attrs.get("__shape__")
            p = Parameter(node.name,
                          shape=tuple(shape) if shape is not None else None,
                          allow_deferred_init=True)
            self._params._params[node.name] = p

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        from ..symbol import var
        inputs = [var(n) for n in (input_names if isinstance(
            input_names, (list, tuple)) else [input_names])]
        block = SymbolBlock(sym, inputs)
        if param_file:
            block.load_parameters(param_file, ctx=ctx,
                                  allow_missing=False, ignore_extra=True)
        return block

    def _collect_params_with_prefix(self, prefix=""):
        # graph params are keyed by their raw symbol arg names (export()'s
        # params-file convention; load_parameters strips reference-style
        # arg:/aux: key prefixes)
        return dict(self._params.items())

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        super().load_parameters(filename, ctx=ctx,
                                allow_missing=allow_missing,
                                ignore_extra=ignore_extra,
                                cast_dtype=cast_dtype,
                                dtype_source=dtype_source)
        # graph params start uninitialized, so the base missing-param
        # check (which only fires for initialized params) cannot catch a
        # file whose keys match nothing — fail loudly here instead of at
        # the first forward
        if not allow_missing:
            missing = [p.name for p in self._params.values()
                       if p._data is None]
            if missing:
                raise MXNetError(
                    "SymbolBlock: params file %r left graph parameters "
                    "unset: %s" % (filename, missing))

    def forward(self, *args):
        from ..symbol import _eval_symbol
        feed = {i.name: a for i, a in zip(self._inputs, args)}
        pd = self.collect_params()
        for name, p in pd.items():
            if p._data is not None:
                feed[name] = p.data()
        return _eval_symbol(self._outputs, feed)
