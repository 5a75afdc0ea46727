"""The run of a training cell: one compiled step with its state, driven
from the seed through its first steps, then timed.

Set-up builds ONE object (the builder's system), takes its first three
steps through the same `step()` the window calls, and hands that object
to the window.  After the window the program's state is freed and the
plain reference follows the same three steps from the same weights and
batch; `correct` compares each step's loss, the norm of the first
gradient as the optimizer got it, and the norm of the parameters' change
after the three steps, the last two by the worst leaf.

The window: steps are dispatched with one step in flight ahead of the
host, the clock starts and stops on `block_until_ready`, and the rate is
all items of all steps over all of that time.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

import harness
import roles as roles_mod
import trace_reduce
import weights as weights_mod

FOLLOW = 3


def _norms(tree):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for k, a in t.items()})
    return {k: float(v) for k, v in fn(tree).items()}


def _delta_norms(a, b):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x, y: {k: jnp.sqrt(jnp.sum(jnp.square(
        x[k].astype(jnp.float32) - y[k].astype(jnp.float32)))) for k in x})
    return {k: float(v) for k, v in fn(a, b).items()}


def first_steps(system):
    """Losses of the first FOLLOW steps, per-leaf norms of the first
    gradient and of the change after them, from the program."""
    import jax
    import jax.numpy as jnp
    p0 = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(
        system.params())
    losses, gnorm = [], None
    for t in range(1, FOLLOW + 1):
        h = system.step()
        system.fence()
        losses.append(system.loss_value(h))
        if t == 1:
            gnorm = _norms(system.first_gradients())
    delta = _delta_norms(system.params(), p0)
    return {"loss": losses, "grad_norm": gnorm, "delta_norm": delta}


def compare(got, ref, limits):
    """Each number compared beside its limit.  Norms by the worst leaf:
    the gap between the two norms against the reference's norm of that
    leaf or of the median leaf, whichever is larger.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    compared = {}
    for t, (a, b) in enumerate(zip(got["loss"], ref["loss"]), 1):
        compared["loss_step%d" % t] = {
            "value": abs(a - b) / abs(b), "limit": limits["loss"]}
    gmed = statistics.median(ref["grad_norm"].values())
    worst, leaf = 0.0, None
    for k, r in ref["grad_norm"].items():
        gap = abs(got["grad_norm"][k] - r) / max(r, gmed)
        if gap >= worst:
            worst, leaf = gap, k
    compared["grad_norm_worst"] = {
        "value": worst, "limit": limits["grad_norm"], "leaf": leaf,
        "got": got["grad_norm"][leaf], "ref": ref["grad_norm"][leaf]}
    moved = [k for k, r in ref["grad_norm"].items() if r >= 1e-3 * gmed]
    dmed = statistics.median(ref["delta_norm"][k] for k in moved)
    worst, leaf = 0.0, None
    for k in moved:
        r = ref["delta_norm"][k]
        gap = abs(got["delta_norm"][k] - r) / max(r, dmed)
        if gap >= worst:
            worst, leaf = gap, k
    compared["delta_norm_worst"] = {
        "value": worst, "limit": limits["delta_norm"], "leaf": leaf,
        "got": got["delta_norm"][leaf], "ref": ref["delta_norm"][leaf],
        "median_ref": dmed}
    compared["leaves_left_out"] = {
        "value": len(ref["grad_norm"]) - len(moved), "limit": None}
    ok = all(v["value"] <= v["limit"] for v in compared.values()
             if v["limit"] is not None)
    return ok, compared


def _probe_roles(system, logdir, prefix, k=5):
    trace_reduce.start(logdir)
    try:
        for _ in range(k):
            system.step()
        system.fence()
    finally:
        tr = trace_reduce.stop(logdir)
    counts = trace_reduce.module_counts(tr)
    found = roles_mod.by_counts(counts, {"step": k}, prefix)
    return {"step": found["step"]}


def run(ctx):
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx

    cfg, traffic = ctx.config, ctx.traffic
    builder = harness.load_module("configs", cfg["builder"], ctx.base)
    ref = harness.load_module("reference", cfg["reference"], ctx.base)
    gen = harness.load_module("generators", traffic["generator"], ctx.base)
    dev = ctx.devices[0]
    mxctx = mx.tpu(0) if dev.platform == "tpu" else mx.cpu(0)
    keep32 = tuple(getattr(ref, "KEEP_F32", lambda c: ())(cfg))

    def fresh_weights():
        return weights_mod.make(ref.spec(cfg), ctx.seed,
                                jnp.dtype(cfg["dtype"]), dev, keep_f32=keep32)

    batch = gen.generate(traffic, cfg, ctx.seed, ctx.seconds)
    system = builder.build(cfg, traffic, fresh_weights(), batch, ctx.devices,
                           mxctx)
    got = first_steps(system)
    ctx.note("first steps: loss %s" % got["loss"])
    for _ in range(2):                      # steady: nothing left to compile
        system.wait(system.step())
    role = None
    if ctx.trace:
        role = _probe_roles(system, ctx.scratch("probe"),
                            cfg.get("executable_prefix", ""))
        ctx.note("roles %s" % role)
    from incubator_mxnet_tpu.monitor import events
    counters = ("train.traces",)
    system.fence()

    # ---- the window -----------------------------------------------------
    c_open = {n: events.get(n) for n in counters}
    t_open = time.monotonic()
    setup_s = t_open - ctx.t_start
    ctx.note("window open, setup_s %.2f" % setup_s)
    trace = None
    trace_at = t_open + 0.3 * ctx.seconds if ctx.trace else None
    tl = min(float(traffic.get("trace_s", 3.0)), 0.5 * ctx.seconds)
    steps, prev, stall_s = 0, None, 0.0
    end = t_open + ctx.seconds
    while time.monotonic() < end:
        if trace_at is not None and time.monotonic() >= trace_at:
            logdir = ctx.scratch("trace")
            if prev is not None:
                system.wait(prev)
            t_stall = time.monotonic()
            trace_reduce.start(logdir)
            ta = time.monotonic()
            while time.monotonic() < ta + tl:
                h = system.step()
                steps += 1
                if prev is not None:
                    system.wait(prev)
                prev = h
            system.wait(prev)
            tb = time.monotonic()
            trace = trace_reduce.stop(logdir, tb - ta)
            # starting, stopping and reading the profiler stalls the loop:
            # that time is no part of the step rate a traced run reports
            stall_s = (ta - t_stall) + (time.monotonic() - tb)
            trace_at = None
            continue
        h = system.step()
        steps += 1
        if prev is not None:
            system.wait(prev)               # one step in flight, no more
        prev = h
    system.fence()
    t_close = time.monotonic()
    c_close = {n: events.get(n) for n in counters}
    window_s = t_close - t_open
    last_loss = system.loss_value(prev)
    device = harness.device_facts(ctx.devices, ctx.rehearse)
    items = steps * system.items_per_step
    e2e = {"train_items_per_s": items / window_s, "setup_s": setup_s}
    ctx.note("window closed: %d steps in %.3f s, last loss %.4f"
             % (steps, window_s, last_loss))
    system.close()
    del system

    ctx.record.update({
        "kind": "train", "window_s": window_s - stall_s, "steps": steps,
        "items": items,
        "counters_open": c_open, "counters_close": c_close, "trace": trace,
        "roles": role, "builder": builder, "chips": len(ctx.devices)})

    # ---- the plain reference follows the same three steps ---------------
    limits = dict(cfg.get("limits", {}), **traffic.get("limits", {}))
    w32, rbatch = builder.reference_place(
        weights_mod.widen(fresh_weights()), batch, ctx.devices)
    want = ref.follow(w32, cfg, rbatch, steps=FOLLOW)
    correct, compared = compare(got, want, limits)
    for tag, kw in (("control", {"quant": ctx.control}),
                    ("fault", {"fault": ctx.fault})):
        if not list(kw.values())[0]:
            continue
        other = ref.follow(w32, cfg, rbatch, steps=FOLLOW, **kw)
        _, c2 = compare(other, want, limits)
        for k, v in c2.items():
            compared["%s.%s" % (tag, k)] = v
    return {"correct": correct, "attempted": steps, "failed": 0,
            "end_to_end": e2e, "device": device, "compared": compared}
