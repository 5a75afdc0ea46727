"""lax.scan layer-stacking (ISSUE 18 tentpole part 2).

N structurally-identical layers — transformer encoder blocks, the
bench MLP's hidden Dense stack — each compile their OWN executable
when applied layer-by-layer: compile wall scales with N, and on the
host-bound virtual mesh dispatch ≈ step time (MULTICHIP breakdown),
so N dispatches per forward is the cost floor.  The XLA answer is to
make the layer count a LOOP, not a program size: stack the per-layer
parameters along a new leading axis and run ONE ``lax.scan`` whose
body is the layer function — one trace, one compile, one dispatch,
N iterations.

Contract: stacking is only sound when the layers are structurally
identical (same param tree, same leaf shapes/dtypes) — ``stackable``
checks exactly that, and ``verify_parity`` is the bit-parity oracle:
the scanned executable must produce the SAME BITS as the unstacked
python-loop path (same primitives in the same order per iteration),
not merely close ones.  ``measure`` produces the compile-wall and
per-dispatch deltas the MULTICHIP compile block reports.
"""
from __future__ import annotations

import time

import numpy as _np

from ..telemetry import costs as _costs
from ..telemetry import flightrec as _bb

__all__ = ["stackable", "stack_params", "unstack_params", "scan_apply",
           "unrolled_apply", "verify_parity", "measure"]


def _flatten(params_list):
    import jax
    flats, defs = [], []
    for p in params_list:
        leaves, treedef = jax.tree_util.tree_flatten(p)
        flats.append(leaves)
        defs.append(treedef)
    return flats, defs


def stackable(params_list) -> bool:
    """True when every layer's param tree has the same structure and
    every corresponding leaf the same shape+dtype — the precondition
    for one scanned executable to stand in for N per-layer ones."""
    if len(params_list) < 2:
        return len(params_list) == 1
    flats, defs = _flatten(params_list)
    if any(d != defs[0] for d in defs[1:]):
        return False
    ref = [(tuple(getattr(x, "shape", ())),
            str(getattr(x, "dtype", type(x)))) for x in flats[0]]
    for leaves in flats[1:]:
        got = [(tuple(getattr(x, "shape", ())),
                str(getattr(x, "dtype", type(x)))) for x in leaves]
        if got != ref:
            return False
    return True


def stack_params(params_list):
    """N same-structure per-layer param trees -> ONE tree whose leaves
    gained a leading layer axis of length N (the scan carry input).
    Raises ValueError when the layers are not stackable."""
    import jax
    import jax.numpy as jnp
    if not params_list:
        raise ValueError("stack_params: empty layer list")
    if not stackable(params_list):
        raise ValueError(
            "stack_params: layers are not structurally identical "
            "(param tree / leaf shape / dtype mismatch) — scan "
            "stacking needs one layer program that fits every layer")
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *params_list)


def unstack_params(stacked):
    """Inverse of ``stack_params``: the list of per-layer trees."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    n = int(leaves[0].shape[0]) if leaves else 0
    return [jax.tree_util.tree_unflatten(
        treedef, [leaf[i] for leaf in leaves]) for i in range(n)]


def scan_apply(layer_fn, stacked, x):
    """Apply ``layer_fn(params_i, h) -> h`` over the stacked layer axis
    with ONE ``lax.scan`` — the single-executable forward."""
    import jax

    def body(h, params_i):
        return layer_fn(params_i, h), None

    out, _ = jax.lax.scan(body, x, stacked)
    return out


def unrolled_apply(layer_fn, params_list, x):
    """The reference path: the plain python loop over layers (N
    applications, N executables when each is jitted separately)."""
    h = x
    for p in params_list:
        h = layer_fn(p, h)
    return h


def verify_parity(layer_fn, params_list, x):
    """The bit-parity oracle: the scanned forward against the unrolled
    one, compared for EXACT equality (scan runs the same primitives in
    the same order per iteration, so same bits is the contract — a
    mismatch means the layer body is shape-polymorphic or stateful and
    must not be stacked).  Returns ``{"ok", "bitwise",
    "max_abs_diff", "n_layers"}``."""
    import jax
    stacked = stack_params(params_list)
    a = jax.jit(lambda s, v: scan_apply(layer_fn, s, v))(stacked, x)
    b = jax.jit(lambda v: unrolled_apply(layer_fn, params_list, v))(x)
    a = _np.asarray(a)
    b = _np.asarray(b)
    bitwise = bool(a.shape == b.shape and _np.array_equal(a, b))
    diff = float(_np.max(_np.abs(a - b))) if a.shape == b.shape \
        else float("inf")
    out = {"ok": bitwise, "bitwise": bitwise, "max_abs_diff": diff,
           "n_layers": len(params_list)}
    _bb.record("compile", "stack_parity", **out)
    return out


def measure(layer_fn, params_list, x, calls=20, label="stacking"):
    """Measured compile-wall + dispatch comparison: N per-layer
    executables (one fresh ``jit`` per layer — the status quo this
    module removes) vs ONE scanned executable.

    Compile wall is timed through ``lower().compile()`` with the
    in-process trace/executable caches cleared before every compile
    (`jax.clear_caches`), so each executable pays its honest
    cold cost — N identical layers would otherwise dedupe to ~one
    compile inside this process while every OTHER process still pays
    N.  Dispatch is the per-forward host wall over ``calls``
    synchronized calls.  The stacked executable files a cost-registry
    row (kind="stacked") so teletop/blackbox attribute it.  Returns
    the delta dict the MULTICHIP compile block embeds."""
    import jax
    n = len(params_list)
    stacked = stack_params(params_list)

    # unstacked: one executable per layer, compiled back to back,
    # each from a cold cache (the N-process reality)
    jax.clear_caches()
    t0 = time.perf_counter()
    per_layer = []
    for p in params_list:
        lowered = jax.jit(layer_fn).lower(p, x)
        per_layer.append(lowered.compile())
        jax.clear_caches()
    compile_unstacked = time.perf_counter() - t0

    def scanned(s, v):
        return scan_apply(layer_fn, s, v)

    t0 = time.perf_counter()
    lowered = jax.jit(scanned).lower(stacked, x)
    compiled = lowered.compile()
    compile_stacked = time.perf_counter() - t0
    try:
        key = _costs.note_executable("stacked", "%s.scan[%d]"
                                     % (label, n), lowered=lowered,
                                     compiled=compiled,
                                     compile_s=compile_stacked)
    except Exception:               # noqa: BLE001 — attribution is
        key = None                  # best-effort, never fatal

    def run_unstacked(v):
        h = v
        for p, exe in zip(params_list, per_layer):
            h = exe(p, h)
        return h

    # warm both paths once (first call pays transfer/initialization)
    jax.block_until_ready(run_unstacked(x))
    jax.block_until_ready(compiled(stacked, x))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = run_unstacked(x)
    jax.block_until_ready(out)
    dispatch_unstacked = (time.perf_counter() - t0) / calls
    t0 = time.perf_counter()
    for _ in range(calls):
        out = compiled(stacked, x)
    jax.block_until_ready(out)
    dispatch_stacked = (time.perf_counter() - t0) / calls
    if key is not None:
        _costs.invoke(key, calls + 1)

    parity = verify_parity(layer_fn, params_list, x)
    result = {
        "n_layers": n,
        "executables_unstacked": n,
        "executables_stacked": 1,
        "compile_wall_unstacked_s": round(compile_unstacked, 4),
        "compile_wall_stacked_s": round(compile_stacked, 4),
        "compile_wall_reduction": round(
            1.0 - compile_stacked / compile_unstacked, 4)
        if compile_unstacked > 0 else 0.0,
        "dispatch_unstacked_us": int(dispatch_unstacked * 1e6),
        "dispatch_stacked_us": int(dispatch_stacked * 1e6),
        "parity_ok": bool(parity["ok"]),
        "parity_max_abs_diff": parity["max_abs_diff"],
    }
    _bb.record("compile", "stack_measure", label=str(label), **result)
    return result
