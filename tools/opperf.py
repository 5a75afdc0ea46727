"""Per-operator performance harness (ref: upstream benchmark/opperf/ —
rule-based per-op benchmarks emitting a machine-readable table).

Measures, per op × shape:
  - ``dispatch_ms``: median host-side cost of one imperative invoke()
    WITHOUT waiting on the device (the tape/dispatch overhead a chain of
    eager ops pays);
  - ``e2e_ms``: per-call wall time of a DEPENDENT chain (each call
    consumes the previous result) ended by a host fetch
    (block_until_ready on independent enqueues measures enqueue rate).

Usage:
  python tools/opperf.py                    # default op set, one JSON doc
  python tools/opperf.py --ops relu,dot     # subset
  python tools/opperf.py --out opperf.json  # also write to file

The default set covers the categories the reference's opperf tracks:
elementwise, broadcast, reduction, matmul/conv/pool, softmax/loss,
transform, random, contrib (NMS/MultiBox), optimizer updates.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _mk(shape, dtype=np.float32, positive=False, ctx=None):
    from incubator_mxnet_tpu import nd
    rs = np.random.RandomState(42)
    a = rs.rand(*shape) if positive else rs.randn(*shape)
    return nd.array(a.astype(dtype), ctx=ctx)


# op name -> (arg builder, kwargs, chainable)  — chainable means output
# shape/dtype == first input's, so a dependent chain re-feeds it.
def _cases(ctx):
    from incubator_mxnet_tpu import nd
    B = 128
    big = (B, 1024)
    img = (8, 64, 56, 56)
    return [
        # elementwise / scalar
        ("relu", [_mk(big, ctx=ctx)], {}, True),
        ("sigmoid", [_mk(big, ctx=ctx)], {}, True),
        ("exp", [_mk(big, ctx=ctx)], {}, True),
        ("sqrt", [_mk(big, positive=True, ctx=ctx)], {}, True),
        ("_plus_scalar", [_mk(big, ctx=ctx)], {"scalar": 1.5}, True),
        # broadcast binary
        ("broadcast_add", [_mk(big, ctx=ctx), _mk((1, 1024), ctx=ctx)],
         {}, True),
        ("broadcast_mul", [_mk(big, ctx=ctx), _mk((1, 1024), ctx=ctx)],
         {}, True),
        # reductions
        ("sum", [_mk(big, ctx=ctx)], {"axis": 1}, False),
        ("mean", [_mk(big, ctx=ctx)], {}, False),
        ("argmax", [_mk(big, ctx=ctx)], {"axis": 1}, False),
        # linear algebra / nn core
        ("dot", [_mk((512, 512), ctx=ctx), _mk((512, 512), ctx=ctx)],
         {}, True),
        ("FullyConnected",
         [_mk((B, 512), ctx=ctx), _mk((512, 512), ctx=ctx),
          _mk((512,), ctx=ctx)], {"num_hidden": 512}, True),
        ("Convolution",
         [_mk(img, ctx=ctx), _mk((64, 64, 3, 3), ctx=ctx),
          _mk((64,), ctx=ctx)],
         {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)}, True),
        ("Pooling", [_mk(img, ctx=ctx)],
         {"kernel": (2, 2), "pool_type": "max", "stride": (2, 2)}, False),
        ("BatchNorm",
         [_mk(img, ctx=ctx), _mk((64,), ctx=ctx), _mk((64,), ctx=ctx),
          _mk((64,), ctx=ctx), _mk((64,), positive=True, ctx=ctx)],
         {}, False),
        # softmax / loss-ish
        ("softmax", [_mk(big, ctx=ctx)], {}, True),
        ("log_softmax", [_mk(big, ctx=ctx)], {}, True),
        ("pick", [_mk(big, ctx=ctx),
                  nd.array(np.zeros(B, np.float32), ctx=ctx)],
         {"axis": 1}, False),
        # transforms
        ("transpose", [_mk((256, 512), ctx=ctx)], {}, False),
        ("reshape", [_mk(big, ctx=ctx)], {"shape": (1024, B)}, False),
        ("slice_axis", [_mk(big, ctx=ctx)],
         {"axis": 1, "begin": 0, "end": 512}, False),
        ("Concat", [_mk(big, ctx=ctx), _mk(big, ctx=ctx)], {"dim": 1},
         False),
        ("take", [_mk((1024, 64), ctx=ctx),
                  nd.array(np.zeros(B, np.int32), ctx=ctx)], {}, False),
        # random
        ("_random_uniform", [], {"shape": big, "ctx": ctx}, False),
        # contrib composite (jit=True registered: ONE program)
        ("box_nms", [_mk((1, 64, 6), positive=True, ctx=ctx)],
         {"overlap_thresh": 0.5}, False),
        # optimizer update ops
        ("sgd_mom_update",
         [_mk(big, ctx=ctx), _mk(big, ctx=ctx), _mk(big, ctx=ctx)],
         {"lr": 0.1, "wd": 1e-4, "momentum": 0.9, "rescale_grad": 1.0,
          "clip_gradient": -1.0}, False),
        ("adam_update",
         [_mk(big, ctx=ctx), _mk(big, ctx=ctx), _mk(big, ctx=ctx),
          _mk(big, positive=True, ctx=ctx)],
         {"lr": 1e-3, "wd": 0.0, "beta1": 0.9, "beta2": 0.999,
          "epsilon": 1e-8, "rescale_grad": 1.0, "clip_gradient": -1.0},
         False),
    ]


def _first(out):
    return out[0] if isinstance(out, (tuple, list)) else out


def bench_op(name, args, kwargs, chainable, n_dispatch=30, n_chain=20):
    from incubator_mxnet_tpu import nd
    invoke = nd.invoke

    out = invoke(name, *args, **kwargs)      # compile/warm
    _first(out).asnumpy()

    # dispatch cost: enqueue only, no sync
    ts = []
    for _ in range(n_dispatch):
        t0 = time.perf_counter()
        invoke(name, *args, **kwargs)
        ts.append(time.perf_counter() - t0)
    dispatch_ms = float(np.median(ts) * 1e3)

    # e2e: dependent chain (or fetch-each-call when not chainable)
    if chainable:
        x = args[0]
        t0 = time.perf_counter()
        cur = x
        for _ in range(n_chain):
            cur = _first(invoke(name, cur, *args[1:], **kwargs))
        cur.asnumpy()
        e2e_ms = (time.perf_counter() - t0) / n_chain * 1e3
    else:
        t0 = time.perf_counter()
        for _ in range(n_chain):
            _first(invoke(name, *args, **kwargs)).wait_to_read()
        e2e_ms = (time.perf_counter() - t0) / n_chain * 1e3
    return dispatch_ms, float(e2e_ms)


def run(ops=None):
    import incubator_mxnet_tpu as mx
    import jax
    ctx = mx.gpu() if jax.default_backend() != "cpu" else mx.cpu()
    rows = []
    for name, args, kwargs, chain in _cases(ctx):
        if ops and name not in ops:
            continue
        try:
            d, e = bench_op(name, args, kwargs, chain)
            rows.append({"op": name,
                         "shape": [list(a.shape) for a in args],
                         "dispatch_ms": round(d, 3),
                         "e2e_ms": round(e, 3)})
        except Exception as exc:        # keep the table going
            rows.append({"op": name, "error": str(exc)[:120]})
    return {"metric": "opperf", "backend": jax.default_backend(),
            "rows": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of op names")
    ap.add_argument("--out", default=None, help="also write JSON here")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend")
    ns = ap.parse_args()
    if ns.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    doc = run(set(ns.ops.split(",")) if ns.ops else None)
    js = json.dumps(doc)
    print(js)
    if ns.out:
        with open(ns.out, "w") as f:
            f.write(js + "\n")


if __name__ == "__main__":
    main()
