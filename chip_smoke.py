#!/usr/bin/env python3
"""Chip smoke: drive the main path once on the accelerator.

    python3 chip_smoke.py                 # needs a TPU; exits non-zero without
    python3 chip_smoke.py --cpu-dry-run   # tiny shapes on mx.cpu(), to debug
                                          # this script's control flow

One process, phases in order, no child process.  Each phase goes through
the entry points a user calls (Gluon + `gluon.Trainer`, `ShardedTrainer`,
`serving.GenerationEngine`, `ops.attention.flash_attention`) at the
published width of the model, prints ONE JSON line, and releases its
arrays before the next.  Any phase that raises, finds one of its arrays
on a non-TPU device, or sees a non-finite loss ends the script with a
traceback and a non-zero exit: nothing is caught and carried on.

Every time printed here is a SMOKE OBSERVATION (`"smoke": true`): a host
clock around `jax.block_until_ready`, a handful of steps, random weights.
It says the path runs and roughly how long compilation takes; it is not a
benchmark and belongs under no metric name.

The last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`
with exactly those keys; the `"phase": "summary"` line before it lists the
phases that ran.  A phase that fails on a chip leaves `"ok": false` there
instead, and the exit code is non-zero; without a chip no result is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import math
import os
import sys
import time
import warnings

PHASES = ("env", "kernels", "train_resnet50", "train_bert_base",
          "serve_nmt", "multichip")


class SmokeFailure(RuntimeError):
    """A check of this script did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# sizes: the chip run is the published width; the dry run is only there to
# exercise this file's control flow on a CPU in about a minute
# --------------------------------------------------------------------------

def _sizes(dry):
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.models import transformer as tfm
    if dry:
        return {
            "kernels": [  # (B, H, T, d, causal)
                (1, 2, 256, 64, False), (1, 2, 256, 64, True)],
            "resnet": dict(model="resnet18_v1",
                           build=lambda: vision.resnet18_v1(classes=10),
                           batch=4, side=32, classes=10),
            "bert": dict(model="bert_small",
                         build=lambda: tfm.bert_small(
                             vocab_size=1000, dropout=0.0,
                             output_hidden=True),
                         vocab=1000, units=64, batch=2, seq=128),
            "nmt": dict(model="transformer_nmt_small",
                        build=lambda: tfm.transformer_nmt_small(97, 97),
                        vocab=97, slots=4, max_len=32, buckets=(8, 16),
                        requests=4, prompt=(3, 14), new=(6, 12)),
            "multichip": dict(
                model="resnet18_v1",
                build=lambda: vision.resnet18_v1(classes=10),
                batch=8, side=32, classes=10),
        }
    return {
        "kernels": [
            (16, 12, 512, 64, False), (16, 12, 512, 64, True),   # BERT-base
            (1, 8, 4096, 64, False), (1, 8, 4096, 64, True)],    # long
        "resnet": dict(model="resnet50_v1b",
                       build=lambda: vision.resnet50_v1b(classes=1000),
                       batch=128, side=224, classes=1000),
        "bert": dict(model="bert_base",
                     build=lambda: tfm.bert_base(dropout=0.0,
                                                 output_hidden=True),
                     vocab=30522, units=768, batch=16, seq=512),
        "nmt": dict(model="transformer_nmt_base",
                    build=lambda: tfm.transformer_nmt_base(32000, 32000),
                    vocab=32000, slots=8, max_len=128,
                    buckets=(16, 32, 64), requests=8, prompt=(5, 60),
                    new=(32, 64)),
        "multichip": dict(
            model="resnet50_v1b",
            build=lambda: vision.resnet50_v1b(classes=1000),
            batch=256, side=224, classes=1000),
    }


# --------------------------------------------------------------------------
# what every phase line carries
# --------------------------------------------------------------------------

class Run:
    """Device facts, the compile cache, and the per-phase JSON line."""

    def __init__(self, dry):
        import jax
        import jaxlib
        from incubator_mxnet_tpu import compile_cache
        self.dry = dry
        self.sizes = _sizes(dry)
        self.cache_dir = compile_cache.enable()
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.versions = {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": _dist_version("libtpu"),
                         "python": sys.version.split()[0]}
        self.ran = []

    def cache_entries(self):
        from incubator_mxnet_tpu import compile_cache
        return compile_cache.entry_count(self.cache_dir)

    def ctx(self, i=0):
        import incubator_mxnet_tpu as mx
        return mx.cpu(i) if self.dry else mx.tpu(i)

    def on_device(self, arrays, what):
        """Every jax array in `arrays` sits on the accelerator (on the
        host CPU in a dry run) — never anywhere else."""
        want = "cpu" if self.dry else "tpu"
        for a in arrays:
            for d in a.devices():
                check(d.platform == want,
                      "%s: array %s %s is on %s, not on a %s device"
                      % (what, a.shape, a.dtype, d, want))

    def memory(self):
        """`memory_stats()` of every device.  None on the chip is a
        failure — there is no live_arrays fallback here."""
        import jax
        out = []
        for d in jax.devices():
            ms = d.memory_stats()
            if ms is None:
                check(self.dry, "device %s reports no memory_stats()" % d)
                out.append({"bytes_in_use": None,
                            "peak_bytes_in_use": None})
            else:
                out.append({"bytes_in_use": int(ms["bytes_in_use"]),
                            "peak_bytes_in_use":
                                int(ms["peak_bytes_in_use"])})
        return out

    def phase(self, name, fn):
        before = self.cache_entries()
        t0 = time.perf_counter()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            body = fn(self)
        donation = sorted({str(w.message)[:200] for w in caught
                           if "donat" in str(w.message).lower()})
        # the phase's arrays are gone by now: what is still in use is
        # what the next phase starts on top of
        gc.collect()
        mem = self.memory()
        donated = self.dry or not donation    # CPU may ignore donation
        line = {"phase": name, "ok": donated, "smoke": True,
                "platform": self.device["platform"],
                "device_kind": self.device["kind"],
                "device_count": self.device["count"],
                **self.versions, **body,
                # the allocator's high-water mark since the process
                # started, not since the phase did
                "peak_bytes_in_use": max(
                    (m["peak_bytes_in_use"] or 0) for m in mem),
                "bytes_in_use_after_release": [m["bytes_in_use"]
                                               for m in mem],
                "donation_warnings": donation,
                "cache_dir": self.cache_dir,
                "cache_entries_before": before,
                "cache_entries_after": self.cache_entries(),
                "phase_wall_s": round(time.perf_counter() - t0, 2)}
        if self.dry:
            line["dry_run"] = True
        print(json.dumps(line), flush=True)
        check(donated, "%s: donation did not take: %s" % (name, donation))
        self.ran.append(name)


def _dist_version(name):
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None


def _timed(fn, n):
    """Host seconds of `n` calls of `fn`, each ended by its own sync."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        out.append(round(time.perf_counter() - t0, 5))
    return out


@contextlib.contextmanager
def _forced_pallas():
    """Both switches on "always": a kernel that cannot run raises."""
    from incubator_mxnet_tpu import config as cfg
    cfg.set("MXNET_USE_PALLAS", "2")
    cfg.set("MXNET_FLASH_BWD_PALLAS", "2")
    try:
        yield
    finally:
        cfg.unset("MXNET_USE_PALLAS")
        cfg.unset("MXNET_FLASH_BWD_PALLAS")


# --------------------------------------------------------------------------
# 1. env
# --------------------------------------------------------------------------

def phase_env(run):
    import jax
    import incubator_mxnet_tpu as mx
    if run.dry:
        check(run.device["platform"] == "cpu",
              "--cpu-dry-run wants the CPU backend, found %r"
              % run.device["platform"])
    else:
        check(run.device["platform"] == "tpu",
              "no TPU: JAX found platform %r (%s x%d); run on the chip "
              "machine, or pass --cpu-dry-run to debug this script"
              % (run.device["platform"], run.device["kind"],
                 run.device["count"]))
        check(mx.num_tpus() == run.device["count"],
              "mx.num_tpus()=%d but jax reports %d devices"
              % (mx.num_tpus(), run.device["count"]))
    return {"default_backend": jax.default_backend(),
            "devices": [str(d) for d in jax.devices()]}


# --------------------------------------------------------------------------
# 2. kernels: ops/attention.py forward, dq, dkv — compiled by Mosaic,
#    checked against naive_attention in float32
# --------------------------------------------------------------------------

def _kernel_case(run, B, H, T, d, causal):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    rs = np.random.RandomState(T + causal)
    dev = run.ctx().jax_device
    q, k, v = (jax.device_put(
        (rs.randn(B, H, T, d) * 0.5).astype(np.float32), dev)
        .astype(jnp.bfloat16) for _ in range(3))
    w = jax.device_put(rs.randn(B, H, T, d).astype(np.float32), dev)
    run.on_device([q, k, v, w], "kernels input")

    def value_and_grads(attend):
        def f(q, k, v):
            o = attend(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    flash = value_and_grads(lambda q, k, v: att.flash_attention(
        q, k, v, causal=causal))
    naive = value_and_grads(lambda q, k, v: att.naive_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), 1.0 / math.sqrt(d), causal=causal))

    t0 = time.perf_counter()
    compiled = flash.lower(q, k, v).compile()
    compile_s = time.perf_counter() - t0
    n_calls = compiled.as_text().count("tpu_custom_call")
    # forward, dq and dkv are three Mosaic programs
    check(run.dry or n_calls >= 3,
          "flash fwd+bwd at T=%d compiled %d tpu_custom_call(s), want "
          "the forward, dq and dkv kernels" % (T, n_calls))

    (_, out), grads = compiled(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = naive(q, k, v)
    run.on_device([out, *grads], "kernels output")

    def rel(a, b):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b.astype(jnp.float32))
        check(np.isfinite(a).all(), "non-finite kernel output")
        return float(np.abs(a - b).max() / np.abs(b).max())

    errs = {name: rel(a, b) for name, a, b in zip(
        ("out", "dq", "dk", "dv"), (out, *grads), (ref, *ref_grads))}
    # bf16 operands and a bf16 P/dS into the second matmul: a few bf16
    # ulps (2^-8) of the largest element
    for name, e in errs.items():
        check(e < 3e-2, "flash %s at BH=%d T=%d causal=%s is %.3g of "
              "max|ref| away from naive_attention"
              % (name, B * H, T, causal, e))
    return {"BH": B * H, "T": T, "d": d, "causal": causal,
            "fwd_blocks": att._block_sizes(T),
            "bwd_blocks": att._bwd_block_sizes(T),
            "tpu_custom_calls": n_calls,
            "compile_s": round(compile_s, 2),
            "fwd_bwd_s": _timed(
                lambda: jax.block_until_ready(compiled(q, k, v)), 3),
            "rel_err": {name: round(e, 5) for name, e in errs.items()}}


def _ragged_case(run, dtype, stacked=False):
    """`decode_attention` as the decode step calls it (on the chip: the
    Mosaic kernel) against the same attention in NumPy float64.  `stacked`:
    the leaves of a model whose layers run several times a token, (S, 3,
    G, T, W) read at index 2, one 128-wide head a row, at the served
    model's slots, heads and rows."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    S, G, T, W, heads = (12, 2, 64, 128, 2) if run.dry \
        else (40, 4, 256, 128, 2)
    if stacked:
        S, G, T, W, heads = (12, 2, 96, 128, 1) if run.dry \
            else (24, 16, 480, 128, 1)
    at = 2 if stacked else None
    rs = np.random.RandomState(S)
    dev = run.ctx().jax_device
    q = jax.device_put(rs.randn(S, G, W).astype(np.float32), dev)
    leaf = (S, 3, G, T, W) if stacked else (S, G, T, W)
    k, v = (jax.device_put(rs.randn(*leaf).astype(np.float32), dev)
            .astype(dtype) for _ in range(2))
    tb = att.ragged_row_block(T, dtype)
    lens = rs.randint(0, T + 1, (S,)).astype(np.int32)
    lens[:5] = [0, 1, tb - 1, min(tb + 1, T), T]
    lens[S // 2:S // 2 + 3] = 0
    run.on_device([q, k, v], "kernels input")
    compiled = jax.jit(lambda q, k, v, n: att.decode_attention(
        q, k, v, n, heads=heads, scale=0.125,
        layer=None if at is None else jnp.int32(at))).lower(
            q, k, v, jax.device_put(lens, dev)).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    check(run.dry or n_calls == 1,
          "decode_attention compiled %d tpu_custom_call(s) on the chip, "
          "want the ragged kernel" % n_calls)
    out = compiled(q, k, v, jax.device_put(lens, dev))
    run.on_device([out], "kernels output")
    out = np.asarray(out)
    check(np.isfinite(out).all(), "non-finite ragged decode output")
    d = W // heads
    q64, k64, v64 = (np.asarray(a.astype(jnp.float32)).astype(np.float64)
                     for a in (q, k if at is None else k[:, at],
                               v if at is None else v[:, at]))
    worst = 0.0
    for s in np.nonzero(lens)[0]:
        for h in range(heads):
            lanes = slice(h * d, (h + 1) * d)
            sc = np.einsum("gd,gtd->gt", q64[s, :, lanes],
                           k64[s, :, :lens[s], lanes]) * 0.125
            p = np.exp(sc - sc.max(-1, keepdims=True))
            ref = np.einsum("gt,gtd->gd", p / p.sum(-1, keepdims=True),
                            v64[s, :, :lens[s], lanes])
            worst = max(worst, float(np.abs(out[s, :, lanes] - ref).max()))
    check(worst < 2e-5, "ragged decode attention over %s leaves is %.3g "
          "away from float64" % (jnp.dtype(dtype).name, worst))
    return {"dtype": jnp.dtype(dtype).name, "slots": S, "rows": T,
            "stacked": stacked, "row_block": tb,
            "tpu_custom_calls": n_calls,
            "max_abs_err": round(worst, 8)}


def _delta_case(run):
    """The gated delta rule as the served model calls it (on the chip: the
    Mosaic kernels `gated_delta_step` and `gated_delta_chunk`) against the
    recurrence in NumPy float64: a prompt stopped at `valid_len`, then one
    step from the state it handed over."""
    import numpy as np
    import jax
    from incubator_mxnet_tpu.ops import linear_attention as la

    T, n, H, dk, dv, chunk, layers = (29, 22, 2, 8, 128, 8, 2) if run.dry \
        else (200, 150, 32, 128, 128, 64, 3)
    rs = np.random.RandomState(T)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(rs.randn(T, H, dk)) * dk ** -0.5, unit(rs.randn(T, H, dk))
    v = rs.randn(T, H, dv)
    g = -np.exp(rs.randn(T, H) * 0.5 - 3.0)
    beta = 1.0 / (1.0 + np.exp(-rs.randn(T, H)))
    dev = run.ctx().jax_device
    args = [jax.device_put(a.astype(np.float32), dev)
            for a in (q, k, v, g, beta)]
    run.on_device(args, "kernels input")
    chunked = jax.jit(lambda *a: la.gated_delta_chunked(
        *a, valid_len=n, chunk=chunk)).lower(*args).compile()
    # a step over the slots' state leaf, at its middle layer: each slot gets
    # the prompt's state and the token the prompt stopped before
    step = jax.jit(lambda q, k, v, g, b, s: la.gated_delta_step(
        q, k, v, g, b, s, layers // 2), donate_argnums=(5,))
    calls = [chunked.as_text().count("tpu_custom_call")]
    o, state = chunked(*args)
    slots = 4
    leaf = jax.numpy.zeros((slots, layers) + state.shape, state.dtype) \
        .at[:, layers // 2].set(state)
    at = [jax.numpy.broadcast_to(a[n - 1], (slots,) + a.shape[1:])
          for a in args]
    low = step.lower(*at, leaf).compile()
    calls.append(low.as_text().count("tpu_custom_call"))
    check(run.dry or calls == [1, 1],
          "the delta rule compiled %s tpu_custom_call(s) on the chip, want "
          "one kernel each for the prompt and the step" % calls)
    o_step, leaf = low(*at, leaf)
    run.on_device([o, state, leaf], "kernels output")
    S = np.zeros((H, dk, dv))
    want = np.zeros((n, H, dv))
    for t in range(n):                  # position n - 1 is the step's
        S = S * np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][:, :, None] * u[:, None, :]
        want[t] = np.einsum("hkv,hk->hv", S, q[t])
        if t == n - 2:
            errs = {"prompt_out": np.abs(np.asarray(o)[:n - 1]
                                         - want[:n - 1]).max(),
                    "prompt_state": np.abs(np.asarray(state) - S).max()}
    leaf = np.asarray(leaf)
    errs["step_out"] = np.abs(np.asarray(o_step) - want[n - 1]).max()
    errs["step_state"] = np.abs(leaf[:, layers // 2] - S).max()
    check(not leaf[:, [i for i in range(layers) if i != layers // 2]].any(),
          "the step wrote a layer of the state leaf it was not at")
    for name, e in errs.items():
        check(np.isfinite(e) and e < 2e-4, "gated delta rule: %s is %.3g "
              "away from float64" % (name, e))
    return {"positions": T, "valid_len": n, "heads": H, "chunk": chunk,
            "tpu_custom_calls": calls,
            "max_abs_err": {k_: round(float(e), 8) for k_, e in errs.items()}}


def _grouped_case(run):
    """`held_experts`' many-token form as the served models call it (on the
    chip: the Mosaic kernels `held_experts_grouped`, stacked weights read at
    a layer, and `held_experts_combine`) against the same sum in NumPy
    float64: uneven runs, one held expert that no token picks."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe

    T, D, F, E, k, held, tile, layers = (40, 128, 128, 8, 2, 4, 8, 2) \
        if run.dry else (600, 512, 256, 32, 4, 8, 64, 3)
    rs = np.random.RandomState(T)
    bf = jnp.bfloat16
    dev = run.ctx().jax_device
    put = lambda a, s: jax.device_put((a * s).astype(np.float32), dev) \
        .astype(bf)
    x = put(rs.randn(T, D), 1.0)
    wg, wu = (put(rs.randn(layers, held, F, D), D ** -0.5) for _ in range(2))
    wd = put(rs.randn(layers, held, D, F), F ** -0.5)
    scores = rs.randn(T, E)
    scores[:, :held] += np.linspace(1.0, -1.0, held)    # uneven runs
    scores[:, 3] = -9.0                                 # nobody picks 3
    gate, expert = moe.topk_route(jax.device_put(
        scores.astype(np.float32), dev), k)
    layer = layers - 1
    args = (x, gate, expert, wg, wu, wd)
    run.on_device(list(args), "kernels input")
    compiled = jax.jit(lambda *a: moe.held_experts(
        *a, 0, tile=tile, layer=jnp.int32(layer))).lower(*args).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    check(run.dry or n_calls == 2,
          "held_experts compiled %d tpu_custom_call(s) on the chip, want "
          "the grouped kernel and the combine" % n_calls)
    out = compiled(*args)
    run.on_device([out], "kernels output")
    out = np.asarray(out)
    check(np.isfinite(out).all(), "non-finite grouped expert output")
    f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(np.float64)
    x64, g64, e64 = f64(x), f64(gate), np.asarray(expert)
    want = np.zeros((T, D))
    runs = []
    for e in range(held):
        rows, col = np.nonzero(e64 == e)
        runs.append(len(rows))
        h = x64[rows] @ f64(wg[layer, e]).T
        a = h / (1.0 + np.exp(-h)) * (x64[rows] @ f64(wu[layer, e]).T)
        # the kernel rounds a to bfloat16 before the down projection and
        # its rows to bfloat16 after it: 2^-8 of each
        np.add.at(want, rows, g64[rows, col][:, None]
                  * (a @ f64(wd[layer, e]).T))
    check(runs[3] == 0 and max(runs) > 2 * min(r for r in runs if r),
          "the case's runs %s are not uneven with one empty" % runs)
    err = float(np.abs(out - want).max() / np.abs(want).max())
    check(err < 2e-2, "the grouped experts are %.3g of max|ref| away from "
          "float64" % err)
    return {"tokens": T, "held": held, "tile": tile, "runs": runs,
            "tpu_custom_calls": n_calls, "rel_err": round(err, 6)}


def _combine_case(run):
    """`held_experts_combine` at `laguna_xs2`'s widths (8192 tokens, 2048
    columns, 8 picks, one in eight held, as 32 of 256 experts give) against
    the same sum in NumPy float64, with NaN in every row that no held pick
    names: on the chip the Mosaic kernel, one DMA a held pick."""
    import numpy as np
    import jax
    from incubator_mxnet_tpu.parallel import moe

    T, D, k = (64, 128, 8) if run.dry else (8192, 2048, 8)
    N = 2 * T
    rs = np.random.RandomState(T + k)
    where = rs.randint(0, N, (T, k)).astype(np.int32)
    held = rs.rand(T, k) < 1.0 / 8
    held[0], held[1] = False, True                      # none and all k
    gate = rs.rand(T, k).astype(np.float32)
    rows = np.full((N, 1, D), np.nan, np.float32)
    named = np.unique(where[held])
    rows[named] = rs.randn(len(named), 1, D)
    dev = run.ctx().jax_device
    args = [jax.device_put(a, dev) for a in (rows, where, gate, held)]
    run.on_device(args, "kernels input")
    compiled = jax.jit(moe.held_experts_combine).lower(*args).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    check(run.dry or n_calls == 1, "held_experts_combine compiled %d "
          "tpu_custom_call(s) on the chip, want 1" % n_calls)
    out = compiled(*args)
    run.on_device([out], "kernels output")
    out = np.asarray(out)
    r64 = rows[:, 0].astype(np.float64)
    want = np.zeros((T, D))
    for i in range(k):
        m = held[:, i]
        want[m] += r64[where[m, i]] * gate[m, i, None]
    check(np.isfinite(out).all(), "the combine read a row no held pick "
          "names (non-finite output)")
    err = float(np.abs(out - want).max() / np.abs(want).max())
    check(err < 4e-6, "the combine is %.3g of max|ref| away from float64"
          % err)
    return {"tokens": T, "picks": k, "held_picks": int(held.sum()),
            "tpu_custom_calls": n_calls, "rel_err": err}


def _latent_case(run):
    """`latent_decode_attention` as `LatentAttention.step` calls it, over
    whole bfloat16 leaves at a layer that is not 0 (on the chip: the Mosaic
    kernel at the published widths, 128 heads of rank 512 + rope 64)
    against the same attention in NumPy float64."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    S, H, T, R, dr, layers = (5, 8, 1024, 128, 64, 2) if run.dry \
        else (12, 128, 3072, 512, 64, 3)
    rs = np.random.RandomState(S)
    dev = run.ctx().jax_device
    put = lambda *shape: jax.device_put(
        rs.randn(*shape).astype(np.float32), dev).astype(jnp.bfloat16)
    qa, qr, ckv, kr = put(S, H, R), put(S, H, dr), put(S, layers, T, R), \
        put(S, layers, T, dr)
    tb = att.latent_row_block(T, jnp.bfloat16)
    lens = rs.randint(1, T + 1, (S,)).astype(np.int32)
    lens[:5] = [1, tb, tb + 1, T, 37]
    run.on_device([qa, qr, ckv, kr], "kernels input")
    args = (qa, qr, ckv, kr, jnp.int32(1), jax.device_put(lens, dev))
    compiled = jax.jit(lambda *a: att.latent_decode_attention(
        *a, 0.1147)).lower(*args).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    check(run.dry or n_calls == 1,
          "latent_decode_attention compiled %d tpu_custom_call(s) on the "
          "chip, want the kernel" % n_calls)
    out = compiled(*args)
    run.on_device([out], "kernels output")
    out = np.asarray(out)
    check(np.isfinite(out).all(), "non-finite latent decode output")
    f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(np.float64)
    qa, qr, ckv, kr = f64(qa), f64(qr), f64(ckv[:, 1]), f64(kr[:, 1])
    worst = 0.0
    for s, n in enumerate(lens):
        sc = (qa[s] @ ckv[s, :n].T + qr[s] @ kr[s, :n].T) * 0.1147
        p = np.exp(sc - sc.max(-1, keepdims=True))
        ref = (p / p.sum(-1, keepdims=True)) @ ckv[s, :n]
        worst = max(worst, float(np.abs(out[s] - ref).max()
                                 / np.abs(ref).max()))
    # the probabilities are rounded to bfloat16 for the context
    check(worst < 1e-2, "latent decode attention is %.3g (relative) away "
          "from float64" % worst)
    return {"slots": S, "rows": T, "row_block": tb,
            "rows_read": int(np.asarray(att.latent_rows_read(
                jnp.asarray(lens), jax.ShapeDtypeStruct(
                    (S, layers, T, R), jnp.bfloat16))).sum()),
            "rows_needed": int(lens.sum()),
            "tpu_custom_calls": n_calls, "rel_err": round(worst, 6)}


def _grouped_attention_case(run):
    """`grouped_decode_attention` as `KindAttention.step` calls it, over
    whole bfloat16 leaves at a layer that is not 0 (on the chip: the Mosaic
    kernel at `laguna_xs2`'s published widths, 48 query heads over 8
    key/value heads of 128 in 9216-row full layers, 64 over 8 in 512-row
    rings) against the same attention in NumPy float64."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    kinds = ((("full", 5, 12, 2, 1024, 3), ("ring", 5, 16, 2, 512, 6))
             if run.dry else (("full", 8, 48, 8, 9216, 3),
                              ("ring", 8, 64, 8, 512, 6)))
    dev = run.ctx().jax_device
    cases = []
    for kind, S, H, G, T, layers in kinds:
        rs = np.random.RandomState(T)
        put = lambda *shape: jax.device_put(
            rs.randn(*shape).astype(np.float32), dev).astype(jnp.bfloat16)
        q, k, v = put(S, H, 128), put(S, layers, G, T, 128), \
            put(S, layers, G, T, 128)
        tb = att.grouped_row_block(T)
        lens = rs.randint(1, T + 1, (S,)).astype(np.int32)
        lens[:5] = [0, 1, tb, tb + 1, T]
        run.on_device([q, k, v], "kernels input")
        args = (q, k, v, jnp.int32(1), jax.device_put(lens, dev))
        compiled = jax.jit(lambda *a: att.grouped_decode_attention(
            *a, 128 ** -0.5)).lower(*args).compile()
        n_calls = compiled.as_text().count("tpu_custom_call")
        check(run.dry or n_calls == 1,
              "grouped_decode_attention compiled %d tpu_custom_call(s) on "
              "the chip, want the kernel" % n_calls)
        out = compiled(*args)
        run.on_device([out], "kernels output")
        out = np.asarray(out)
        check(np.isfinite(out).all(), "non-finite grouped decode output")
        f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(np.float64)
        q, k, v = f64(q), f64(k[:, 1]), f64(v[:, 1])
        worst = 0.0
        for s, n in enumerate(lens):
            for i in range(H if n else 0):
                g = i // (H // G)
                sc = k[s, g, :n] @ q[s, i] * 128 ** -0.5
                p = np.exp(sc - sc.max())
                ref = (p / p.sum()) @ v[s, g, :n]
                worst = max(worst, float(np.abs(out[s, i] - ref).max()
                                         / np.abs(ref).max()))
        # the probabilities are rounded to bfloat16 for the context
        check(worst < 1e-2, "grouped decode attention (%s) is %.3g "
              "(relative) away from float64" % (kind, worst))
        cases.append({"kind": kind, "slots": S, "heads": H, "rows": T,
                      "row_block": tb, "tpu_custom_calls": n_calls,
                      "rel_err": round(worst, 6)})
    return cases


def _latent_prefill_case(run):
    """`latent_prefill_attention` as `LatentAttention.prompt` calls it (on
    the chip: the Mosaic kernel at the published widths, 128 heads, keys of
    128 + the shared 64, values of 128, prompts of 2048 and 1536 positions)
    against causal attention in NumPy float64, every head."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    H, prompts = (4, (1024, 256)) if run.dry else (128, (2048, 1536))
    dev = run.ctx().jax_device
    cases = []
    for T in prompts:
        rs = np.random.RandomState(T)
        put = lambda *shape: jax.device_put(
            rs.randn(*shape).astype(np.float32), dev).astype(jnp.bfloat16)
        args = (put(T, H * 128), put(H, T, 64), put(T, H * 128), put(T, 64),
                put(T, H * 128))
        run.on_device(list(args), "kernels input")
        compiled = jax.jit(lambda *a: att.latent_prefill_attention(
            *a, 0.1147)).lower(*args).compile()
        n_calls = compiled.as_text().count("tpu_custom_call")
        check(run.dry or n_calls == 1,
              "latent_prefill_attention compiled %d tpu_custom_call(s) on "
              "the chip, want the kernel" % n_calls)
        out = compiled(*args)
        run.on_device([out], "kernels output")
        out = np.asarray(out.astype(jnp.float32)).reshape(T, H, 128)
        check(np.isfinite(out).all(), "non-finite latent prefill output")
        f64 = lambda a: np.asarray(a.astype(jnp.float32)).astype(np.float64)
        qn, qr, kn, kr, v = (f64(a) for a in args)
        qn, kn, v = (a.reshape(T, H, 128) for a in (qn, kn, v))
        causal = np.tril(np.ones((T, T), bool))
        worst = 0.0
        for h in range(H):
            sc = np.where(causal, (qn[:, h] @ kn[:, h].T + qr[h] @ kr.T)
                          * 0.1147, -np.inf)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            ref = (p / p.sum(-1, keepdims=True)) @ v[:, h]
            worst = max(worst, float(np.abs(out[:, h] - ref).max()
                                     / np.abs(ref).max()))
        # the probabilities and the result are rounded to bfloat16
        check(worst < 1e-2, "latent prefill attention is %.3g (relative) "
              "away from float64 at %d positions" % (worst, T))
        cases.append({"positions": T, "heads": H,
                      "block": att.latent_prefill_block(T),
                      "tpu_custom_calls": n_calls,
                      "rel_err": round(worst, 6)})
    return cases


def phase_kernels(run):
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as att

    check(att._interpret() == run.dry,
          "MXNET_PALLAS_INTERPRET must be off on the chip (and on in a "
          "dry run)")
    with _forced_pallas():
        cases = [_kernel_case(run, *case)
                 for case in run.sizes["kernels"]]
    return {"compile_s": round(sum(c["compile_s"] for c in cases), 2),
            "cases": cases,
            "ragged_decode": [_ragged_case(run, dt)
                              for dt in (jnp.float32, jnp.bfloat16)]
            + [_ragged_case(run, jnp.bfloat16, stacked=True)],
            "gated_delta": _delta_case(run),
            "grouped_experts": _grouped_case(run),
            "combine_experts": _combine_case(run),
            "latent_decode": _latent_case(run),
            "latent_prefill": _latent_prefill_case(run),
            "grouped_decode": _grouped_attention_case(run)}


# --------------------------------------------------------------------------
# 3/4. the imperative Gluon train loop: record / backward / Trainer.step
# --------------------------------------------------------------------------

def _gluon_train(run, net, loss_block, params, optimizer, opt_args,
                 inputs, label, batch, warm, steps):
    """`warm + steps` steps of the user-facing loop on one repeated
    batch.  Each step ends with a device->host read of its loss, after
    `block_until_ready` on every parameter the step rebound: if the
    read still has to wait, block_until_ready returned early."""
    import numpy as np
    import jax
    from incubator_mxnet_tpu import autograd as ag, gluon

    trainer = gluon.Trainer(params, optimizer, opt_args)
    trainable = [p for p in params.values() if p.grad_req != "null"]
    probe = before = None
    losses, walls, fences = [], [], []
    for _ in range(warm + steps):
        t0 = time.perf_counter()
        with ag.record():
            l = loss_block(net(*inputs), label)
            l.backward()
        trainer.step(batch)
        jax.block_until_ready([p.data()._data for p in trainable])
        t1 = time.perf_counter()
        losses.append(float(l.asnumpy().astype(np.float64).mean()))
        t2 = time.perf_counter()
        walls.append(round(t2 - t0, 5))
        fences.append(round(t2 - t1, 5))
        if probe is None:
            # shapes are deferred until the first forward.  The eight
            # smallest parameters: a bf16 LayerNorm gamma of 1.0 does
            # not move under adam at lr 1e-4, a zero bias does
            probe = sorted(trainable,
                           key=lambda p: int(np.prod(p.shape)))[:8]
            before = [p.data().asnumpy().copy() for p in probe]
    check(all(math.isfinite(x) for x in losses),
          "non-finite loss: %s" % losses)
    check(any(not np.array_equal(b, p.data().asnumpy())
              for b, p in zip(before, probe)),
          "no parameter among %s changed" % [p.name for p in probe])
    run.on_device([p.data()._data for p in params.values()], "parameter")
    run.on_device([l._data] + [a._data for a in inputs] + [label._data],
                  "train step array")
    return {"compile_s": walls[0], "warm_step_s": walls[1:warm],
            "step_s": walls[warm:],
            # smoke observation: seconds the loss read still waited
            # AFTER block_until_ready on the rebound parameters
            "read_after_block_s": fences[warm:],
            "loss": [round(x, 5) for x in losses]}


def phase_train_resnet50(run):
    import numpy as np
    from incubator_mxnet_tpu import gluon, nd

    sz = run.sizes["resnet"]
    ctx = run.ctx()
    net = sz["build"]()
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    rs = np.random.RandomState(0)
    b, side = sz["batch"], sz["side"]
    x = nd.array(rs.randn(b, 3, side, side).astype(np.float32), ctx=ctx,
                 dtype="bfloat16")
    y = nd.array(rs.randint(0, sz["classes"], b).astype(np.float32),
                 ctx=ctx)
    out = _gluon_train(run, net, loss_fn, net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9,
                        "wd": 1e-4}, (x,), y, b, warm=2, steps=5)
    return {"model": sz["model"], "batch": b, **out}


def phase_train_bert_base(run):
    import numpy as np
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models.transformer import FusedMLMCELoss
    from incubator_mxnet_tpu.telemetry import costs

    sz = run.sizes["bert"]
    ctx = run.ctx()
    with _forced_pallas():
        net = sz["build"]()
        net.initialize(ctx=ctx)
        net.cast("bfloat16")
        net.hybridize(static_alloc=True, static_shape=True)
        loss_b = FusedMLMCELoss(sz["vocab"], sz["units"])
        loss_b.initialize(ctx=ctx)
        loss_b.cast("bfloat16")
        loss_b.hybridize()
        rs = np.random.RandomState(0)
        b, seq = sz["batch"], sz["seq"]
        tokens = nd.array(rs.randint(0, sz["vocab"], (b, seq))
                          .astype(np.int32), ctx=ctx, dtype="int32")
        labels = nd.array(rs.randint(0, sz["vocab"], (b, seq))
                          .astype(np.float32), ctx=ctx)
        out = _gluon_train(
            run, net, loss_b,
            {**net.collect_params(), **loss_b.collect_params()},
            "adam", {"learning_rate": 1e-4}, (tokens,), labels, b,
            warm=2, steps=3)
        check(out["loss"][-1] < out["loss"][0],
              "BERT loss did not fall on a repeated batch: %s"
              % out["loss"])
        # the compiled step itself: a second compile of the same
        # lowering, which the persistent cache should answer
        lows = costs.lowerings("gluon.train_step")
        check(lows, "no gluon.train_step executable in the cost registry")
        t0 = time.perf_counter()
        n_calls = sum(low.compile().as_text().count("tpu_custom_call")
                      for low in lows)
        recompile_s = time.perf_counter() - t0
    check(run.dry or n_calls >= 3,
          "the compiled BERT step holds %d tpu_custom_call(s): the "
          "forced Pallas forward/dq/dkv kernels are not in it" % n_calls)
    return {"model": sz["model"], "batch": b, "seq": seq,
            "tpu_custom_calls": n_calls,
            "audit_recompile_s": round(recompile_s, 2), **out}


# --------------------------------------------------------------------------
# 5. the generation server
# --------------------------------------------------------------------------

def phase_serve_nmt(run):
    import numpy as np
    import jax
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.serving import GenerationEngine

    sz = run.sizes["nmt"]
    ctx = run.ctx()
    bos, eos, vocab = 1, 2, sz["vocab"]
    net = sz["build"]()
    net.initialize(ctx=ctx)
    rs = np.random.RandomState(0)

    eng = GenerationEngine(net, bos=bos, eos=eos, ctx=ctx,
                           slots=sz["slots"], max_len=sz["max_len"],
                           prompt_buckets=sz["buckets"])
    try:
        # (deferred shapes: the engine's priming forward made them)
        run.on_device([p.data()._data
                       for p in net.collect_params().values()],
                      "NMT parameter")
        t0 = time.perf_counter()
        warm = eng.warmup()
        compile_s = time.perf_counter() - t0
        traces0 = events.get("serve.traces")
        want = "cpu:" if run.dry else "tpu:"
        check(all(d.startswith(want) for d in warm["kv_cache"]["devices"]),
              "KV cache leaves sit on %s" % warm["kv_cache"]["devices"])

        # an on-bucket prompt first: its first token is checked against
        # the teacher-forced forward of the same block
        lo, hi = sz["prompt"]
        lens = [sz["buckets"][0]] + [int(n) for n in rs.randint(
            lo, hi + 1, sz["requests"] - 1)]
        prompts = [rs.randint(3, vocab, (n,)).astype(np.int32)
                   for n in lens]
        news = [int(n) for n in rs.randint(sz["new"][0], sz["new"][1] + 1,
                                           len(prompts))]
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, news)]
        results = [np.asarray(s.result(timeout=300)) for s in streams]
        serve_s = time.perf_counter() - t0
        for r, n in zip(results, news):
            check(1 <= r.size <= n, "stream of %d tokens, budget %d"
                  % (r.size, n))
            check(((r >= 0) & (r < vocab)).all(),
                  "token outside the vocabulary: %s" % r)
            check(r.size == n or r[-1] == eos,
                  "stream stopped short without EOS")
        check(events.get("serve.traces") == traces0,
              "serve.traces moved after warm-up: %d -> %d"
              % (traces0, events.get("serve.traces")))
        st = eng.stats()
        # the engine probes its donated cache on the first decode step
        check(st["steps"] > 0, "no decode step ran")
        check(run.dry or events.get("gen.donation_copy") == 0,
              "the decode step COPIED its donated KV cache")

        pure = parallel.functionalize(net)
        src = jax.device_put(prompts[0][None], ctx.jax_device)
        tgt = jax.device_put(np.full((1, 1), bos, np.int32),
                             ctx.jax_device)
        logits, _ = jax.jit(pure)(
            parallel.extract_params(net), src, tgt,
            rng_bits=jax.random.key_data(jax.random.PRNGKey(0)))
        ref = np.asarray(logits, np.float32)[0, 0]
        got = int(results[0][0])
        # random weights make near-ties: the engine's token must be
        # within 2% of the logit range of the reference's best
        check(ref[got] >= ref.max() - 0.02 * (ref.max() - ref.min()),
              "engine's first token %d (logit %.4f) is not the "
              "reference's argmax %d (logit %.4f)"
              % (got, ref[got], int(ref.argmax()), ref.max()))
        tokens = int(sum(r.size for r in results))
    finally:
        eng.close()
    return {"model": sz["model"], "slots": sz["slots"],
            "max_len": sz["max_len"], "prompt_buckets": list(sz["buckets"]),
            "compile_s": round(compile_s, 2),
            "warmup_bucket_s": warm["bucket_wall_s"],
            "kv_cache_bytes": warm["kv_cache"]["total"],
            "kv_cache_devices": warm["kv_cache"]["devices"],
            "requests": len(results), "prompt_lens": lens,
            "tokens_out": tokens, "decode_steps": st["steps"],
            "serve_wall_s": round(serve_s, 3),
            "traces_after_warmup": events.get("serve.traces") - traces0,
            "donation_copies": events.get("gen.donation_copy"),
            "first_token_matches_reference": True}


# --------------------------------------------------------------------------
# 6. four chips, one process: ShardedTrainer over a data mesh
# --------------------------------------------------------------------------

def phase_multichip(run):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.contrib import amp

    n = 4
    sz = run.sizes["multichip"]
    mesh = parallel.make_mesh((n,), ("data",), devices=jax.devices()[:n])
    rs = np.random.RandomState(0)
    b, side = sz["batch"], sz["side"]
    x = jax.device_put(
        rs.randn(b, 3, side, side).astype(np.float32),
        parallel.batch_sharded(mesh)).astype(jnp.bfloat16)
    y = jax.device_put(rs.randint(0, sz["classes"], b),
                       parallel.batch_sharded(mesh))
    shard_devs = {s.device for s in x.addressable_shards}
    check(len(x.addressable_shards) == n and len(shard_devs) == n,
          "batch has %d shards on %d devices, want %d on %d"
          % (len(x.addressable_shards), len(shard_devs), n, n))
    run.on_device([x, y], "multichip batch")

    out = {}
    try:
        for zero, collective in ((0, "all-reduce"),
                                 (2, "reduce-scatter")):
            net = sz["build"]()
            net.initialize(ctx=run.ctx())
            net(nd.array(np.zeros((2, 3, side, side), np.float32),
                         ctx=run.ctx()))
            trainer = parallel.ShardedTrainer(
                net, optimizer="sgd", lr=0.1, momentum=0.9, wd=1e-4,
                mesh=mesh, zero=zero, amp="bfloat16")
            try:
                losses, walls = [], []
                for _ in range(2 + 3):
                    t0 = time.perf_counter()
                    loss = trainer.step(x, y)
                    losses.append(float(np.asarray(loss)))
                    walls.append(round(time.perf_counter() - t0, 5))
                check(all(math.isfinite(v) for v in losses),
                      "zero=%d: non-finite loss %s" % (zero, losses))
                leaves = jax.tree_util.tree_leaves(
                    (trainer.params, trainer.opt_state))
                run.on_device(leaves, "zero=%d trainer state" % zero)
                for a in leaves:
                    check(len({s.device for s in a.addressable_shards})
                          == n, "zero=%d: a state leaf %s lives on fewer "
                          "than %d devices" % (zero, a.shape, n))
                mem = run.memory()[:n]
                check(run.dry or all(m["bytes_in_use"] > (32 << 20)
                                     for m in mem),
                      "zero=%d: bytes_in_use per device %s — something "
                      "sits on device 0 only" % (zero, mem))
                # the step's own lowering, with its real shardings (a
                # second compile: the persistent cache answers it)
                t0 = time.perf_counter()
                found = trainer.lower_step(x, y).compile().as_text() \
                    .count(collective)
                recompile_s = time.perf_counter() - t0
                check(found >= 1, "zero=%d step compiled without %s"
                      % (zero, collective))
                out["zero%d" % zero] = {
                    "compile_s": walls[0], "step_s": walls[2:],
                    "loss": [round(v, 5) for v in losses],
                    "collectives": {collective: found},
                    "audit_recompile_s": round(recompile_s, 2),
                    "bytes_in_use": [m["bytes_in_use"] for m in mem]}
            finally:
                trainer.release()
            del net, trainer, leaves
    finally:
        amp.turn_off()
    return {"model": sz["model"], "batch": b, "mesh": {"data": n},
            "shard_devices": sorted(str(d) for d in shard_devs),
            "compile_s": round(sum(v["compile_s"]
                                   for v in out.values()), 2), **out}


# --------------------------------------------------------------------------

_BODIES = {"env": phase_env, "kernels": phase_kernels,
           "train_resnet50": phase_train_resnet50,
           "train_bert_base": phase_train_bert_base,
           "serve_nmt": phase_serve_nmt, "multichip": phase_multichip}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny shapes on mx.cpu() with Pallas in "
                         "interpret mode: debugs this script, proves "
                         "nothing about the chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, for debugging one "
                         "phase on the chip (default: all)")
    args = ap.parse_args(argv)
    wanted = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(wanted) - set(PHASES))
    if unknown:
        ap.error("unknown phase(s) %s; choose from %s"
                 % (unknown, ", ".join(PHASES)))
    if "env" not in wanted:
        wanted.insert(0, "env")

    if args.cpu_dry_run:
        # before jax initialises: the CPU backend, four virtual devices
        # for the multichip phase, Pallas through the interpreter
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["MXNET_PALLAS_INTERPRET"] = "1"
        if "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
                " --xla_force_host_platform_device_count=4"

    import jax
    run = Run(dry=args.cpu_dry_run)
    try:
        for name in PHASES:
            if name not in wanted:
                continue
            if name == "multichip" and jax.device_count() < 4:
                print(json.dumps({"phase": name, "skipped": True,
                                  "reason": "needs 4 devices, found %d"
                                  % jax.device_count()}), flush=True)
                continue
            run.phase(name, _BODIES[name])
    except BaseException:
        # a result is printed only where an accelerator was found; the
        # failure itself still ends the script below, nothing carries on
        if not run.dry and "env" in run.ran:
            print(json.dumps({"ok": False, "device": run.device}),
                  flush=True)
        raise
    ran = {"phase": "summary", "smoke": True, "phases": run.ran}
    if args.cpu_dry_run:
        ran["dry_run"] = True
    if set(wanted) != set(PHASES):
        ran["partial"] = True
    print(json.dumps(ran), flush=True)
    # the result line: these keys and no others
    print(json.dumps({"ok": True, "device": run.device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print("chip_smoke: FAILED: %s" % e, file=sys.stderr)
        sys.exit(1)
