"""Fused (flash) multi-head self-attention.

TPU-native replacement for the reference's fused attention contrib ops
(ref: src/operator/contrib/transformer.cc `interleaved_matmul_selfatt_qk`
/ `_valatt`, which exist to keep the score matmul inside one kernel).
Here the whole softmax(QK^T)V is ONE Pallas kernel using the online-
softmax (flash) recurrence, so the T×T score matrix never hits HBM:

  grid = (batch*heads, T/bq, T/bk), k-dimension innermost ("arbitrary"),
  VMEM scratch carries (m, l, acc) across k blocks; outputs are written
  on the last k step.  Forward also emits the log-sum-exp row statistics
  so the backward pass can rebuild P = exp(S - lse) block-free in XLA
  (one fused executable).

MXNET_USE_PALLAS=1 (auto) takes the plain jnp einsum-softmax path
(identical math) when not on a TPU backend, when shapes don't tile
(T % block != 0), or below MXNET_FLASH_AUTO_BYTES; =0 always does.
A FORCED kernel (MXNET_USE_PALLAS=2, MXNET_FLASH_BWD_PALLAS=2) that
cannot be used raises with the reason — it never gives way to the
reference silently.  MXNET_PALLAS_INTERPRET=1 runs the Pallas kernel in
interpreter mode so the CPU test suite exercises the real kernel.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError
from ..monitor import events
from ..telemetry import costs as _costs
from .registry import register

__all__ = ["flash_attention", "naive_attention", "index_scores",
           "select_mask", "masked_decode_attention",
           "blocked_select_attention", "blocked_causal_attention",
           "blocked_window_attention", "latent_prefill_attention",
           "latent_prefill_block",
           "latent_decode_attention", "latent_rows_read", "latent_row_block",
           "grouped_decode_attention", "grouped_rows_read",
           "grouped_row_block",
           "decode_attention", "live_rows_plan", "live_rows_write",
           "ragged_decode_attention", "dense_decode_attention",
           "decode_rows_read", "ragged_row_block", "decode_rows_write"]

_NEG_INF = -1e30


def _largest_divisor(T, cap, tile=8):
    """Largest divisor of T that is ≤ cap and a multiple of `tile` (8: the
    TPU sublanes of float32), or T itself if T ≤ cap; 0 if there is none."""
    if T <= cap:
        return T
    for b in range(cap, tile - 1, -1):
        if T % b == 0 and b % tile == 0:
            return b
    return 0


def _block_sizes(T):
    """Fewer+bigger blocks (caps chosen on an earlier setup; not
    re-measured on this chip).  Defaults keep the f32 score block
    ≤ 8 MB of VMEM."""
    from .. import config as _cfg
    bq = int(_cfg.get("MXNET_FLASH_BLOCK_Q")) \
        or _largest_divisor(T, 1024)
    bk = int(_cfg.get("MXNET_FLASH_BLOCK_K")) \
        or _largest_divisor(T, max(128, (2 * 1024 * 1024) // max(bq, 1)))
    return min(bq, T), min(bk, T)


def _interpret():
    from .. import config as _cfg
    return bool(_cfg.get("MXNET_PALLAS_INTERPRET"))


def _kernel_form(fits):
    """What `_kernel_or_reference` runs, as a static value: "reference"
    where the kernel does not take the shapes (`fits` false), "interpret"
    where `MXNET_PALLAS_INTERPRET` runs the kernel itself, "kernel"
    otherwise."""
    if not fits:
        return "reference"
    return "interpret" if _interpret() else "kernel"


def _kernel_or_reference(kernel, reference, args, fits, counters=()):
    """The one rule that picks a Pallas kernel: `reference(*args)` where the
    kernel does not take the shapes (`fits` false, in every mode: a fits
    test that lets the interpreter take more says so itself); the kernel
    itself in interpret mode; otherwise the choice is made where the caller
    is LOWERED (`lax.platform_dependent`): the kernel for a TPU, the
    reference (the `jax.numpy` form, what the CPU runs and the tests'
    oracle) elsewhere, so a compile for a described TPU sees the kernel.
    Each counter in `counters` counts once a trace that holds the kernel
    (interpret mode, or a TPU backend): a trace-time side effect, as
    `serve.traces` is."""
    form = _kernel_form(fits)
    if form == "reference":
        return reference(*args)
    if form == "interpret" or jax.default_backend() == "tpu":
        for name in counters:
            events.incr(name)
    if form == "interpret":
        return kernel(*args)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=reference)


def _tiles(T, bq, bk):
    return bool(bq and bk and T % bq == 0 and T % bk == 0
                and (bq % 8 == 0 or bq == T) and (bk % 8 == 0 or bk == T))


def _unusable(T, d, blocks):
    """Why the Pallas kernels cannot take this shape here, or None."""
    bq, bk = blocks(T)
    if not _tiles(T, bq, bk):
        return "T=%d does not tile into blocks (%d, %d)" % (T, bq, bk)
    if _interpret():
        return None
    if jax.default_backend() != "tpu":
        return ("the backend is %r, not 'tpu', and "
                "MXNET_PALLAS_INTERPRET is off" % jax.default_backend())
    if d > 256:
        return "head dim %d > 256" % d
    return None


def _pallas_enabled(BH, T, d):
    """Dispatch policy (thresholds chosen on an earlier setup; not
    re-measured on this chip): the one-fused-XLA-program path wins at
    short T, but its B·H·T·T f32 score matrix stops compiling well
    before T=8192; the Pallas kernel streams k/v blocks through VMEM
    and keeps working.  MXNET_USE_PALLAS: 0=never, 1=auto (score bytes
    > MXNET_FLASH_AUTO_BYTES), 2=always (raises where it cannot)."""
    from .. import config as _cfg
    mode = _cfg.get("MXNET_USE_PALLAS")
    if mode == "0":
        return False
    why = _unusable(T, d, _block_sizes)
    if mode == "2":
        if why:
            raise MXNetError("MXNET_USE_PALLAS=2 forces the flash "
                             "attention kernel, but " + why)
        return True
    if why:
        return False
    if _interpret():
        return True
    auto_bytes = float(_cfg.get("MXNET_FLASH_AUTO_BYTES"))
    return BH * T * T * 4.0 > auto_bytes


# ---------------------------------------------------------------------------
# naive (XLA) reference path — also the backward building block
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, scale, causal=False, bias=None):
    """softmax(q k^T * scale [+ bias]) v over (..., T, d) operands."""
    f32 = jnp.float32
    s = jnp.einsum("...qd,...kd->...qk", q.astype(f32), k.astype(f32))
    s = s * scale
    if bias is not None:
        s = s + bias.astype(f32)
    if causal:
        T, S = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                scale, causal, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_s[:] = jnp.full(m_s.shape, _NEG_INF, m_s.dtype)
        l_s[:] = jnp.zeros(l_s.shape, l_s.dtype)
        acc_s[:] = jnp.zeros(acc_s.shape, acc_s.dtype)

    # causal: skip k blocks strictly above the diagonal band
    should_run = (ik * bk <= iq * bq + (bq - 1)) if causal else (ik >= 0)

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_s[:, :1]                                    # (bq, 1)
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                 # (bq, bk) f32
        alpha = jnp.exp(m_prev - m_new)                        # (bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # (bq, d)
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0] = (acc_s[:] / l_s[:, :1]).astype(o_ref.dtype)
        # lse replicated across the 128 lanes (TPU tiling needs a full
        # lane-dim block; caller slices [..., 0])
        lse_ref[0] = m_s[:] + jnp.log(l_s[:])


def _flash_fwd(q, k, v, scale, causal):
    """q,k,v: (BH, T, d) → out (BH, T, d), lse (BH, T) f32."""
    BH, T, d = q.shape
    bq, bk = _block_sizes(T)
    grid = (BH, T // bq, T // bk)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), q.dtype),
            jax.ShapeDtypeStruct((BH, T, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v)
    # lse kept lane-replicated (BH, T, 128): the backward kernels read
    # it blockwise without a sublane↔lane transpose
    return out, lse


# ---------------------------------------------------------------------------
# Pallas flash kernels (backward): dq and dk/dv, block recompute from
# the lse residuals — no T×T slab in HBM (FlashAttention-2 schedule)
# ---------------------------------------------------------------------------

def _bwd_block_sizes(T):
    """Smaller slabs than forward: the backward keeps ~4 live (bq, bk)
    f32 intermediates (s, p, dp, ds) in VMEM (~16 MB/core).  Explicit
    MXNET_FLASH_BLOCK_Q/K overrides apply here too."""
    from .. import config as _cfg
    bq = int(_cfg.get("MXNET_FLASH_BLOCK_Q")) or _largest_divisor(T, 512)
    bk = int(_cfg.get("MXNET_FLASH_BLOCK_K")) or \
        _largest_divisor(T, max(128, (1024 * 1024) // max(bq, 1)))
    return min(bq, T), min(bk, T)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
               dq_s, dD_s, *, scale, causal, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    f32 = jnp.float32

    @pl.when(ik == 0)
    def _init():
        dq_s[:] = jnp.zeros(dq_s.shape, f32)
        do32 = do_ref[0].astype(f32)
        o32 = o_ref[0].astype(f32)
        dD_s[:] = jnp.broadcast_to(
            jnp.sum(do32 * o32, axis=-1, keepdims=True), dD_s.shape)

    should_run = (ik * bk <= iq * bq + (bq - 1)) if causal else (ik >= 0)

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale                # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                     # (bq, bk)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                        # (bq, bk)
        ds = p * (dp - dD_s[:, :1]) * scale
        dq_s[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                        # (bq, d)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dk_ref,
                dv_ref, dk_s, dv_s, *, scale, causal, bq, bk):
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    f32 = jnp.float32

    @pl.when(iq == 0)
    def _init():
        dk_s[:] = jnp.zeros(dk_s.shape, f32)
        dv_s[:] = jnp.zeros(dv_s.shape, f32)

    # causal: q blocks entirely above the diagonal contribute nothing
    should_run = (iq * bq + (bq - 1) >= jk * bk) if causal else (iq >= 0)

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale                # (bq, bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 0)
            kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                      (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])                     # (bq, bk)
        # dv += p^T do — contraction over the q (sublane) dim
        dv_s[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)                        # (bk, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                        # (bq, bk)
        D = jnp.sum(do.astype(f32) * o_ref[0].astype(f32), axis=-1,
                    keepdims=True)                             # (bq, 1)
        ds = p * (dp - D) * scale
        dk_s[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)                        # (bk, d)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, do, scale, causal):
    """dq/dk/dv via two Pallas kernels (dq: k-inner; dkv: q-inner)."""
    BH, T, d = q.shape
    bq, bk = _bwd_block_sizes(T)
    interp = _interpret()

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(BH, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(q, k, v, do, out, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(BH, T // bk, T // bq),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, d), k.dtype),
            jax.ShapeDtypeStruct((BH, T, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interp,
    )(k, v, q, do, out, lse)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP: pallas forward, fused-XLA backward from lse residuals
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, scale, causal):
    out, _ = _flash_fwd(q, k, v, scale, causal)
    return out


def _flash_attention_fwd(q, k, v, scale, causal):
    out, lse = _flash_fwd(q, k, v, scale, causal)
    # persist only the (BH, T) column — XLA DCEs the replicated lanes;
    # the backward re-broadcasts transiently for the kernels
    return out, (q, k, v, out, lse[..., 0])


def _flash_attention_bwd(scale, causal, res, do):
    """Backward from the saved lse row statistics: P = exp(S - lse)
    rebuilt blockwise.  The Pallas dq/dkv kernel pair has O(T·d) HBM
    traffic like the forward, which is what makes seq-4k/8k training
    fit.  MXNET_FLASH_BWD_PALLAS: 0 = a fused-XLA `lax.scan` whose live
    score slab is bounded by MXNET_FLASH_BWD_BYTES; 1 = the kernels
    once the score slab outgrows that bound (threshold chosen on an
    earlier setup; not re-measured on this chip); 2 = the kernels
    always (raises where they cannot run)."""
    q, k, v, out, lse = res
    from .. import config as _cfg
    BH, T, d = q.shape
    mode = _cfg.get("MXNET_FLASH_BWD_PALLAS")
    if mode != "0":
        why = _unusable(T, d, _bwd_block_sizes)
        if mode == "2" and why:
            raise MXNetError("MXNET_FLASH_BWD_PALLAS=2 forces the flash "
                             "attention backward kernels, but " + why)
        want = (mode == "2" or
                BH * T * T * 4.0 >
                float(_cfg.get("MXNET_FLASH_BWD_BYTES")))
        if want and not why:
            lse128 = jnp.broadcast_to(lse[..., None], (BH, T, 128))
            return _flash_bwd_pallas(q, k, v, out, lse128, do,
                                     scale, causal)
    f32 = jnp.float32
    qf, kf, vf, dof = (t.astype(f32) for t in (q, k, v, do))
    D = jnp.sum(dof * out.astype(f32), axis=-1, keepdims=True)  # (BH, T, 1)

    limit = float(_cfg.get("MXNET_FLASH_BWD_BYTES"))
    bk = T
    while BH * T * bk * 4.0 > limit and bk % 2 == 0:
        bk //= 2
    nk = T // bk

    def block_grads(kb, vb, k0):
        s = jnp.einsum("bqd,bkd->bqk", qf, kb) * scale
        if causal:
            qpos = jnp.arange(T)[:, None]
            kpos = k0 + jnp.arange(bk)[None, :]
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                         # (BH, T, bk)
        dvb = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vb)
        ds = p * (dp - D) * scale
        dq_part = jnp.einsum("bqk,bkd->bqd", ds, kb)
        dkb = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_part, dkb, dvb

    if nk == 1:
        dq, dk, dv = block_grads(kf, vf, 0)
    else:
        def body(dq, ik):
            k0 = ik * bk
            kb = jax.lax.dynamic_slice_in_dim(kf, k0, bk, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vf, k0, bk, axis=1)
            dq_part, dkb, dvb = block_grads(kb, vb, k0)
            return dq + dq_part, (dkb, dvb)

        dq, (dks, dvs) = jax.lax.scan(body, jnp.zeros_like(qf),
                                      jnp.arange(nk))
        dk = dks.transpose(1, 0, 2, 3).reshape(BH, T, d)
        dv = dvs.transpose(1, 0, 2, 3).reshape(BH, T, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q, k, v, scale=None, causal=False, bias=None):
    """Fused attention over (B, H, T, d) operands (any leading batch dims
    folded by the caller).  Returns (B, H, T, d)."""
    with _costs.part("attn"):
        *lead, T, d = q.shape
        if scale is None:
            scale = 1.0 / math.sqrt(d)
        BH = 1
        for n in lead:
            BH *= n
        if bias is None and _pallas_enabled(BH, T, d):
            q3 = q.reshape(BH, T, d)
            k3 = k.reshape(BH, T, d)
            v3 = v.reshape(BH, T, d)
            out = _flash_attention(q3, k3, v3, float(scale), bool(causal))
            return out.reshape(*lead, T, d)
        return naive_attention(q, k, v, scale, causal=causal, bias=bias)


# ---------------------------------------------------------------------------
# registry entry: (B, T, C) projected q/k/v, heads handled inside
# ---------------------------------------------------------------------------

@register("_contrib_flash_attention",
          ndarray_inputs=("query", "key", "value"))
def _contrib_flash_attention(query, key, value, num_heads=1, scale=None,
                             causal=False):
    """Fused multi-head attention core: softmax(QK^T/sqrt(d))V.

    query/key/value: (B, T, C) post-projection activations; C = H*d.
    Returns (B, T, C).  Pallas flash kernel on TPU, fused XLA fallback
    elsewhere (ref: contrib interleaved_matmul_* fused attention ops,
    src/operator/contrib/transformer.cc).
    """
    B, T, C = query.shape
    H = int(num_heads)
    d = C // H

    def split(x):
        return x.reshape(B, T, H, d).transpose(0, 2, 1, 3)

    out = flash_attention(split(query), split(key), split(value),
                          scale=scale, causal=causal)
    return out.transpose(0, 2, 1, 3).reshape(B, T, C)


# ---------------------------------------------------------------------------
# select-then-attend: attention that reads a learned top-k of its cache
# ---------------------------------------------------------------------------
# An indexer scores every cached position for each query,
#   I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s]),
# the k best positions are kept (exactly: ties go to the lower position,
# no approx_max_k), and softmax attention runs over those alone.
#
# The selection is kept as a MASK over the positions (`select_mask`: the
# k-th largest score of a row by bisection on the float's bits, counting
# passes and no sort), and attention runs over the cache under it:
#
# - decode, one query a slot: `masked_decode_attention` over the slot's
#   rows, head-major.  Measured on a v5e (PERF.md, PR 29): gathering the k
#   selected rows instead moves one row a descriptor, about 24 ns a row of
#   2 KB, a tenth of the memory's rate, so at k = 2048 it loses to
#   streaming the whole cache until contexts pass some 40 k positions, and
#   no such form is kept here.
# - prefill, a query for every position: `blocked_select_attention`, blocks
#   of queries each against the keys at or before its last position.  No
#   (T, T) matrix of a head is ever whole.

def index_scores(qi, ki, w):
    """I (Tq, Tk) float32 from indexer queries qi (Tq, J, d), keys ki
    (Tk, d) and head weights w (Tq, J)."""
    with _costs.part("index"):
        s = jnp.einsum("qjd,kd->qjk", qi, ki,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("qjk,qj->qk", jax.nn.relu(s),
                          w.astype(jnp.float32))


def _ordered_bits(x):
    """float32 -> uint32 with the same order (-inf lowest; -0.0 counts as
    0.0, as it does in a comparison)."""
    x = x.astype(jnp.float32)
    u = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def _kth_largest_bits(bits, k):
    """The k-th largest of each row of `bits` (uint32; 0 where the row has
    fewer than k non-zero entries), found from the top bit down.  A small
    array (a decode step's scores) takes four bits a pass, eight unrolled
    passes of fifteen counts: its cost is the number of passes.  A large one
    (a block of a prefill) takes one bit a pass in a loop: its cost is the
    comparisons, and more bits a pass multiply them."""
    acc = jnp.zeros(bits.shape[:-1], jnp.uint32)
    if bits.size <= 1 << 20:
        steps = jnp.arange(1, 16, dtype=jnp.uint32)
        for shift in range(28, -1, -4):
            cand = acc[..., None] | (steps << shift)            # (..., 15)
            enough = jnp.sum(bits[..., None, :] >= cand[..., None], -1,
                             dtype=jnp.int32) >= k
            acc = acc | (jnp.sum(enough, -1).astype(jnp.uint32) << shift)
        return acc

    def narrow(i, acc):
        cand = acc | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(bits >= cand[..., None], -1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, acc)

    return jax.lax.fori_loop(0, 32, narrow, acc)


def select_mask(scores, valid, k):
    """Mask (..., N) of the min(k, valid count) valid entries of each row
    with the largest score, ties to the lower index."""
    with _costs.part("index"):
        bits = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
        kth = _kth_largest_bits(bits, k)[..., None]
        above = bits > kth
        room = k - jnp.sum(above, -1, dtype=jnp.int32, keepdims=True)
        equal = bits == kth

        def first_of_equal(_):
            return above | (equal & (jnp.cumsum(equal, -1, dtype=jnp.int32)
                                     <= room))

        # more entries equal the k-th than there is room for: rare, and the
        # running count that settles it costs a pass of its own
        tied = jnp.any(
            jnp.sum(equal, -1, dtype=jnp.int32, keepdims=True) > room)
        return valid & jax.lax.cond(tied, first_of_equal,
                                    lambda _: above | equal, None)


def masked_decode_attention(q, k_rows, v_rows, mask, scale, part="attn"):
    """One query a slot over the slot's cached rows under `mask`.
    q (S, H, d); k_rows, v_rows (S, G, L, d), head-major, H a multiple of
    the G key/value heads (query head i reads head i // (H/G)); mask
    (S, L).  Returns (S, H, d) float32.  `part` names the model part the
    work is counted under (`costs.part`)."""
    with _costs.part(part):
        S, H, d = q.shape
        G = k_rows.shape[1]
        qg = q.reshape(S, G, H // G, d)
        s = jnp.einsum("sghd,sgld->sghl", qg, k_rows,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, _NEG_INF), -1)
        o = jnp.einsum("sghl,sgld->sghd", p.astype(v_rows.dtype), v_rows,
                       preferred_element_type=jnp.float32)
        return o.reshape(S, H, d)


def _masked_block(qg, k, v, mask, scale, chunk):
    """qg (G, h, bq, d) against k (G, Tk, d), v (G, Tk, dv) under mask
    (bq, Tk), the keys in chunks of `chunk` with a running maximum and sum
    (the flash recurrence), so that the float32 scores alive are
    (G, h, bq, chunk).  Returns (G, h, bq, dv).
    Measured on a v5e (PERF.md, PR 29): a softmax over whole rows of 8192
    keys takes XLA fifty times as long as these chunks of 512."""
    G, h, bq, _ = qg.shape
    n = k.shape[1] // chunk
    split = lambda t: t.reshape(G, n, chunk, -1).transpose(1, 0, 2, 3)
    masks = mask.reshape(bq, n, chunk).transpose(1, 0, 2)

    def one(carry, xs):
        top, total, acc = carry
        kc, vc, mc = xs
        s = jnp.einsum("ghqd,gkd->ghqk", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mc[None, None], s, _NEG_INF)
        new_top = jnp.maximum(top, jnp.max(s, -1))
        # a row with no selected key in this chunk adds nothing
        p = jnp.where(mc[None, None], jnp.exp(s - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        acc = acc * shrink[..., None] + jnp.einsum(
            "ghqk,gkd->ghqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (new_top, total * shrink + jnp.sum(p, -1), acc), None

    init = (jnp.full((G, h, bq), _NEG_INF, jnp.float32),
            jnp.zeros((G, h, bq), jnp.float32),
            jnp.zeros((G, h, bq, v.shape[-1]), jnp.float32))
    (_, total, acc), _ = jax.lax.scan(one, init, (split(k), split(v), masks))
    return acc / total[..., None]                       # (G, h, bq, dv)


def blocked_select_attention(q, k, v, qi, ki, w, top_k, scale, block=1024,
                             chunk=512):
    """Causal select-then-attend for a whole prompt.  q (T, H, d); k, v
    (T, G, d); indexer qi (T, J, di), ki (T, di), w (T, J).  Query block
    b sees keys [0, end of b), in chunks of `chunk`: the blocks are
    unrolled with static shapes, so what lies after a block is neither
    scored nor read.  Returns (T, H, d) float32."""
    with _costs.part("attn"):
        T, H, d = q.shape
        G = k.shape[1]
        bq = min(int(block), T)
        chunk = min(int(chunk), bq)
        if T % bq or bq % chunk:
            raise ValueError("%d positions are no whole number of query "
                             "blocks of %d in key chunks of %d"
                             % (T, bq, chunk))
        qg = q.reshape(T, G, H // G, d).transpose(1, 2, 0, 3)   # (G, h, T, d)
        kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (G, T, d)
        out = []
        for q0 in range(0, T, bq):
            end = q0 + bq
            pos_q = q0 + jnp.arange(bq)
            mask = jnp.arange(end)[None, :] <= pos_q[:, None]   # causal
            if end > top_k:         # else every causal key is selected
                mask = select_mask(
                    index_scores(qi[q0:end], ki[:end], w[q0:end]), mask,
                    top_k)
            o = _masked_block(qg[:, :, q0:end], kg[:, :end], vg[:, :end],
                              mask, scale, chunk)               # (G, h, bq, d)
            out.append(o.transpose(2, 0, 1, 3).reshape(bq, H, d))
        return jnp.concatenate(out, 0)


def blocked_causal_attention(q, k, v, scale, block=512, chunk=512):
    """Causal multi-head attention over a whole prompt, keys and values of
    different widths: q, k (T, H, d), v (T, H, dv).  Query block b sees
    keys [0, end of b) in chunks of `chunk` (`_masked_block`), the blocks
    unrolled with static shapes, so no (T, T) matrix of a head is ever
    whole and what lies after a block is not read.  Returns (T, H, dv)
    float32."""
    with _costs.part("attn"):
        T, H, _ = q.shape
        bq = min(int(block), T)
        chunk = min(int(chunk), bq)
        if T % bq or bq % chunk:
            raise ValueError("%d positions are no whole number of query "
                             "blocks of %d in key chunks of %d"
                             % (T, bq, chunk))
        qg = q.transpose(1, 0, 2)[:, None]                      # (H, 1, T, d)
        kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (H, T, .)
        out = []
        for q0 in range(0, T, bq):
            end = q0 + bq
            mask = jnp.arange(end)[None, :] <= q0 + jnp.arange(bq)[:, None]
            o = _masked_block(qg[:, :, q0:end], kg[:, :end], vg[:, :end],
                              mask, scale, chunk)           # (H, 1, bq, dv)
            out.append(o[:, 0].transpose(1, 0, 2))
        return jnp.concatenate(out, 0)


def blocked_window_attention(q, k, v, scale, window=None, block=512,
                             chunk=512, part="attn"):
    """Grouped-query attention over a whole prompt, causal or under a band:
    q (T, H, d); k, v (T, G, d), H a multiple of G (query head i reads
    head i // (H/G)).  Position t sees keys j <= t, and with `window` only
    t - window < j <= t.  Query block b reads the key chunks that meet its
    band, from the chunk that holds its first query's earliest key to the
    block's end (`_masked_block`, the query heads grouped by key/value
    head); the blocks are unrolled with static shapes, so a chunk outside
    the band is neither sliced, scored nor read.  `part` names the model
    part the work is counted under.  Returns (T, H, d) float32."""
    with _costs.part(part):
        T, H, d = q.shape
        G = k.shape[1]
        bq = min(int(block), T)
        chunk = min(int(chunk), bq)
        if T % bq or bq % chunk:
            raise ValueError("%d positions are no whole number of query "
                             "blocks of %d in key chunks of %d"
                             % (T, bq, chunk))
        qg = q.reshape(T, G, H // G, d).transpose(1, 2, 0, 3)   # (G, h, T, d)
        kg, vg = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (G, T, d)
        out = []
        for q0 in range(0, T, bq):
            end = q0 + bq
            lo = 0 if window is None else \
                max(0, (q0 - int(window) + 1) // chunk * chunk)
            key = lo + jnp.arange(end - lo)[None, :]
            at = q0 + jnp.arange(bq)[:, None]
            mask = key <= at
            if window is not None:
                mask = mask & (key > at - int(window))
            o = _masked_block(qg[:, :, q0:end], kg[:, lo:end], vg[:, lo:end],
                              mask, scale, chunk)               # (G, h, bq, d)
            out.append(o.transpose(2, 0, 1, 3).reshape(bq, H, d))
        return jnp.concatenate(out, 0)


# ---------------------------------------------------------------------------
# latent prefill attention: causal attention over a whole prompt, every head's
# key a part of its own without a position and ONE rotary part all heads share
# ---------------------------------------------------------------------------
# The expanded form of a latent-attention prompt: a head's score is
# q_n . k_n + q_r . k_r with k_r (T, rope) the same for every head, its values
# narrower than its keys.
#
# - `_prefill_blocked`: the rotary key copied to every head and joined to the
#   keys, then `blocked_causal_attention`: float32 score chunks (H, 1, block,
#   chunk) in memory.  The reference, what the CPU runs, and what runs for
#   shapes the kernel does not tile.
# - `_prefill_pallas`: the kernel `latent_prefill_attention`.  Grid (groups of
#   heads, causal pairs of a query block and a key block): the pairs on or
#   under the diagonal are listed on the host and prefetched as scalars, so a
#   key block above the diagonal is no grid step at all: neither fetched nor
#   computed.  The operands lie as the projections leave them, (T, H * d)
#   (a (T, H, d) array is OTHER bytes on the TPU, whose tiles span the two
#   minor axes): a head's 128-wide part is a column block of whole lane
#   tiles, and so is its part of the result, which the output projection
#   takes as it is; only the rotary queries (64 wide, no whole tile a head)
#   come head-major, and the shared rotary key transposed, (rope, T), once.
#   A pair's scores, the flash recurrence (float32) and the probabilities
#   stay in VMEM; only the pairs that the diagonal crosses are masked.
# - `latent_prefill_attention` chooses between them (`_kernel_or_reference`).

_PREFILL_BLOCK = 512    # query rows, and key rows, a grid step, at most
_PREFILL_HEADS = 4      # heads a grid step, at most


def latent_prefill_block(T):
    """Rows of a query block and of a key block of the prefill kernel over a
    prompt of T positions: the largest divisor of T that is whole lane tiles
    (a pair's scores lie with the keys on the lanes) up to `_PREFILL_BLOCK`,
    T itself where the prompt is shorter than that; 0 if there is none."""
    return _largest_divisor(T, _PREFILL_BLOCK, 128)


def _prefill_fits(T, dn, dv):
    """Whether the kernel tiles a prompt of T positions with `dn` key dims
    without a position and `dv` value dims a head: whole blocks, and a
    head's part of a (T, H * d) operand whole lane tiles.  The interpreter
    takes any widths (the tests' tiny model)."""
    tb = latent_prefill_block(T)
    return tb > 0 and (_interpret() or not (tb % 128 or dn % 128 or dv % 128))


def latent_prefill_attention(qn, qr, kn, kr, v, scale, block=512, chunk=512):
    """Causal attention of a whole prompt in the expanded latent form, over
    the operands as the projections leave them.  qn, kn (T, H * dn) every
    head's query and key parts without a position side by side, qr
    (H, T, dr) the rotary queries, head-major, kr (T, dr) the ONE rotary key
    a position, v (T, H * dv).  Scores (qn . kn + qr . kr) * scale over keys
    <= the query, operands in their own type summed in float32, softmax in
    float32, the probabilities rounded to the values' type for the context.
    Returns (T, H * dv) in the values' type.

    `_kernel_or_reference` picks the kernel or `blocked_causal_attention` in
    query blocks of `block` and key chunks of `chunk`; the counter
    `mla.prefill_kernel_traces` counts the layer bodies lowered with the
    kernel."""
    with _costs.part("attn"):
        H, T, _ = qr.shape
        return _kernel_or_reference(
            functools.partial(_prefill_pallas, scale=scale),
            functools.partial(_prefill_blocked, scale=scale, block=block,
                              chunk=chunk),
            (qn, qr, kn, kr, v),
            _prefill_fits(T, qn.shape[1] // H, v.shape[1] // H),
            ("mla.prefill_kernel_traces",))


def _prefill_blocked(qn, qr, kn, kr, v, scale, block, chunk):
    H, T, dr = qr.shape
    heads = lambda a: a.reshape(T, H, -1)
    k = jnp.concatenate(
        [heads(kn), jnp.broadcast_to(kr[:, None, :], (T, H, dr))], -1)
    q = jnp.concatenate([heads(qn), qr.transpose(1, 0, 2)], -1)
    return blocked_causal_attention(q, k, heads(v), scale, block, chunk) \
        .astype(v.dtype).reshape(T, -1)


def _prefill_kernel(qi_ref, kj_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                    o_ref, m_s, l_s, acc_s, *, scale, tb, hb, dn, dv):
    p = pl.program_id(1)
    i, j = qi_ref[p], kj_ref[p]
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)

    def pair(diagonal):
        kr_t = kr_ref[...]                                      # (dr, tb)
        lanes = l_s.shape[2]
        for h in range(hb):
            n, o = slice(h * dn, (h + 1) * dn), slice(h * dv, (h + 1) * dv)
            s = jax.lax.dot_general(
                qn_ref[:, n], kn_ref[:, n], (((1,), (1,)), ((), ())),
                preferred_element_type=f32) \
                + jnp.dot(qr_ref[h], kr_t, preferred_element_type=f32)
            s = s * scale                                       # (tb, tb)
            if diagonal:
                s = jnp.where(
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1),
                    s, _NEG_INF)
            m_prev = m_s[h]                                     # (tb, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            e = jnp.exp(s - m_new)
            shrink = jnp.exp(m_prev - m_new)
            # the row sums stay a lane apart until the query block's end:
            # adding lane tiles is elementwise, a sum ACROSS lanes is not
            # (measured, PERF.md PR 40: a tenth of the kernel)
            l_s[h] = l_s[h] * shrink + sum(
                e[:, t:t + lanes] for t in range(0, tb, lanes))
            acc_s[:, o] = acc_s[:, o] * shrink + jnp.dot(
                e.astype(v_ref.dtype), v_ref[:, o],
                preferred_element_type=f32)
            m_s[h] = m_new

    # query and key blocks are alike, so the diagonal crosses pair (i, i)
    # alone, which is also a query block's last
    pl.when(j < i)(functools.partial(pair, False))

    @pl.when(j == i)
    def _last():
        pair(True)
        for h in range(hb):
            o = slice(h * dv, (h + 1) * dv)
            o_ref[:, o] = (acc_s[:, o] / jnp.sum(
                l_s[h], axis=1, keepdims=True)).astype(o_ref.dtype)


def _prefill_pallas(qn, qr, kn, kr, v, scale):
    H, T, dr = qr.shape
    dn, dv = qn.shape[1] // H, v.shape[1] // H
    tb = latent_prefill_block(T)
    hb = math.gcd(H, _PREFILL_HEADS)
    pairs = [(i, j) for i in range(T // tb) for j in range(i + 1)]
    qi, kj = (jnp.asarray(a, jnp.int32) for a in zip(*pairs))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=float(scale), tb=tb, hb=hb,
                          dn=dn, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // hb, len(pairs)),
            in_specs=[
                pl.BlockSpec((tb, hb * dn), lambda g, p, qi, kj: (qi[p], g)),
                pl.BlockSpec((hb, tb, dr),
                             lambda g, p, qi, kj: (g, qi[p], 0)),
                pl.BlockSpec((tb, hb * dn), lambda g, p, qi, kj: (kj[p], g)),
                pl.BlockSpec((dr, tb), lambda g, p, qi, kj: (0, kj[p])),
                pl.BlockSpec((tb, hb * dv), lambda g, p, qi, kj: (kj[p], g)),
            ],
            out_specs=pl.BlockSpec((tb, hb * dv),
                                   lambda g, p, qi, kj: (qi[p], g)),
            scratch_shapes=[pltpu.VMEM((hb, tb, 1), jnp.float32),
                            pltpu.VMEM((hb, tb, math.gcd(tb, 128)),
                                       jnp.float32),
                            pltpu.VMEM((tb, hb * dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H * dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_prefill_attention",
        interpret=_interpret(),
    )(qi, kj, qn, qr, kn, kr.T, v)


# ---------------------------------------------------------------------------
# latent decode attention: every head reads ONE compressed row a position
# ---------------------------------------------------------------------------
# A latent cache holds, a position, one row c of `rank` values that every
# head's key and value are linear maps of, and one rotary key kr that all
# heads share.  With the key's map absorbed into the query (q_abs = Wk^T q,
# a head) the scores are q_abs . c + q_rope . kr, and the context is the
# softmax-weighted sum of the rows c themselves, (H, rank) a slot; the
# value's map is applied to that afterwards.  No head's keys or values are
# ever formed from cached rows.  The work a row: H (2 rank + rope) x 2
# operations for (rank + rope) values read, so at H = 128 the read sits on
# the v5e's ridge and not under it, unlike every other decode attention here.
#
# - `_latent_einsums`: two einsums over ALL rows of every slot of the layer's
#   slice under a mask of the slots' lengths, the float32 scores (S, H, T)
#   in memory between them.  The reference, what the CPU runs, and what runs
#   for leaves the kernel does not tile.
# - `_latent_pallas`: the kernel `latent_decode_attention`.  Grid (slots,
#   row blocks); the layer, each slot's count of needed blocks and its
#   length are prefetched as scalars.  The leaves come WHOLE: the layer only
#   enters their index maps, so no layer's rows are copied out.  A step past
#   a slot's last needed block computes nothing and fetches nothing new: its
#   index map already names the NEXT slot's first block, so that block
#   arrives under the last needed block's arithmetic and not after it.  A
#   block's scores, its probabilities and the flash recurrence (float32)
#   stay in VMEM; the context is summed from the same block's latent rows,
#   which is why a row is read once.
# - `latent_decode_attention` chooses between them (`_kernel_or_reference`).
#
# The rotary keys' leaf kr (S, layers, T, rope) has rope < 128: XLA lays
# such a leaf on the TPU with T on the lanes (minor-to-major {2,3,1,0}), so
# the kernel takes it as (S, layers, rope, T), which is the same bytes (the
# `swapaxes` lowers to a bitcast) and gives blocks (rope, rows) of whole
# tiles whose product with the queries needs no transpose.

_LATENT_ROW_BLOCK = 512     # cached bfloat16 rows a grid step, at most


def latent_row_block(T, dtype=jnp.bfloat16):
    """Rows of one block of the latent kernel over leaves of T rows of
    `dtype`: the largest divisor of T that is whole lane tiles (a block's
    scores lie with the rows on the lanes) up to `_LATENT_ROW_BLOCK` rows of
    bfloat16, half as many of float32 (the same bytes); 0 if there is
    none."""
    cap = _LATENT_ROW_BLOCK * 2 // jnp.dtype(dtype).itemsize
    tb = _largest_divisor(T, cap, 128)
    return tb if tb % 128 == 0 else 0


def _latent_fits(ckv):
    """Whether the kernel tiles the leaf ckv (S, layers, T, rank): full-lane
    latent rows and a whole number of row blocks."""
    return ckv.shape[3] % 128 == 0 \
        and latent_row_block(ckv.shape[2], ckv.dtype) > 0


def latent_rows_read(lengths, ckv):
    """Rows of the leaf ckv (S, layers, T, rank) that
    `latent_decode_attention` covers for each slot, (S,) int32: the slot's
    length rounded up to the kernel's row block, or all T where the leaf
    does not tile."""
    T = ckv.shape[2]
    tb = latent_row_block(T, ckv.dtype) if _latent_fits(ckv) else T
    return (-(-jnp.clip(lengths, 1, T) // tb) * tb).astype(jnp.int32)


def latent_decode_attention(q_abs, q_rope, ckv, kr, layer, lengths, scale):
    """One query a slot over the slot's latent rows [0, lengths[slot]) of
    one layer.  q_abs (S, H, rank) the queries with the keys' map absorbed,
    q_rope (S, H, rope); ckv (S, layers, T, rank) and kr (S, layers, T,
    rope) the cache's leaves, whole; `layer` (a traced scalar) the layer
    whose rows are read; lengths (S,) int32 in [1, T].  Products of
    operands in the leaves' type summed in float32, softmax in float32,
    the probabilities rounded to the leaves' type for the context.  Returns
    the context in latent space, (S, H, rank) float32.

    `_kernel_or_reference` picks the kernel or the einsums; the counter
    `mla.kernel_traces` counts the layer bodies lowered with the kernel."""
    with _costs.part("attn"):
        return _kernel_or_reference(
            functools.partial(_latent_pallas, scale=scale),
            functools.partial(_latent_einsums, scale=scale),
            (q_abs, q_rope, ckv, kr,
             jnp.asarray(layer, jnp.int32).reshape(1), lengths),
            _latent_fits(ckv), ("mla.kernel_traces",))


def _latent_einsums(q_abs, q_rope, ckv, kr, layer, lengths, scale):
    f32 = jnp.float32
    c, r = jnp.take(ckv, layer[0], axis=1), jnp.take(kr, layer[0], axis=1)
    s = jnp.einsum("shc,stc->sht", q_abs, c, preferred_element_type=f32) \
        + jnp.einsum("shr,str->sht", q_rope, r, preferred_element_type=f32)
    live = jnp.arange(c.shape[1])[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s * scale, _NEG_INF), -1)
    return jnp.einsum("sht,stc->shc", p.astype(c.dtype), c,
                      preferred_element_type=f32)


def _latent_kernel(layer_ref, need_ref, len_ref, qa_ref, qr_ref, c_ref, r_ref,
                   o_ref, m_s, l_s, acc_s, *, scale, tb):
    del layer_ref                       # read by the index maps
    s, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)

    @pl.when(j < need_ref[s])
    def _block():
        c = c_ref[0, 0]                                         # (tb, rank)
        sc = jax.lax.dot_general(qa_ref[0], c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) \
            + jnp.dot(qr_ref[0], r_ref[0, 0], preferred_element_type=f32)
        row = j * tb + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(row < len_ref[s], sc * scale, _NEG_INF)  # (H, tb)
        m_prev = m_s[...]                                       # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        shrink = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * shrink + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * shrink + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=f32)
        m_s[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = acc_s[...] / l_s[...]


def _latent_pallas(q_abs, q_rope, ckv, kr, layer, lengths, scale):
    S, H, rank = q_abs.shape
    T, rope = kr.shape[2:]
    tb = latent_row_block(T, ckv.dtype)
    lens = jnp.clip(lengths.astype(jnp.int32), 1, T)
    need = -(-lens // tb)                   # row blocks a slot computes

    def at(s, j, need):
        """(slot, row block) that step (s, j) holds: its own while the slot
        needs block j, then the next slot's first (the last slot keeps its
        last)."""
        done, last = j >= need[s], s == S - 1
        return (jnp.where(done & ~last, s + 1, s),
                jnp.where(done, jnp.where(last, need[s] - 1, 0), j))

    def query(s, j, layer, need, lens):
        return (at(s, j, need)[0], 0, 0)

    def rows(s, j, layer, need, lens):
        slot, block = at(s, j, need)
        return (slot, layer[0], block, 0)

    def rows_t(s, j, layer, need, lens):
        slot, block = at(s, j, need)
        return (slot, layer[0], 0, block)

    return pl.pallas_call(
        functools.partial(_latent_kernel, scale=float(scale), tb=tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, T // tb),
            in_specs=[
                pl.BlockSpec((1, H, rank), query),
                pl.BlockSpec((1, H, rope), query),
                pl.BlockSpec((1, 1, tb, rank), rows),
                pl.BlockSpec((1, 1, rope, tb), rows_t),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda s, j, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, rank), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="latent_decode_attention",
        interpret=_interpret(),
    )(layer, need, lens, q_abs, q_rope, ckv, jnp.swapaxes(kr, 2, 3))


# ---------------------------------------------------------------------------
# grouped decode attention: several query heads a key/value head, rows to a length
# ---------------------------------------------------------------------------
# One query a slot and head over the slot's rows [0, length) of one layer of
# stacked head-major leaves (S, layers, G, T, d), query head i reading
# key/value head i // (H/G): a layer of full rows (length pos + 1) or a ring
# (length min(pos + 1, window)).
#
# - `_grouped_einsums`: the layer's rows taken out of the leaves and
#   `masked_decode_attention` over ALL T rows of every slot under a mask of
#   the lengths, the float32 scores (S, H, T) in memory between its two
#   einsums.  The reference, what the CPU runs, and what runs for leaves the
#   kernel does not tile.
# - `_grouped_pallas`: the kernel `grouped_decode_attention`.  Grid (slots,
#   row blocks); the layer, each slot's count of needed blocks and its length
#   are prefetched as scalars.  The leaves come WHOLE, the layer only in their
#   index maps.  A block holds all G heads' rows, (G, tb, d); each head's
#   group of query rows meets its rows on the MXU, and the scores, the flash
#   recurrence (float32) and the probabilities rounded to the leaves' type
#   stay in VMEM.  A step past a slot's last needed block computes nothing
#   and fetches nothing new: its index map names the NEXT slot's first block,
#   as `latent_decode_attention`'s does.
# - `grouped_decode_attention` chooses between them (`_kernel_or_reference`).

# Measured alone on a TPU v5e at 64 slots (PERF.md, section 6): over
# (3, 8, 9216, 128) bfloat16 leaves at lengths 6144-9216, 2.83 ms with
# blocks of 256 rows and 2.86 with 512 (the einsums 3.91); over full rings
# (6, 8, 512, 128) 0.230 either way (the einsums 0.223).  With the query
# rows as the stationary operand of the scores' product, 3.13 and 2.88 over
# the full leaves.
_GROUPED_ROW_BLOCK = 256    # cached rows a grid step, at most


def grouped_row_block(T):
    """Rows of one block of the grouped kernel over leaves of T rows: the
    largest divisor of T up to `_GROUPED_ROW_BLOCK` that is whole lane
    tiles (a block's scores lie with the rows on the lanes); 0 if there is
    none."""
    tb = _largest_divisor(T, _GROUPED_ROW_BLOCK, 128)
    return tb if tb % 128 == 0 else 0


def _grouped_fits(k):
    """Whether the kernel tiles the leaf k (S, layers, G, T, d): full-lane
    rows and a whole number of row blocks."""
    return k.shape[4] % 128 == 0 and grouped_row_block(k.shape[3]) > 0


def grouped_rows_read(lengths, k):
    """Rows of the leaf k (S, layers, G, T, d) that
    `grouped_decode_attention` covers for each slot in one layer, (S,)
    int32: the slot's length rounded up to the kernel's row block, or all T
    where the leaf does not tile."""
    T = k.shape[3]
    tb = grouped_row_block(T) if _grouped_fits(k) else T
    return (-(-jnp.clip(lengths, 0, T) // tb) * tb).astype(jnp.int32)


def grouped_decode_attention(q, k, v, layer, lengths, scale, part="attn"):
    """One query a slot and head over the slot's rows [0, lengths[slot]) of
    one layer.  q (S, H, d); k, v (S, layers, G, T, d) the cache's stacked
    leaves, whole, H a multiple of G (query head i reads head i // (H/G));
    `layer` (a traced scalar) the layer whose rows are read; lengths (S,)
    int32 in [0, T].  Products of operands in the leaves' type summed in
    float32, softmax in float32, the probabilities rounded to the leaves'
    type for the context.  Returns (S, H, d) float32, counted under the
    model part `part`; a slot of length 0 reads nothing, and its rows of
    the result are finite and unspecified.

    `_kernel_or_reference` picks the kernel or the einsums; the counter
    `attn.grouped_kernel_traces` counts the layer bodies lowered with the
    kernel."""
    with _costs.part(part):
        return _kernel_or_reference(
            functools.partial(_grouped_pallas, scale=scale),
            functools.partial(_grouped_einsums, scale=scale, part=part),
            (q, k, v, jnp.asarray(layer, jnp.int32).reshape(1),
             jnp.clip(lengths.astype(jnp.int32), 0, k.shape[3])),
            _grouped_fits(k), ("attn.grouped_kernel_traces",))


def _grouped_einsums(q, k, v, layer, lengths, scale, part):
    live = jnp.arange(k.shape[3])[None, :] < lengths[:, None]
    return masked_decode_attention(q, jnp.take(k, layer[0], axis=1),
                                   jnp.take(v, layer[0], axis=1), live, scale,
                                   part)


def _grouped_kernel(layer_ref, need_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    m_s, l_s, acc_s, *, scale, tb):
    del layer_ref                       # read by the index maps
    s, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)

    @pl.when(j < need_ref[s])
    def _block():
        # each key/value head's h query rows against its tb rows
        v = v_ref[...]                                          # (G, tb, d)
        sc = jax.lax.dot_general(q_ref[...], k_ref[...],
                                 (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=f32)    # (G, h, tb)
        row = j * tb + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 2)
        sc = jnp.where(row < len_ref[s], sc * scale, _NEG_INF)
        m_prev = m_s[...]                                       # (G, h, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        p = jnp.exp(sc - m_new)
        shrink = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * shrink + jnp.sum(p, axis=2, keepdims=True)
        acc_s[...] = acc_s[...] * shrink + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32)                         # (G, h, d)
        m_s[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        total = l_s[...]
        o_ref[...] = jnp.where(total > 0, acc_s[...] / total, 0.0)


def _grouped_pallas(q, k, v, layer, lengths, scale):
    S, H, d = q.shape
    G, T = k.shape[2], k.shape[3]
    h = H // G
    tb = grouped_row_block(T)
    need = -(-lengths // tb)                # row blocks a slot computes

    def at(s, j, need):
        """(slot, row block) that step (s, j) holds: its own while the slot
        needs block j, then the next slot's first (the last slot keeps its
        last)."""
        done, last = j >= need[s], s == S - 1
        return (jnp.where(done & ~last, s + 1, s),
                jnp.where(done, jnp.where(last, jnp.maximum(need[s] - 1, 0),
                                          0), j))

    def query(s, j, layer, need, lens):
        return (at(s, j, need)[0], 0, 0, 0)

    def rows(s, j, layer, need, lens):
        slot, block = at(s, j, need)
        return (slot, layer[0], 0, block, 0)

    item = jnp.dtype(k.dtype).itemsize
    # K and V blocks and the query's, each twice (the pipeline's two
    # buffers), the result's twice, the recurrence's scratch, and room for
    # a block's float32 scores and probabilities
    vmem = 2 * (2 * G * tb * d * item + H * d * jnp.dtype(q.dtype).itemsize
                + H * d * 4) + 3 * H * d * 4 + 4 * G * 8 * tb * 4
    return pl.pallas_call(
        functools.partial(_grouped_kernel, scale=float(scale), tb=tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, T // tb),
            in_specs=[
                pl.BlockSpec((None, G, h, d), query),
                pl.BlockSpec((None, None, G, tb, d), rows),
                pl.BlockSpec((None, None, G, tb, d), rows),
            ],
            out_specs=pl.BlockSpec((None, G, h, d),
                                   lambda s, j, *_: (s, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((G, h, 1), jnp.float32),
                            pltpu.VMEM((G, h, 1), jnp.float32),
                            pltpu.VMEM((G, h, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, G, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (4 << 20)),
        name="grouped_decode_attention",
        interpret=_interpret(),
    )(layer, need, lengths, q.reshape(S, G, h, d), k, v).reshape(S, H, d)


# ---------------------------------------------------------------------------
# ragged decode attention: one query row a slot, cost by the rows that are live
# ---------------------------------------------------------------------------
# A decode step of a slot-major cache attends one query row a slot over the
# slot's own rows [0, length): `lengths` is data, 0 for a slot nobody sits
# in.  The leaves lie (S, G, T, W): G groups of `heads` heads whose d-wide
# rows share one W = heads*d wide row (models/transformer.py `_lane_heads`).
#
# - `dense_decode_attention`: two einsums over the whole leaves under an
#   additive mask.  Reads every row of every slot; the reference, and what
#   runs wherever the kernel does not.
# - `ragged_decode_attention`: the Pallas kernel.  Grid (slot blocks, row
#   blocks); the lengths are prefetched as scalars, and the index map of K
#   and V repeats the block it fetched last wherever a step needs none, so a
#   skipped step moves no byte and runs no arithmetic.  Inside a needed block
#   a loop over its slots skips those that end before it.  The scores of the
#   `heads` heads of a row come from one product k*q summed over each head's
#   own lanes by a matmul with a 0/1 matrix (the product split into three
#   bfloat16 parts, so the sum keeps float32), and stay broadcast over those
#   lanes: softmax and the context are then plain elementwise work and
#   reductions over rows, the flash recurrence in float32.
# - `decode_attention` chooses between them (`_kernel_or_reference`).
# - With `layer`, the leaves are STACKED, (S, layers, G, T, W), and read at
#   that index of axis 1 (a traced scalar, prefetched with the plan): the
#   kernel's blocks are cut out of the leaves as they lie.  A layer's slice
#   in front of the kernel would be copied, a leaf's worth a layer body.

_SLOT_BLOCK = 16        # slots a grid step
_ROW_BLOCK = 64         # cached rows a grid step, at most


def _tile_rows(dtype):
    """Rows of one TPU tile of `dtype`: 8 of float32, 16 of bfloat16."""
    return 32 // jnp.dtype(dtype).itemsize


def ragged_row_block(T, dtype=jnp.float32):
    """Rows of one key block of the ragged kernel over leaves of T rows:
    the largest divisor of T up to `_ROW_BLOCK` that is whole tiles of
    `dtype`, or T where there is none (one block, every row read)."""
    return _largest_divisor(T, _ROW_BLOCK, _tile_rows(dtype)) or T


def _ragged_fits(k):
    """Whether the ragged kernel tiles the leaf k (S, G, T, W), or
    (S, layers, G, T, W), on a TPU: full-lane rows, row blocks of whole
    tiles, a block that fits VMEM twice over beside V's."""
    S, (G, T, W) = k.shape[0], k.shape[-3:]
    tb = ragged_row_block(T, k.dtype)
    block = min(S, _SLOT_BLOCK) * G * tb * W * jnp.dtype(k.dtype).itemsize
    return W % 128 == 0 and tb % _tile_rows(k.dtype) == 0 \
        and block <= 4 << 20


def decode_rows_read(lengths, k):
    """Rows of the leaf k (S, G, T, W), or (S, layers, G, T, W), that
    `decode_attention` covers for each slot in one layer, (S,) int32: the
    slot's length rounded up to the kernel's row block, or all T where the
    leaf does not tile."""
    T = k.shape[-2]
    tb = ragged_row_block(T, k.dtype) if _ragged_fits(k) else T
    return (-(-jnp.clip(lengths, 0, T) // tb) * tb).astype(jnp.int32)


def _ragged_takes(k):
    """Whether `decode_attention` runs the ragged kernel on the leaf k:
    where it tiles it (`_ragged_fits`), and on any leaf in interpret
    mode."""
    return _interpret() or _ragged_fits(k)


def decode_attention(q, k, v, lengths, heads=1, scale=1.0, layer=None):
    """`ragged_decode_attention` or `dense_decode_attention`, as
    `_kernel_or_reference` picks them (`_ragged_takes`).  The two agree on
    every slot with lengths > 0.  With `layer` (a traced scalar), k and v
    are stacked leaves (S, layers, G, T, W), read at that layer."""
    with _costs.part("attn"):
        args = (q, k, v, lengths)
        if layer is not None:
            args += (jnp.asarray(layer, jnp.int32).reshape(1),)
        return _kernel_or_reference(
            functools.partial(ragged_decode_attention, heads=heads,
                              scale=scale),
            functools.partial(dense_decode_attention, heads=heads,
                              scale=scale),
            args, _ragged_takes(k))


def _lane_owner(heads, W):
    """(W, W) 0/1: lane i and lane j belong to the same head."""
    own = jnp.arange(W) // (W // heads)
    return own[:, None] == own[None, :]


def dense_decode_attention(q, k, v, lengths, layer=None, heads=1, scale=1.0):
    """q (S, G, W) against k, v (S, G, T, W) under rows < lengths (S,):
    (S, G, W) float32.  Head j of a group reads its own d = W/heads of the
    row's lanes: its query is zero on the others', and of the (heads, W)
    context it keeps its own d.  With `layer` (1,), the leaves are stacked
    (S, layers, G, T, W) and that layer's rows are taken."""
    if layer is not None:
        k, v = jnp.take(k, layer[0], axis=1), jnp.take(v, layer[0], axis=1)
    S, G, T, W = k.shape
    d = W // heads
    own = jnp.eye(heads, dtype=q.dtype)[:, :, None]             # (P, P, 1)
    qh = (q.reshape(S, G, 1, heads, d) * own).reshape(S, G, heads, W)
    mask = (jnp.arange(T)[None, :] >= lengths[:, None]) * -1e9  # (S, T)
    sc = jnp.einsum("bgjw,bgtw->bgjt", qh, k) * scale \
        + mask[:, None, None, :]
    at = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bgjt,bgtw->bgjw", at, v)
    ctx = jnp.einsum("bgjjd->bgjd", ctx.reshape(S, G, heads, heads, d))
    return ctx.reshape(S, G, W)


def _ragged_kernel(fetch_ref, need_ref, lo_ref, hi_ref, len_ref, *refs,
                   scale, sb, tb):
    # stacked leaves prefetch their layer as well: the index maps read it
    q_ref, own_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs[-8:]
    i, j = pl.program_id(0), pl.program_id(1)
    f32, bf16 = jnp.float32, jnp.bfloat16
    G, W = k_ref.shape[1], k_ref.shape[3]

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG_INF, f32)
        l_s[...] = jnp.zeros(l_s.shape, f32)
        acc_s[...] = jnp.zeros(acc_s.shape, f32)

    @pl.when(j < need_ref[i])
    def _block():
        own = own_ref[...]                                      # (W, W)
        row = j * tb + jax.lax.broadcasted_iota(jnp.int32, (G, tb, W), 1)

        def one_slot(s, carry):
            n = len_ref[i * sb + s]

            @pl.when(n > j * tb)
            def _slot():
                x = (k_ref[s].astype(f32) * q_ref[s]).reshape(G * tb, W)
                # the sum over each head's lanes, left on those lanes
                hi = x.astype(bf16)
                x = x - hi.astype(f32)
                mid = x.astype(bf16)
                lo = (x - mid.astype(f32)).astype(bf16)
                sc = sum(jnp.dot(part, own, preferred_element_type=f32)
                         for part in (hi, mid, lo)).reshape(G, tb, W)
                sc = jnp.where(row < n, sc * scale, _NEG_INF)
                m_prev = m_s[s]                                 # (G, 1, W)
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sc, axis=1, keepdims=True))
                p = jnp.exp(sc - m_new)
                shrink = jnp.exp(m_prev - m_new)
                l_s[s] = l_s[s] * shrink + jnp.sum(p, axis=1, keepdims=True)
                acc_s[s] = acc_s[s] * shrink + jnp.sum(
                    p * v_ref[s].astype(f32), axis=1, keepdims=True)
                m_s[s] = m_new

            return carry

        jax.lax.fori_loop(0, sb, one_slot, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        total = l_s[...]
        o_ref[...] = jnp.where(total > 0, acc_s[...] / total, 0.0)


def _ragged_plan(lengths, S, sb, tb):
    """What each grid row (a block of `sb` slots) does, as the scalars the
    kernel prefetches: `fetch`, the slot block it keeps in VMEM; `need`, how
    many row blocks of `tb` it computes; `lo`/`hi`, the row blocks it
    fetches (step j fetches clip(j, lo, hi)).  A slot block that needs
    nothing repeats what was fetched last before it (or what is fetched
    first after it), so its steps move nothing."""
    nB = -(-S // sb)
    lens = jnp.pad(lengths.astype(jnp.int32), (0, nB * sb - S))
    need = -(-jnp.max(lens.reshape(nB, sb), axis=1) // tb)
    idx = jnp.arange(nB, dtype=jnp.int32)
    live = need > 0
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    fetch = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    still = jnp.where(before >= 0, need[fetch] - 1, 0)
    lo = jnp.where(live, 0, still)
    hi = jnp.where(live, need - 1, still)
    return [a.astype(jnp.int32) for a in (fetch, need, lo, hi, lens)]


def ragged_decode_attention(q, k, v, lengths, layer=None, heads=1,
                            scale=1.0):
    """One query row a slot over the slot's rows [0, lengths[slot]) of
    slot-major leaves: q (S, G, W), k and v (S, G, T, W) float32 or
    bfloat16, lengths (S,) int32 in [0, T]; W = heads * d lanes hold
    `heads` heads side by side.  Returns (S, G, W) float32.  Reads only
    the key blocks below a slot's length and nothing of a slot of length
    0, whose rows of the result are finite and unspecified.  With `layer`
    (1,) int32, k and v are stacked leaves (S, layers, G, T, W) and the
    blocks are that layer's, cut out of the leaves where they lie."""
    S, (G, T, W) = k.shape[0], k.shape[-3:]
    sb = min(S, _SLOT_BLOCK)
    tb = ragged_row_block(T, k.dtype)
    nB = -(-S // sb)
    plan = _ragged_plan(jnp.clip(lengths, 0, T), S, sb, tb)
    one = lambda i, j, fetch, *_: (fetch[i], 0, 0, 0)
    if layer is None:
        block = (sb, G, tb, W)
        rows = lambda i, j, fetch, need, lo, hi, lens: (
            fetch[i], 0, jnp.clip(j, lo[i], hi[i]), 0)
    else:
        plan.append(layer)
        block = (sb, None, G, tb, W)     # the layer's axis squeezed away
        rows = lambda i, j, fetch, need, lo, hi, lens, layer: (
            fetch[i], layer[0], 0, jnp.clip(j, lo[i], hi[i]), 0)
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=float(scale), sb=sb, tb=tb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(nB, T // tb),
            in_specs=[
                pl.BlockSpec((sb, G, 1, W), one),
                pl.BlockSpec((W, W), lambda i, j, *_: (0, 0)),
                pl.BlockSpec(block, rows),
                pl.BlockSpec(block, rows),
            ],
            out_specs=pl.BlockSpec((sb, G, 1, W),
                                   lambda i, j, *_: (i, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((sb, G, 1, W), jnp.float32)] * 3,
        ),
        out_shape=jax.ShapeDtypeStruct((S, G, 1, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="ragged_decode_attention",
        interpret=_interpret(),
    )(*plan, q.reshape(S, G, 1, W).astype(jnp.float32),
      _lane_owner(heads, W).astype(jnp.bfloat16), k, v)
    return out.reshape(S, G, W)


# ---------------------------------------------------------------------------
# a decode step's new rows, written into stacked leaves where they lie
# ---------------------------------------------------------------------------
# One row a slot and head, each slot at its own position: k[s, layer, g,
# pos[s]] = k_new[s, g].  As an indexed update that is an XLA scatter of
# S x G rows, which the v5e runs a row at a time: 0.1 us a row, 39 us a leaf
# and layer body at 24 slots of 16 heads, a fifth of a looped model's step
# (PERF.md, PR 41; promising sorted, unique indices changes nothing, and one
# window (G, W) a slot makes XLA lay the leaves out anew and copy them whole).
# The kernel `decode_rows_write` takes the tile of rows that holds a slot's
# position, for all its heads, out of both leaves, sets the one row and puts
# the tile back: the leaves are aliased to the results, nothing else moves.


def _rows_fit(k):
    """Whether the row-write kernel tiles the leaf k (S, layers, G, T, W):
    full-lane rows in whole tiles."""
    T, W = k.shape[-2:]
    return W % 128 == 0 and T % _tile_rows(k.dtype) == 0


def decode_rows_write(k, v, k_new, v_new, layer, pos):
    """The stacked leaves k, v (S, layers, G, T, W) with row pos[s] of
    `layer` (a traced scalar) set to k_new[s], v_new[s] (S, G, W) for every
    slot s; pos (S,) int32 in [0, T).  The kernel or an indexed update, as
    `_kernel_or_reference` picks them."""
    with _costs.part("cache"):
        return _kernel_or_reference(
            _rows_pallas, _rows_scatter,
            (k, v, k_new.astype(k.dtype), v_new.astype(v.dtype),
             jnp.asarray(layer, jnp.int32).reshape(1), pos.astype(jnp.int32)),
            _rows_fit(k))


def _rows_scatter(k, v, k_new, v_new, layer, pos):
    S, G = k_new.shape[:2]
    at = (jnp.arange(S)[:, None], layer[0], jnp.arange(G)[None, :],
          pos[:, None])
    return k.at[at].set(k_new), v.at[at].set(v_new)


def _rows_kernel(layer_ref, pos_ref, kn_ref, vn_ref, k_ref, v_ref, ko_ref,
                 vo_ref, *, tr):
    del layer_ref                       # read by the index maps
    f32 = jnp.float32
    here = jax.lax.broadcasted_iota(jnp.int32, k_ref.shape, 1) \
        == pos_ref[pl.program_id(0)] % tr
    # through float32 and back: exact, and the v5e selects no bfloat16
    for new, old, out in ((kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref)):
        out[...] = jnp.where(here, new[...].astype(f32),
                             old[...].astype(f32)).astype(out.dtype)


def _rows_pallas(k, v, k_new, v_new, layer, pos):
    S, _, G, T, W = k.shape
    tr = _tile_rows(k.dtype)
    new = pl.BlockSpec((None, G, 1, W), lambda s, layer, pos: (s, 0, 0, 0))
    tile = pl.BlockSpec((None, None, G, tr, W),
                        lambda s, layer, pos: (s, layer[0], 0, pos[s] // tr,
                                               0))
    return pl.pallas_call(
        functools.partial(_rows_kernel, tr=tr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[new, new, tile, tile],
            out_specs=[tile, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        # operands 4 and 5 count the two prefetched scalars: the leaves
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="decode_rows_write",
        interpret=_interpret(),
    )(layer, jnp.clip(pos, 0, T - 1), k_new.reshape(S, G, 1, W),
      v_new.reshape(S, G, 1, W), k, v)


# ---------------------------------------------------------------------------
# a decode step's new rows, written at the live slots alone
# ---------------------------------------------------------------------------
# Slot-major leaves (S, G, T, W) whose slots are mostly empty: `nmt_base`
# holds 512 slots with about 7 live at 37 req/s.  The indexed update writes
# every slot's row, a scatter of S x G rows a leaf run a row at a time (1.72
# of a 2.56 ms step, PERF.md, PR 34), and `decode_rows_write`, a grid step a
# slot, costs as much whether the slot is live or not.  `live_rows_write`
# copies each live slot's new rows, all its groups, into both aliased leaves
# at the slot's position: a DMA a slot and leaf, in a loop as long as the
# live count, a few of them in flight.  A dead slot costs nothing.

_ROWS_IN_FLIGHT = 16    # copies a leaf before the loop waits for the oldest


def live_rows_plan(pos, live, T):
    """What `live_rows_write` needs of a step, computed once for all its
    layers: the slots that are live with a position in [0, T), in order at
    the front of an (S,) int32 list; how many, (1,) int32; and pos (S,)
    int32."""
    S = pos.shape[0]
    ok = live & (pos >= 0) & (pos < T)
    rank = jnp.cumsum(ok) - 1
    idx = jnp.arange(S)
    at = ok[None, :] & (rank[None, :] == idx[:, None])          # (S, S)
    slots = jnp.sum(jnp.where(at, idx[None, :], 0), axis=1)
    return (slots.astype(jnp.int32), jnp.sum(ok, dtype=jnp.int32).reshape(1),
            pos.astype(jnp.int32))


def _live_fits(k):
    """Whether the kernel of `live_rows_write` takes the leaf k (S, G, T,
    W): full-lane rows of 32 bits, which a DMA moves one at a time (a
    bfloat16 row is half of a packed pair of rows)."""
    return k.shape[-1] % 128 == 0 and jnp.dtype(k.dtype).itemsize == 4


def live_rows_write(k, v, k_new, v_new, plan):
    """The leaves k, v (S, G, T, W) with row pos[s] set to k_new[s], v_new[s]
    (S, G, W) for every slot s of the plan (`live_rows_plan`); every other
    row of a live slot as it was.  The kernel, which writes nothing else,
    or the indexed update, which writes every slot's row whose position is
    in [0, T), live or not (nothing reads a dead slot's rows), as
    `_kernel_or_reference` picks them; the counter
    `cache.rows_kernel_traces` counts the layer bodies lowered with the
    kernel."""
    with _costs.part("cache"):
        return _kernel_or_reference(
            _live_pallas, _live_update,
            (k, v, k_new.astype(k.dtype), v_new.astype(v.dtype))
            + tuple(plan),
            _live_fits(k), ("cache.rows_kernel_traces",))


def _live_update(k, v, k_new, v_new, slots, count, pos):
    del slots, count
    S, G = k_new.shape[:2]
    at = (jnp.arange(S)[:, None], jnp.arange(G)[None, :], pos[:, None])
    return k.at[at].set(k_new), v.at[at].set(v_new)


def _live_kernel(slot_ref, count_ref, pos_ref, kn_ref, vn_ref, k_ref, v_ref,
                 ko_ref, vo_ref, sem):
    del k_ref, v_ref                    # the same buffers as ko_ref, vo_ref

    def copies(i):
        s = slot_ref[i]
        row = pl.ds(pos_ref[s], 1)
        return [pltpu.make_async_copy(new.at[s], out.at[s, :, row, :],
                                      sem.at[j])
                for j, (new, out) in enumerate(((kn_ref, ko_ref),
                                                (vn_ref, vo_ref)))]

    def wait_oldest():
        # a copy of the same size on the same semaphore: which one is moot
        for c in copies(0):
            c.wait()

    def start(i, carry):
        pl.when(i >= _ROWS_IN_FLIGHT)(wait_oldest)
        for c in copies(i):
            c.start()
        return carry

    def drain(i, carry):
        wait_oldest()
        return carry

    n = count_ref[0]
    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, jnp.minimum(n, _ROWS_IN_FLIGHT), drain, 0)


def _live_pallas(k, v, k_new, v_new, slots, count, pos):
    S, G, _, W = k.shape
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _live_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(),
            in_specs=[hbm] * 4,
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        # operands 5 and 6 count the three prefetched scalars: the leaves
        input_output_aliases={5: 0, 6: 1},
        name="live_rows_write",
        interpret=_interpret(),
    )(slots, count, pos, k_new.reshape(S, G, 1, W),
      v_new.reshape(S, G, 1, W), k, v)
