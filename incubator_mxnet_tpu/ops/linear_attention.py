"""The gated delta rule (Gated DeltaNet; Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) and the short causal convolution in front of it.

A head keeps a state S (dk, dv), float32.  Token t brings a query and a
key q_t, k_t (dk), a value v_t (dv), a log decay g_t <= 0 and a writing
strength beta_t in (0, 1):

    S~  = exp(g_t) S_{t-1}                  the memory fades
    u_t = beta_t (v_t - S~^T k_t)           what k_t does not yet recall of v_t
    S_t = S~ + k_t u_t^T                    is written under k_t
    o_t = S_t^T q_t

Three forms, one result:

- `gated_delta_scan`: the recurrence as written, a `lax.scan` over
  positions.  What the tests compare the other two with.
- `gated_delta_chunked`: a prompt in chunks of C positions (the
  decay-aware WY form).  With gamma_i the sum of g up to i inside a chunk
  and S_0 the state the chunk starts from, the u of a chunk solve
  (I + A) U = beta V - (beta e^gamma K) S_0, where
  A_ij = beta_i e^(gamma_i - gamma_j) k_i.k_j below the diagonal: one
  triangular inverse a chunk, every chunk at once
  (`_unit_lower_inverse`), and then a scan over
  the chunks that carries S and is five small matrix products a chunk
  (`_chunk_scan`; on a TPU the kernel `gated_delta_chunk`).  Every exponent
  is a decay from an earlier position to a later one, so none overflows.
- `gated_delta_step`: one token a slot against the slot cache's state
  leaf, read once and written once IN PLACE (on a TPU the kernel
  `gated_delta_step`, which aliases the donated leaf: a step moves the
  2 x 64 KB a head of the layer it is at and touches no other layer's).

`valid_len`: a prompt is padded to its bucket, and a decoder-only stream
starts by reading the prompt's last token AGAIN (`serving/generation.py`).
A recurrent state cannot shrug either off as cached rows do, so the
chunked form stops the recurrence short: positions >= valid_len - 1 decay
nothing and write nothing (g = 0, beta = 0), and the state handed over is
the one after valid_len - 1 tokens.  `causal_conv`'s rows follow the same
rule.

The kernels are chosen where the step is LOWERED
(`lax.platform_dependent`), as `ops.attention.decode_attention` is: a
compile for a TPU sees them, the CPU runs the `jax.numpy` forms, and
`MXNET_PALLAS_INTERPRET` runs the kernels themselves in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import costs as _costs
from .attention import _interpret

__all__ = ["gated_delta_scan", "gated_delta_chunked", "gated_delta_step",
           "causal_conv", "causal_conv_step"]

_HI = jax.lax.Precision.HIGHEST


# -- the recurrence as written -------------------------------------------

def gated_delta_scan(q, k, v, g, beta, state=None):
    """q, k (T, H, dk), v (T, H, dv), g, beta (T, H); state (H, dk, dv) or
    None for zeros.  Returns (o (T, H, dv), the state after T tokens), all
    float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), f32)

    def one(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=_HI))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    state, o = jax.lax.scan(one, state.astype(f32), (q, k, v, g, beta))
    return o, state


# -- a prompt, in chunks ---------------------------------------------------

def _chunk_scan_xla(ut, w, qg, m, krt, decay, state):
    """The scan over chunks.  Per chunk c (leading axis) and head h:
    U = ut - w S;  O = qg S + m U;  S <- decay S + krt U.
    ut (N, H, C, dv); w, qg (N, H, C, dk); m (N, H, C, C); krt
    (N, H, dk, C); decay (N, H); state (H, dk, dv)."""
    def one(S, x):
        ut_c, w_c, qg_c, m_c, krt_c, d_c = x
        u = ut_c - jnp.einsum("hck,hkv->hcv", w_c, S, precision=_HI)
        o = jnp.einsum("hck,hkv->hcv", qg_c, S, precision=_HI) \
            + jnp.einsum("hcd,hdv->hcv", m_c, u, precision=_HI)
        S = S * d_c[:, None, None] \
            + jnp.einsum("hkc,hcv->hkv", krt_c, u, precision=_HI)
        return S, o

    state, o = jax.lax.scan(one, state, (ut, w, qg, m, krt, decay))
    return o, state


def _chunk_kernel(ut_ref, w_ref, qg_ref, m_ref, krt_ref, d_ref, s0_ref,
                  o_ref, s_ref, acc):
    c = pl.program_id(1)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)

    @pl.when(c == 0)
    def _init():
        acc[...] = s0_ref[0]

    S = acc[...]
    u = ut_ref[0, 0] - dot(w_ref[0, 0], S)
    o_ref[0, 0] = dot(qg_ref[0, 0], S) + dot(m_ref[0, 0], u)
    acc[...] = S * d_ref[0, 0] + dot(krt_ref[0, 0], u)

    @pl.when(c == pl.num_programs(1) - 1)
    def _done():
        s_ref[0] = acc[...]


def _chunk_scan_pallas(ut, w, qg, m, krt, decay, state):
    """`_chunk_scan_xla` as the kernel `gated_delta_chunk`: grid (heads,
    chunks), the head's state in VMEM from its first chunk to its last."""
    N, H, C, dv = ut.shape
    dk = w.shape[-1]
    # one decay a (chunk, head), laid along the lanes of a state row
    d = jnp.broadcast_to(decay[:, :, None, None], (N, H, 1, dv))
    blk = lambda *shape: pl.BlockSpec((1, 1) + shape,
                                      lambda h, c: (c, h, 0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        grid=(H, N),
        in_specs=[blk(C, dv), blk(C, dk), blk(C, dk), blk(C, C), blk(dk, C),
                  blk(1, dv),
                  pl.BlockSpec((1, dk, dv), lambda h, c: (h, 0, 0))],
        out_specs=[blk(C, dv),
                   pl.BlockSpec((1, dk, dv), lambda h, c: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, H, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="gated_delta_chunk",
    )(ut, w, qg, m, krt, d, state)
    return o, state


def _chunk_scan(*args):
    if _interpret():
        return _chunk_scan_pallas(*args)
    return jax.lax.platform_dependent(*args, tpu=_chunk_scan_pallas,
                                      default=_chunk_scan_xla)


def _unit_lower_inverse(a):
    """(I + A)^-1 for A (..., C, C) strictly lower triangular, C a power of
    two: the inverses of the diagonal blocks of size 1, 2, 4, ... C, each
    from the two of half its size, [[P, 0], [R, Q]]^-1 = [[P^-1, 0],
    [-Q^-1 R P^-1, Q^-1]].  Forward substitution's own arithmetic, as
    log2(C) rounds of small matrix products (XLA's triangular solve on a
    v5e inverts 512 blocks of 64 in 1.37 ms, a row at a time)."""
    C = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (C, 1, 1), a.dtype)       # the C blocks of size 1
    b = 1
    while b < C:
        n = C // (2 * b)
        # R of each of the n diagonal blocks of size 2b: block row 2i + 1,
        # block column 2i (a select and a sum: no gather, exact)
        r = a.reshape(lead + (n, 2, b, n, 2, b))[..., :, 1, :, :, 0, :]
        r = jnp.sum(jnp.where(jnp.eye(n, dtype=bool)[:, None, :, None],
                              r, 0.0), axis=-2)
        d = inv.reshape(lead + (n, 2, b, b))
        p_inv, q_inv = d[..., 0, :, :], d[..., 1, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", q_inv, r, p_inv,
                          precision=_HI)
        inv = jnp.concatenate(
            [jnp.concatenate([p_inv, jnp.zeros_like(p_inv)], -1),
             jnp.concatenate([low, q_inv], -1)], -2)
        b *= 2
    return inv.reshape(a.shape)


def gated_delta_chunked(q, k, v, g, beta, valid_len=None, chunk=64):
    """The recurrence over a prompt, from a zero state, in chunks of `chunk`
    positions (a power of two).  Shapes as `gated_delta_scan`; T need be no
    multiple of the chunk.  With
    `valid_len` (a scalar), positions >= valid_len - 1 leave the state as
    it is, so the state returned is the one after valid_len - 1 tokens;
    their outputs read that state and mean nothing.  Returns (o (T, H, dv),
    state (H, dk, dv)) float32."""
    with _costs.part("state"):
        f32 = jnp.float32
        T, H, dk = q.shape
        dv = v.shape[2]
        C = int(chunk)
        if C & (C - 1):
            raise ValueError("a chunk of %d positions is no power of two" % C)
        N = -(-T // C)
        g, beta = g.astype(f32), beta.astype(f32)
        if valid_len is not None:
            on = (jnp.arange(T) < valid_len - 1)[:, None]
            g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)
        pad = N * C - T                     # padded positions: g = 0, beta = 0

        def chunks(a):                      # (T, H, ...) -> (N, H, C, ...)
            a = jnp.pad(a.astype(f32), [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            a = a.reshape((N, C) + a.shape[1:])
            return jnp.moveaxis(a, 2, 1)

        q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
        gam = jnp.cumsum(g, axis=-1)                                # (N, H, C)
        # e^(gamma_i - gamma_j) for j <= i, 0 above the diagonal
        low = jnp.tril(jnp.ones((C, C), bool))
        dec = jnp.where(low, jnp.exp(jnp.where(
            low, gam[..., :, None] - gam[..., None, :], 0.0)), 0.0)
        kk = jnp.einsum("nhik,nhjk->nhij", k, k, precision=_HI)
        a = jnp.tril(beta[..., :, None] * dec * kk, -1)
        rhs = jnp.concatenate(
            [beta[..., None] * v,
             (beta * jnp.exp(gam))[..., None] * k], axis=-1)
        sol = jnp.einsum("nhij,nhjk->nhik", _unit_lower_inverse(a), rhs,
                         precision=_HI)
        ut, w = sol[..., :dv], sol[..., dv:]
        qg = q * jnp.exp(gam)[..., None]
        m = jnp.einsum("nhik,nhjk->nhij", q, k, precision=_HI) * dec
        krt = jnp.swapaxes(k * jnp.exp(gam[..., -1:] - gam)[..., None], -1, -2)
        decay = jnp.exp(gam[..., -1])
        o, state = _chunk_scan(ut, w, qg, m, krt, decay,
                               jnp.zeros((H, dk, dv), f32))
        o = jnp.moveaxis(o, 1, 2).reshape(N * C, H, dv)[:T]
        return o, state


# -- one token a slot, against the slot cache ------------------------------

def _step_xla(layer, q, k, v, alpha, beta, states):
    S = jnp.take(states, layer[0], axis=1)              # (B, H, dk, dv)
    S = S * alpha[:, :, None, None]
    u = beta[:, :, None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k,
                                           precision=_HI))
    S = S + k[..., :, None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", S, q, precision=_HI)
    return o, jax.lax.dynamic_update_slice_in_dim(
        states, S[:, None], layer[0], axis=1)


def _step_kernel(layer_ref, qt_ref, kt_ref, v_ref, a_ref, b_ref, s_ref,
                 o_ref, so_ref):
    del layer_ref                       # read by the index maps
    for h in range(s_ref.shape[2]):
        kc = kt_ref[0, :, h:h + 1]                              # (dk, 1)
        qc = qt_ref[0, :, h:h + 1]
        S = s_ref[0, 0, h] * a_ref[0, h:h + 1, :]               # (dk, dv)
        u = b_ref[0, h:h + 1, :] * (
            v_ref[0, h:h + 1, :] - jnp.sum(S * kc, axis=0, keepdims=True))
        S = S + kc * u
        o_ref[0, h:h + 1, :] = jnp.sum(S * qc, axis=0, keepdims=True)
        so_ref[0, 0, h] = S


def _step_fits(states):
    """Whether the step kernel takes the leaf (B, layers, H, dk, dv): one
    slot's heads of one layer are a block, in VMEM twice over as input and
    twice as output."""
    _, _, H, dk, dv = states.shape
    return dk % 8 == 0 and dv % 128 == 0 and 4 * H * dk * dv * 4 <= 48 << 20


def _step_pallas(layer, q, k, v, alpha, beta, states):
    """The kernel `gated_delta_step`: one slot a grid step, all its heads.
    The layer comes as a prefetched scalar and only enters the index map of
    the state leaf, which is aliased to the result: the blocks of this
    layer are read and written in place, nothing else of the leaf moves."""
    B, _, H, dk, dv = states.shape
    # queries and keys come with dk on the sublanes, as the products want
    # them (a column against the rows of S); values, decay and strength
    # along the lanes.  All of it is a hundredth of the state's bytes.
    cols = lambda a: a.transpose(0, 2, 1)                       # (B, dk, H)
    wide = lambda a: jnp.broadcast_to(a[:, :, None], (B, H, dv))
    vec = lambda *shape: pl.BlockSpec((1,) + shape,
                                      lambda b, layer: (b, 0, 0))
    leaf = pl.BlockSpec((1, 1, H, dk, dv),
                        lambda b, layer: (b, layer[0], 0, 0, 0))
    o, states = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[vec(dk, H), vec(dk, H), vec(H, dv), vec(H, dv),
                      vec(H, dv), leaf],
            out_specs=[vec(H, dv), leaf],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        # operand 6 counts the prefetched scalar: the state leaf
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20),
        interpret=_interpret(),
        name="gated_delta_step",
    )(layer, cols(q), cols(k), v, wide(alpha), wide(beta), states)
    return o, states


def gated_delta_step(q, k, v, g, beta, states, layer):
    """One token a slot.  q, k (B, H, dk), v (B, H, dv), g, beta (B, H);
    `states` (B, layers, H, dk, dv) float32 is the slot cache's leaf and
    `layer` (a traced scalar) the layer whose state this step reads and
    rewrites.  Returns (o (B, H, dv) float32, the leaf with that layer's
    states advanced one token)."""
    with _costs.part("state"):
        f32 = jnp.float32
        args = (jnp.asarray(layer, jnp.int32).reshape(1), q.astype(f32),
                k.astype(f32), v.astype(f32), jnp.exp(g.astype(f32)),
                beta.astype(f32), states)
        if _interpret():
            return _step_pallas(*args)
        if not _step_fits(states):
            return _step_xla(*args)
        return jax.lax.platform_dependent(*args, tpu=_step_pallas,
                                          default=_step_xla)


# -- the short causal convolution -------------------------------------------

def causal_conv(x, w, valid_len=None):
    """Depthwise causal convolution over a prompt: x (T, C), w (C, K):
    y_t = sum_j w[:, j] x_(t-K+1+j), zeros before the start.  Returns
    (y (T, C) float32, the K - 1 input rows BEFORE position valid_len - 1
    (before T without it), in x's type: what `causal_conv_step` needs to
    go on from there)."""
    with _costs.part("state"):
        T, C = x.shape
        K = w.shape[1]
        xp = jnp.pad(x, [(K - 1, 0), (0, 0)])
        wf = w.astype(jnp.float32)
        y = sum(xp[j:j + T].astype(jnp.float32) * wf[:, j] for j in range(K))
        # rows [end - K + 1, end)
        end = T if valid_len is None else valid_len - 1
        rows = jax.lax.dynamic_slice_in_dim(xp, jnp.maximum(end, 0), K - 1, 0)
        return y, rows


def causal_conv_step(x, rows, w):
    """One position a slot: x (B, C) the new input, rows (B, K - 1, C) the
    inputs before it.  Returns (y (B, C) float32, the rows for the next
    position)."""
    with _costs.part("state"):
        full = jnp.concatenate([rows, x[:, None].astype(rows.dtype)], axis=1)
        y = jnp.einsum("bkc,ck->bc", full.astype(jnp.float32),
                       w.astype(jnp.float32))
        return y, full[:, 1:]
