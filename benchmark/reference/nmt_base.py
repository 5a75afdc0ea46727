"""Plain reference of `nmt_base`: Vaswani et al. 2017 "base" encoder-
decoder, teacher-forced, float32, `jax.numpy` only.  Imports nothing of
the program.

Departures from the paper that the program makes and this file follows
(stated in configs/nmt_base.json): learned position embeddings, a
LayerNorm after each embedding sum, post-LN residual blocks, an exact
(erf) GELU in the feed-forward block, biases on every projection, an
output projection of its own (not tied to the embedding).

`forward(..., quant="int8")` is the control: every Dense computes in
int8 (weights per output channel, activations per row, symmetric), the
nearest precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter."""
    U, F, V = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    P = cfg["max_position_embeddings"]
    out = [("src_embed", (V, U), "embed:%r" % (1.0 / math.sqrt(U))),
           ("tgt_embed", (V, U), "embed:%r" % (1.0 / math.sqrt(U))),
           ("pos_embed", (P, U), "embed:0.5")]

    def ln(p):
        out.extend([(p + ".g", (U,), "gamma"), (p + ".b", (U,), "beta")])

    def dense(p, o, i):
        out.extend([(p + ".w", (o, i), "matrix"), (p + ".b", (o,), "bias")])

    def attn(p):
        for n in ("q", "k", "v", "o"):
            dense(p + "." + n, U, U)

    ln("enc_ln")
    ln("dec_ln")
    for i in range(cfg["encoder_layers"]):
        p = "enc.%d" % i
        attn(p + ".attn")
        dense(p + ".ffn1", F, U)
        dense(p + ".ffn2", U, F)
        ln(p + ".ln1")
        ln(p + ".ln2")
    for i in range(cfg["decoder_layers"]):
        p = "dec.%d" % i
        attn(p + ".self")
        attn(p + ".cross")
        dense(p + ".ffn1", F, U)
        dense(p + ".ffn2", U, F)
        ln(p + ".ln1")
        ln(p + ".ln2")
        ln(p + ".ln3")
    dense("out", V, U)
    return out


def _q8(x, axis):
    """Symmetric int8 along `axis`: (int8 values, float32 scale)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def dense(x, w, b, quant=None):
    """x (..., in) @ w(out, in)^T + b."""
    if quant == "int8":
        xq, xs = _q8(x, -1)
        wq, ws = _q8(w, -1)
        acc = jnp.einsum("...i,oi->...o", xq.astype(jnp.int32),
                         wq.astype(jnp.int32))
        return acc.astype(jnp.float32) * xs * ws[:, 0] + b
    return jnp.einsum("...i,oi->...o", x, w) + b


def layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def attention(p, pre, xq, xkv, mask, heads, quant):
    """Multi-head attention; `mask` (B, 1, Tq, Tk) additive."""
    B, Tq, U = xq.shape
    Tk = xkv.shape[1]
    d = U // heads

    def split(t, T):
        return t.reshape(B, T, heads, d).transpose(0, 2, 1, 3)

    q = split(dense(xq, p[pre + ".q.w"], p[pre + ".q.b"], quant), Tq)
    k = split(dense(xkv, p[pre + ".k.w"], p[pre + ".k.b"], quant), Tk)
    v = split(dense(xkv, p[pre + ".v.w"], p[pre + ".v.b"], quant), Tk)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d) + mask
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, Tq, U)
    return dense(ctx, p[pre + ".o.w"], p[pre + ".o.b"], quant)


def ffn(p, pre, x, quant):
    h = jax.nn.gelu(dense(x, p[pre + ".ffn1.w"], p[pre + ".ffn1.b"], quant),
                    approximate=False)
    return dense(h, p[pre + ".ffn2.w"], p[pre + ".ffn2.b"], quant)


def embed(p, table, ln, tokens, U):
    T = tokens.shape[1]
    x = p[table][tokens] * math.sqrt(U) + p["pos_embed"][:T][None]
    return layer_norm(x, p[ln + ".g"], p[ln + ".b"])


def forward(p, cfg, src, src_len, tgt_in, quant=None):
    """Logits (B, Tt, V) of the decoder fed `tgt_in` (B, Tt) after the
    encoder read `src` (B, Ts) of which `src_len` (B,) tokens are real."""
    with jax.default_matmul_precision("highest"):
        U, H = cfg["d_model"], cfg["num_heads"]
        Ts, Tt = src.shape[1], tgt_in.shape[1]
        keep = jnp.arange(Ts)[None, :] < src_len[:, None]          # (B, Ts)
        mem_mask = jnp.where(keep, 0.0, -1e9)[:, None, None, :]
        x = embed(p, "src_embed", "enc_ln", src, U)
        for i in range(cfg["encoder_layers"]):
            pre = "enc.%d" % i
            h = attention(p, pre + ".attn", x, x, mem_mask, H, quant)
            x = layer_norm(x + h, p[pre + ".ln1.g"], p[pre + ".ln1.b"])
            x = layer_norm(x + ffn(p, pre, x, quant),
                           p[pre + ".ln2.g"], p[pre + ".ln2.b"])
        memory = x
        causal = jnp.where(jnp.arange(Tt)[None, :] <= jnp.arange(Tt)[:, None],
                           0.0, -1e9)[None, None]
        y = embed(p, "tgt_embed", "dec_ln", tgt_in, U)
        for i in range(cfg["decoder_layers"]):
            pre = "dec.%d" % i
            h = attention(p, pre + ".self", y, y, causal, H, quant)
            y = layer_norm(y + h, p[pre + ".ln1.g"], p[pre + ".ln1.b"])
            h = attention(p, pre + ".cross", y, memory, mem_mask, H, quant)
            y = layer_norm(y + h, p[pre + ".ln2.g"], p[pre + ".ln2.b"])
            y = layer_norm(y + ffn(p, pre, y, quant),
                           p[pre + ".ln3.g"], p[pre + ".ln3.b"])
        return dense(y, p["out.w"], p["out.b"], quant)


def served_gaps(p, cfg, src, src_len, tgt_in, served, n_served, quant=None):
    """For each row, at each served position t < n_served: how far the
    served token's reference logit lies below the reference's best.
    With `quant`, the control: the token read is the one the lower
    precision puts first, its gap read in the float32 logits.
    Returns gaps (B, Tt) with 0 beyond n_served."""
    ref = forward(p, cfg, src, src_len, tgt_in)
    if quant is not None:
        served = jnp.argmax(forward(p, cfg, src, src_len, tgt_in, quant), -1)
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    live = jnp.arange(tgt_in.shape[1])[None, :] < n_served[:, None]
    return jnp.where(live, best - got, 0.0)
