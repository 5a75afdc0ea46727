"""Plain reference of `ouro_2_6b`: Ouro-2.6B, a looped language model (one
stack of layers run `total_ut_steps` times a token over the same weights,
an exit gate after every pass), float32, `jax.numpy` only.  Imports nothing
of the program.  No cache: every pass is causal attention over the whole
sequence.

No biases but the gate's, eps `rms_norm_eps`.  N(x; g) = g x / sqrt(mean(x^2)
+ eps).  D hidden, H heads of d (every query head has keys and values of its
own), F the feed-forward width, L layers, R passes.

    h = E[token]
    for r in 0..R-1:                       # the SAME L layers every pass
      for l in 0..L-1:
        a = N(h; ln1_l);  q, k, v = Wq_l a, Wk_l a, Wv_l a  (H heads of d)
        q, k take rotary positions: rotate-half over the whole head, theta
          `rope_theta`; position t turns pair (i, i + d/2) by t theta^(-2i/d),
          in every pass alike
        o = softmax(q . k / sqrt(d)) v, causal, over what THIS pass of THIS
          layer gives at positions s <= t
        h = h + N(Wo_l o; ln2_l)
        m = N(h; ln3_l);  h = h + N(Wd_l (silu(Wg_l m) * Wu_l m); ln4_l)
      h = N(h; norm)                       # closes every pass, feeds the next
      lam_r = sigmoid(gate.w . h + gate.b)
    p_r = lam_r prod_{j<r} (1 - lam_j) for r < R-1, p_{R-1} what is left
    r* = the first r with p_0 + .. + p_r >= early_exit_threshold; the last
         pass where the threshold is 1 or more, by definition
    logits = Whead h^(r*)                  # h^(r) is already normed

All R passes are always computed: a later token's pass r attends over this
token's pass r.  `assumed` and `departures` are listed in
configs/ouro_2_6b.json.  A request is its prompt followed by the tokens
served: the logit row at position n_prompt - 1 + j is read against served
token j.  Attention runs in blocks of query rows, so that no (H, T, T) array
is ever whole beside the float32 weights.

`quant="int8"` is the control: every matrix product with a weight computes
in int8 (weights per output channel, activations per row, symmetric), the
nearest precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 128


def sizes(cfg):
    """The sizes the equations use, under short names."""
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "d": cfg["head_dim"], "F": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "R": cfg["total_ut_steps"],
            "V": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "exit": float(cfg["early_exit_threshold"])}


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter; a layer's are stacked
    on a leading axis of the L layers."""
    z = sizes(cfg)
    D, L, F, Hd = z["D"], z["L"], z["F"], z["H"] * z["d"]
    return [
        ("embed", (z["V"], D), "embed:1.0"),
        ("ln1", (L, D), "gamma"),
        ("wq", (L, Hd, D), "matrix"),
        ("wk", (L, Hd, D), "matrix"),
        ("wv", (L, Hd, D), "matrix"),
        ("wo", (L, D, Hd), "matrix"),
        ("ln2", (L, D), "gamma"),
        ("ln3", (L, D), "gamma"),
        ("wg", (L, F, D), "matrix"),
        ("wu", (L, F, D), "matrix"),
        ("wd", (L, D, F), "matrix"),
        ("ln4", (L, D), "gamma"),
        ("norm", (D,), "gamma"),
        ("gate.w", (1, D), "matrix"),
        ("gate.b", (1,), "bias"),
        ("head", (z["V"], D), "matrix"),
    ]


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def dense(x, w, quant=None):
    """x (..., in) @ w (out, in)^T."""
    if quant == "int8":
        xq, xs = _q8(x, -1)
        wq, ws = _q8(w, -1)
        acc = jnp.einsum("...i,oi->...o", xq.astype(jnp.int32),
                         wq.astype(jnp.int32))
        return acc.astype(jnp.float32) * xs * ws[:, 0]
    return jnp.einsum("...i,oi->...o", x, w)


def norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rotary(x, z):
    """x (T, H, d) at positions 0..T-1: pair (i, i + d/2) turns by
    t theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    inv = z["theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def layer(h, p, l, z, quant):
    """Layer l over h (T, D), one pass."""
    T, H, d = h.shape[0], z["H"], z["d"]
    rb = math.gcd(T, ROW_BLOCK)
    pos = jnp.arange(T)
    a = norm(h, p["ln1"][l], z["eps"])
    heads = lambda w: dense(a, w[l], quant).reshape(T, H, d)
    q, k, v = rotary(heads(p["wq"]), z), rotary(heads(p["wk"]), z), \
        heads(p["wv"])

    def rows(r0):
        at = r0 + jnp.arange(rb)
        s = jnp.einsum("qhd,khd->hqk", q[at], k) / math.sqrt(d)
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, jnp.arange(0, T, rb)).reshape(T, H * d)
    h = h + norm(dense(o, p["wo"][l], quant), p["ln2"][l], z["eps"])
    m = norm(h, p["ln3"][l], z["eps"])
    f = dense(jax.nn.silu(dense(m, p["wg"][l], quant))
              * dense(m, p["wu"][l], quant), p["wd"][l], quant)
    return h + norm(f, p["ln4"][l], z["eps"])


def exit_pass(lam, threshold):
    """lam (R, T), the gates of every pass -> (T,) the pass read out."""
    R = lam.shape[0]
    if threshold >= 1.0:
        return jnp.full(lam.shape[1:], R - 1, jnp.int32)
    at = jnp.full(lam.shape[1:], R - 1, jnp.int32)
    total = jnp.zeros(lam.shape[1:], jnp.float32)
    left = jnp.ones(lam.shape[1:], jnp.float32)
    for r in range(R - 1):
        total = total + lam[r] * left
        left = left * (1.0 - lam[r])
        at = jnp.where((at == R - 1) & (total >= threshold), r, at)
    return at


def passes(p, cfg, tokens, quant=None):
    """(hs (R, T, D) the closed state of every pass, lam (R, T) its gate)
    of one sequence `tokens` (T,)."""
    z = sizes(cfg)

    def one_pass(h, _):
        h = jax.lax.fori_loop(0, z["L"],
                              lambda l, x: layer(x, p, l, z, quant), h)
        h = norm(h, p["norm"], z["eps"])
        lam = jax.nn.sigmoid(dense(h, p["gate.w"], quant)[:, 0]
                             + p["gate.b"][0])
        return h, (h, lam)

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(one_pass, p["embed"][tokens], None,
                            length=z["R"])[1]


def forward(p, cfg, tokens, quant=None):
    """Logits (T, V) of one sequence `tokens` (T,)."""
    hs, lam = passes(p, cfg, tokens, quant)
    at = exit_pass(lam, sizes(cfg)["exit"])
    h = jnp.take_along_axis(hs, at[None, :, None], 0)[0]
    with jax.default_matmul_precision("highest"):
        return dense(h, p["head"], quant)


def served_gaps(p, cfg, src, src_len, tgt_in, served, n_served, quant=None):
    """For each row, at each served position j < n_served: how far the served
    token's reference logit lies below the reference's best.  The sequence of a
    row is its prompt (`src[:src_len]`) followed by the tokens served before
    the last (`tgt_in[1:]`; its first entry, a start token, belongs to models
    that have one).  With `quant`, the control: the token read is the one the
    lower precision puts first, its gap read in the float32 logits.
    Returns gaps (B, Tt) with 0 beyond n_served."""
    Tt = tgt_in.shape[1]
    j = jnp.arange(Tt)

    def one(src_r, n, tgt_r, served_r, ns):
        seq = jnp.where(j < n, src_r[jnp.minimum(j, src_r.shape[0] - 1)],
                        tgt_r[jnp.clip(j - n + 1, 0, Tt - 1)])
        at = jnp.clip(n - 1 + j, 0, Tt - 1)         # the row that predicts j
        ref = forward(p, cfg, seq)[at]
        if quant is not None:
            served_r = jnp.argmax(forward(p, cfg, seq, quant)[at], -1)
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, served_r[:, None], -1)[:, 0]
        return jnp.where(j < ns, best - got, 0.0)

    return jnp.stack([one(src[r], src_len[r], tgt_in[r], served[r],
                          n_served[r]) for r in range(src.shape[0])])
