"""Plain reference of `keye_vl2_30b_a3b`: the language model of
Keye-VL-2.0-30B-A3B as one chip of its eight-chip deployment holds it,
float32, `jax.numpy` only.  Imports nothing of the program.

The layer, with h_t the residual stream at position t (eps 1e-6, no bias):

1. x = RMSNorm(h_t; g1).  q = Wq x (heads x head_dim), k = Wk x, v = Wv x
   (kv heads x head_dim); q, k get a per-head RMSNorm (gq, gk), then rotary
   positions (theta, rotate-half over the whole head).
2. Indexer: qI = WqI x (index heads x index dim), kI_s = LayerNorm(WkI x_s)
   (one head), w = Ww x; rotary on all of qI and kI.
   I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <= t.  S_t = the
   min(top_k, t + 1) positions with the largest I[t, s], ties to the lower s.
3. q head i reads kv head i // group: softmax over s in S_t of q.k / sqrt(d),
   o = sum a v;  h += Wo [o_1 .. o_H].
4. x2 = RMSNorm(h; g2).  r = softmax(Wr x2) over all experts; T = the top
   `num_experts_per_tok`; p_e = r_e / sum_T r.  h += sum over e in T that this
   chip holds of p_e Wd_e (silu(Wg_e x2) * Wu_e x2).  Terms of experts held
   elsewhere are left out: their chips add them in the deployment.
5. After the last layer: RMSNorm, logits over the vocabulary rows held.

`assumed` and `departures` are listed in configs/keye_vl2_30b_a3b.json.
A request is its prompt followed by the tokens served: the logit row at
position n_prompt - 1 + j is read against served token j.  The forward runs in
blocks of queries, so that no (T, T) matrix per head is ever whole.

`quant="int8"` is the control: every matrix product with a weight computes in
int8 (weights per output channel, activations per row, symmetric), the nearest
precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512
# The q/k norm scales are drawn around 0.42: q.k / sqrt(head_dim) of two
# normed heads of 128 then has a standard deviation near 2, and the best of
# 2048 keys holds some 7 % of a softmax, the spread a trained model's logits
# have.  Around 1.0 it would be 11.3: attention would be a hard argmax over
# random keys, and any rounding that swaps the two best keys of a head (or a
# key at the selection's boundary) would put another value row in its place.
QK_SCALE = "gamma:0.42"


def sizes(cfg):
    """The sizes the equations use, under short names."""
    sa = cfg["sa_config"]
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "NL": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "E": cfg["num_experts"], "EH": cfg["num_local_experts"],
            "E0": cfg["first_local_expert"],
            "F": cfg["moe_intermediate_size"],
            "TOPE": cfg["num_experts_per_tok"],
            "IH": sa["indexer_num_heads"], "ID": sa["indexer_head_dim"],
            "TOPK": sa["topk"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter.  A layer's
    parameters are stacked on a leading axis of layers."""
    z = sizes(cfg)
    D, NL, dh = z["D"], z["NL"], z["dh"]
    return [
        ("embed", (z["V"], D), "embed:1.0"),
        ("ln1.g", (NL, D), "gamma"),
        ("attn.wq", (NL, z["H"] * dh, D), "matrix"),
        ("attn.wk", (NL, z["KV"] * dh, D), "matrix"),
        ("attn.wv", (NL, z["KV"] * dh, D), "matrix"),
        ("attn.wo", (NL, D, z["H"] * dh), "matrix"),
        ("attn.gq", (NL, dh), QK_SCALE),
        ("attn.gk", (NL, dh), QK_SCALE),
        ("idx.wq", (NL, z["IH"] * z["ID"], D), "matrix"),
        ("idx.wk", (NL, z["ID"], D), "matrix"),
        ("idx.ww", (NL, z["IH"], D), "matrix"),
        ("idx.ln.g", (NL, z["ID"]), "gamma"),
        ("idx.ln.b", (NL, z["ID"]), "beta"),
        ("ln2.g", (NL, D), "gamma"),
        ("moe.router", (NL, z["E"], D), "matrix"),
        ("moe.wg", (NL, z["EH"], z["F"], D), "matrix"),
        ("moe.wu", (NL, z["EH"], z["F"], D), "matrix"),
        ("moe.wd", (NL, z["EH"], D, z["F"]), "matrix"),
        ("norm.g", (D,), "gamma"),
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


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rotary(x, pos, theta):
    """x (T, heads, d) at positions pos (T,): rotate-half over all d."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]           # (T, d/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def select(scores, pos_q, k):
    """scores (Tq, Tk) of queries at positions pos_q against keys 0..Tk-1:
    a mask of, for each query, the min(k, pos + 1) causal keys with the
    largest score, ties to the lower key.  By a stable sort."""
    Tk = scores.shape[1]
    causal = jnp.arange(Tk)[None, :] <= pos_q[:, None]
    order = jnp.argsort(jnp.where(causal, -scores, jnp.inf), axis=-1,
                        stable=True)                    # best first
    rank = jnp.argsort(order, axis=-1, stable=True)     # rank of each key
    return causal & (rank < k)


def experts(x2, p, l, z, quant):
    """Step 4 for tokens x2 (T, D): every held expert over every token,
    weighted by the renormalised gate where the expert is among the token's
    top ones and by zero elsewhere."""
    r = jax.nn.softmax(dense(x2, p["moe.router"][l], quant), axis=-1)
    top_r, top_e = jax.lax.top_k(r, z["TOPE"])
    gate = top_r / jnp.sum(top_r, -1, keepdims=True)               # (T, 8)
    out = jnp.zeros_like(x2)
    for e in range(z["EH"]):
        g_e = jnp.sum(jnp.where(top_e == z["E0"] + e, gate, 0.0), -1)
        a = jax.nn.silu(dense(x2, p["moe.wg"][l, e], quant)) * \
            dense(x2, p["moe.wu"][l, e], quant)
        out = out + g_e[:, None] * dense(a, p["moe.wd"][l, e], quant)
    return out


def layer(h, p, l, z, quant):
    """One layer over the whole sequence h (T, D), attention in blocks of
    queries."""
    T = h.shape[0]
    blk = min(Q_BLOCK, T)
    if T % blk:
        raise ValueError("%d positions are no whole number of query blocks "
                         "of %d" % (T, blk))
    H, KV, dh = z["H"], z["KV"], z["dh"]
    pos = jnp.arange(T)
    x = rms_norm(h, p["ln1.g"][l], z["eps"])
    q = dense(x, p["attn.wq"][l], quant).reshape(T, H, dh)
    k = dense(x, p["attn.wk"][l], quant).reshape(T, KV, dh)
    v = dense(x, p["attn.wv"][l], quant).reshape(T, KV, dh)
    q = rotary(rms_norm(q, p["attn.gq"][l], z["eps"]), pos, z["theta"])
    k = rotary(rms_norm(k, p["attn.gk"][l], z["eps"]), pos, z["theta"])
    qi = dense(x, p["idx.wq"][l], quant).reshape(T, z["IH"], z["ID"])
    ki = layer_norm(dense(x, p["idx.wk"][l], quant), p["idx.ln.g"][l],
                    p["idx.ln.b"][l], z["eps"])
    w = dense(x, p["idx.ww"][l], quant)                            # (T, IH)
    qi = rotary(qi, pos, z["theta"])
    ki = rotary(ki[:, None, :], pos, z["theta"])[:, 0]
    qg = q.reshape(T, KV, H // KV, dh)

    def block(q0):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, q0, blk, 0)
        pq = q0 + jnp.arange(blk)
        s_i = jnp.einsum("qjd,kd->qjk", sl(qi), ki)
        score = jnp.einsum("qjk,qj->qk", jax.nn.relu(s_i), sl(w))
        mask = select(score, pq, z["TOPK"])                        # (bq, T)
        s = jnp.einsum("qghd,kgd->qghk", sl(qg), k) / math.sqrt(dh)
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("qghk,kgd->qghd", a, v).reshape(blk, H * dh)

    o = jax.lax.map(block, jnp.arange(0, T, blk)).reshape(T, H * dh)
    h = h + dense(o, p["attn.wo"][l], quant)
    return h + experts(rms_norm(h, p["ln2.g"][l], z["eps"]), p, l, z, quant)


def forward(p, cfg, tokens, quant=None):
    """Logits (T, V) of one sequence `tokens` (T,), T a multiple of the
    query block (or shorter than one)."""
    z = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        h = jax.lax.fori_loop(0, z["NL"],
                              lambda l, h: layer(h, p, l, z, quant), h)
        return dense(rms_norm(h, p["norm.g"], z["eps"]), p["head"], quant)


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
