"""Plain reference of `qwen3_next_80b_a3b`: Qwen3-Next-80B-A3B-Instruct as
one chip of its eight-chip deployment holds it, float32, `jax.numpy` only.
Imports nothing of the program.  No cache, no chunks: the recurrence is a
scan over positions.

Pre-norm, no biases, eps 1e-6.  N(x; w) = x / sqrt(mean(x^2) + eps) (1 + w).
Layer i (from 0) is full attention where (i + 1) mod interval = 0, else
Gated DeltaNet; h <- h + mixer(N(h)); h <- h + moe(N(h)); after the last
layer N and the logits over the vocabulary rows held.

**Gated attention.**  x = N(h).  [q_h, gate_h] = Wq x a head (2 x head_dim
each), k, v over the key/value heads.  q_h, k_g <- N over the head (own
scales), rotary positions on the first `partial_rotary_factor` of the head
(rotate-half within them, theta), the other dims pass.  Head i reads
key/value head i // group: causal softmax of q.k / sqrt(head_dim);
o <- o * sigmoid(gate); h += Wo o.

**Gated DeltaNet.**  From x: q, k (key heads x key dim), v, z (value heads x
value dim), b, a (value heads).  [q, k, v] pass a causal depthwise
convolution of width `linear_conv_kernel_dim` (no bias) and silu.  q and k
are L2-normalised a head (eps 1e-6), each repeated to value heads / key
heads value heads (head i -> value heads r i .. r i + r - 1), q scaled by
key_dim^-1/2.  beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
alpha = exp(g).  A value head's state S (key dim x value dim) from zero:

    S~ = alpha_t S_(t-1);  u_t = beta_t (v_t - S~^T k_t);
    S_t = S~ + k_t u_t^T;  o_t = S_t^T q_t.

o <- w o / sqrt(mean(o^2) + eps) * silu(z) a head (scale w, not 1 + w);
h += Wo o.

**Experts.**  x2 = N(h).  r = softmax(Wr x2) over all experts; T = the top
`num_experts_per_tok`; p_e = r_e / sum_T r.  h += sum over e in T that
this chip holds of p_e Wd_e (silu(Wg_e x2) * Wu_e x2), plus
sigmoid(w_s . x2) * shared(x2), a SwiGLU of `shared_expert_intermediate_size`
that every chip computes alike.  Terms of experts held elsewhere are left
out: their chips add them in the deployment.

`assumed` and `departures` are listed in configs/qwen3_next_80b_a3b.json
(no multi-token-prediction module; 40 of 48 layers, 448 of 512 experts and
7/8 of the vocabulary are on other chips).  A request is its prompt followed
by the tokens served: the logit row at position n_prompt - 1 + j is read
against served token j.

`quant="int8"` is the control: every matrix product with a weight computes
in int8 (weights per output channel, activations per row, symmetric), the
nearest precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# 1 + w around 0.42, as configs/qwen3_next_80b_a3b.json `assumed` argues
QK_SCALE = "gamma:-0.58"
# softplus(a + dt_bias) around e^-4: alpha near 0.98, a memory of some
# thirty to fifty tokens.  Around 0 the state would forget in two tokens and
# the recurrence would not be tested.
DT_BIAS = "gamma:-4"


def sizes(cfg):
    """The sizes the equations use, under short names."""
    NL, P = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return {"D": cfg["hidden_size"], "NL": NL, "P": P, "NF": NL // P,
            "NG": NL - NL // P, "V": cfg["vocab_size"],
            "H": cfg["num_attention_heads"],
            "KV": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "theta": float(cfg["rope_theta"]),
            "HK": cfg["linear_num_key_heads"],
            "HV": cfg["linear_num_value_heads"],
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "K": cfg["linear_conv_kernel_dim"],
            "E": cfg["num_experts"], "EH": cfg["num_local_experts"],
            "E0": cfg["first_local_expert"],
            "F": cfg["moe_intermediate_size"],
            "FS": cfg["shared_expert_intermediate_size"],
            "TOPE": cfg["num_experts_per_tok"],
            "eps": float(cfg["rms_norm_eps"])}


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter.  A block's parameters
    are stacked on a leading axis of ITS layers: `gdn.*` over the DeltaNet
    layers, `attn.*` over the full-attention layers, `moe.*` over all."""
    z = sizes(cfg)
    D, NL, NG, NF = z["D"], z["NL"], z["NG"], z["NF"]
    nk, nv = z["HK"] * z["dk"], z["HV"] * z["dv"]
    return [
        ("embed", (z["V"], D), "embed:1.0"),
        ("gdn.ln", (NG, D), "beta"),
        ("gdn.wq", (NG, nk, D), "matrix"),
        ("gdn.wk", (NG, nk, D), "matrix"),
        ("gdn.wv", (NG, nv, D), "matrix"),
        ("gdn.wz", (NG, nv, D), "matrix"),
        ("gdn.wb", (NG, z["HV"], D), "matrix"),
        ("gdn.wa", (NG, z["HV"], D), "matrix"),
        ("gdn.conv", (NG, 2 * nk + nv, z["K"]), "matrix"),
        ("gdn.a_log", (NG, z["HV"]), "beta"),
        ("gdn.dt_bias", (NG, z["HV"]), DT_BIAS),
        ("gdn.norm", (NG, z["dv"]), "gamma"),
        ("gdn.wo", (NG, D, nv), "matrix"),
        ("attn.ln", (NF, D), "beta"),
        ("attn.wq", (NF, z["H"] * 2 * z["dh"], D), "matrix"),
        ("attn.wk", (NF, z["KV"] * z["dh"], D), "matrix"),
        ("attn.wv", (NF, z["KV"] * z["dh"], D), "matrix"),
        ("attn.wo", (NF, D, z["H"] * z["dh"]), "matrix"),
        ("attn.gq", (NF, z["dh"]), QK_SCALE),
        ("attn.gk", (NF, z["dh"]), QK_SCALE),
        ("moe.ln", (NL, D), "beta"),
        ("moe.router", (NL, z["E"], D), "matrix"),
        ("moe.wg", (NL, z["EH"], z["F"], D), "matrix"),
        ("moe.wu", (NL, z["EH"], z["F"], D), "matrix"),
        ("moe.wd", (NL, z["EH"], D, z["F"]), "matrix"),
        ("moe.shared_gate", (NL, 1, D), "matrix"),
        ("moe.shared_wg", (NL, z["FS"], D), "matrix"),
        ("moe.shared_wu", (NL, z["FS"], D), "matrix"),
        ("moe.shared_wd", (NL, D, z["FS"]), "matrix"),
        ("norm", (D,), "beta"),
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


def norm(x, w, eps):
    """The zero-centred RMSNorm: scale 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, pos, theta, rot):
    """x (T, heads, d) at positions pos (T,): rotate-half within the first
    `rot` dims, the others pass."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (T, rot/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    r, rest = x[..., :rot], x[..., rot:]
    r1, r2 = r[..., :rot // 2], r[..., rot // 2:]
    r = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([r, rest], -1)


def swiglu(x, wg, wu, wd, quant):
    return dense(jax.nn.silu(dense(x, wg, quant)) * dense(x, wu, quant),
                 wd, quant)


def experts(h, p, l, z, quant):
    """The expert half of layer l over tokens h (T, D)."""
    x = norm(h, p["moe.ln"][l], z["eps"])
    r = jax.nn.softmax(dense(x, p["moe.router"][l], quant), axis=-1)
    top_r, top_e = jax.lax.top_k(r, z["TOPE"])
    gate = top_r / jnp.sum(top_r, -1, keepdims=True)            # (T, top)

    def held(out, e):
        g_e = jnp.sum(jnp.where(top_e == z["E0"] + e, gate, 0.0), -1)
        return out + g_e[:, None] * swiglu(
            x, p["moe.wg"][l, e], p["moe.wu"][l, e], p["moe.wd"][l, e],
            quant), None

    out, _ = jax.lax.scan(held, jnp.zeros_like(x), jnp.arange(z["EH"]))
    shared = swiglu(x, p["moe.shared_wg"][l], p["moe.shared_wu"][l],
                    p["moe.shared_wd"][l], quant)
    return h + out + jax.nn.sigmoid(
        dense(x, p["moe.shared_gate"][l], quant)) * shared


def attention(h, p, a, z, quant):
    """Full-attention layer number a (among its kind) over h (T, D)."""
    T, H, KV, dh = h.shape[0], z["H"], z["KV"], z["dh"]
    pos = jnp.arange(T)
    x = norm(h, p["attn.ln"][a], z["eps"])
    qg = dense(x, p["attn.wq"][a], quant).reshape(T, H, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = dense(x, p["attn.wk"][a], quant).reshape(T, KV, dh)
    v = dense(x, p["attn.wv"][a], quant).reshape(T, KV, dh)
    q = rotary(norm(q, p["attn.gq"][a], z["eps"]), pos, z["theta"], z["rot"])
    k = rotary(norm(k, p["attn.gk"][a], z["eps"]), pos, z["theta"], z["rot"])
    s = jnp.einsum("qghd,kgd->ghqk", q.reshape(T, KV, H // KV, dh), k) \
        / math.sqrt(dh)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("ghqk,kgd->qghd", jax.nn.softmax(s, -1), v)
    o = o.reshape(T, H * dh) * jax.nn.sigmoid(gate.reshape(T, H * dh))
    return h + dense(o, p["attn.wo"][a], quant)


def delta_net(h, p, n, z, quant):
    """Gated-DeltaNet layer number n (among its kind) over h (T, D)."""
    T, HK, HV, dk, dv, K = (h.shape[0], z["HK"], z["HV"], z["dk"], z["dv"],
                            z["K"])
    nk = HK * dk
    x = norm(h, p["gdn.ln"][n], z["eps"])
    qkv = jnp.concatenate([dense(x, p["gdn.w" + c][n], quant)
                           for c in "qkv"], -1)                 # (T, C)
    z_gate = dense(x, p["gdn.wz"][n], quant).reshape(T, HV, dv)
    beta = jax.nn.sigmoid(dense(x, p["gdn.wb"][n], quant))      # (T, HV)
    g = -jnp.exp(p["gdn.a_log"][n]) * jax.nn.softplus(
        dense(x, p["gdn.wa"][n], quant) + p["gdn.dt_bias"][n])
    # causal depthwise convolution: y_t = sum_j w[:, j] x_(t - K + 1 + j)
    padded = jnp.pad(qkv, [(K - 1, 0), (0, 0)])
    w = p["gdn.conv"][n]                                        # (C, K)
    y = jax.nn.silu(sum(padded[j:j + T] * w[:, j] for j in range(K)))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True)
                                       + 1e-6)
    rep = lambda a: jnp.repeat(a, HV // HK, axis=1)
    q = rep(unit(y[:, :nk].reshape(T, HK, dk))) * dk ** -0.5
    k = rep(unit(y[:, nk:2 * nk].reshape(T, HK, dk)))
    v = y[:, 2 * nk:].reshape(T, HV, dv)

    def position(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(position, jnp.zeros((HV, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + z["eps"]) * p["gdn.norm"][n] \
        * jax.nn.silu(z_gate)
    return h + dense(o.reshape(T, HV * dv), p["gdn.wo"][n], quant)


def forward(p, cfg, tokens, quant=None):
    """Logits (T, V) of one sequence `tokens` (T,)."""
    z = sizes(cfg)
    P = z["P"]

    def period(i, h):
        for j in range(P - 1):
            h = delta_net(h, p, i * (P - 1) + j, z, quant)
            h = experts(h, p, i * P + j, z, quant)
        h = attention(h, p, i, z, quant)
        return experts(h, p, i * P + P - 1, z, quant)

    with jax.default_matmul_precision("highest"):
        h = jax.lax.fori_loop(0, z["NL"] // P, period, p["embed"][tokens])
        return dense(norm(h, p["norm"], z["eps"]), p["head"], quant)


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
