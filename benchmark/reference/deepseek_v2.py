"""Plain reference of `deepseek_v2`: DeepSeek-V2 as one chip of sixteen that
share each layer holds it, float32, `jax.numpy` only.  Imports nothing of
the program.  No cache, and attention in the EXPANDED form only: every
head's keys and values are formed from the latent rows, so the program's
absorbed decode step is checked against different algebra.

Pre-norm, no biases, eps 1e-6.  N(x; w) = w x / sqrt(mean(x^2) + eps).
D hidden, H heads, a head's query and key `nope` dims without a position and
`rope` dims with one, its value `v` dims.

**Attention**, every layer.  x = N(h_t).  c_q = N(Wqa x; q_norm);
[q_n, q_r] = Wqb c_q a head.  c = N(Wkc x; kv_norm), k_r = Wkr x (ONE for all
heads; Wkc and Wkr are the rows of the published kv_a_proj_with_mqa).
q_r, k_r take rotary positions at t: pair (2i, 2i + 1) turns by
t * f_i, the YaRN frequencies f below.  k_n = Wkn c, v = Wv c a head (the
rows of the published kv_b_proj).  s(t, j) = (q_n . k_n(j) + q_r . k_r(j))
* sigma, causal softmax over j <= t, o = sum p v, h += Wo [o_1 .. o_H].

YaRN (`rope_scaling`): plain f_i = theta^(-2i/rope); a frequency that turns
more than beta_fast times in the original context stays, one that turns
fewer than beta_slow times is divided by `factor`, between the two indices
low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
d(n) = rope ln(original / (2 pi n)) / (2 ln theta), a linear ramp mixes
them.  m(s) = 0.1 s ln(factor) + 1; cos and sin are scaled by
m(mscale) / m(mscale_all_dim); sigma = (nope + rope)^-1/2 m(mscale_all_dim)^2.

**Layer l < first_k_dense_replace**: h += SwiGLU(N(h)), width
`intermediate_size`.  **Later layers**: x = N(h); r = softmax(Wr x) over all
`n_routed_experts`; a group of n_routed_experts / n_group consecutive
experts scores its best r; the `topk_group` best groups are kept (ties to
the lower group), the `num_experts_per_tok` best experts inside them (ties
to the lower expert); gate_e = routed_scaling_factor r_e, not renormalised.
h += sum over picked e that this chip holds of gate_e SwiGLU_e(x), plus
one un-gated SwiGLU of width n_shared_experts * moe_intermediate_size that
every chip computes alike.  Terms of experts held elsewhere are left out:
their chips add them in the deployment.  After the last layer N and the
logits over the vocabulary rows held.

`assumed` and `departures` are listed in configs/deepseek_v2.json.  A request
is its prompt followed by the tokens served: the logit row at position
n_prompt - 1 + j is read against served token j.  Attention runs in blocks
of heads and of query rows, so that no (H, T, T) array is ever whole.

`quant="int8"` is the control: every matrix product with a weight computes
in int8 (weights per output channel, activations per row, symmetric), the
nearest precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16
ROW_BLOCK = 512
# N(.; q_norm) and N(.; kv_norm) around 1.3: a head's score then has a
# standard deviation near 2 (configs/deepseek_v2.json `assumed`)
LATENT_SCALE = "gamma:1.3"


def sizes(cfg):
    """The sizes the equations use, under short names."""
    NL, ND = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"D": cfg["hidden_size"], "NL": NL, "ND": ND, "NM": NL - ND,
            "V": cfg["vocab_size"], "H": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "FD": cfg["intermediate_size"],
            "E": cfg["n_routed_experts"], "EH": cfg["num_local_experts"],
            "E0": cfg["first_local_expert"],
            "F": cfg["moe_intermediate_size"],
            "FS": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "TOPE": cfg["num_experts_per_tok"], "NG": cfg["n_group"],
            "TOPG": cfg["topk_group"],
            "gate": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]), "yarn": cfg["rope_scaling"],
            "eps": float(cfg["rms_norm_eps"])}


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter.  A block's parameters
    are stacked on a leading axis of ITS layers: `attn.*` over all layers,
    `dense.*` over the leading dense ones, `moe.*` over the others."""
    z = sizes(cfg)
    D, NL, ND, NM, H = z["D"], z["NL"], z["ND"], z["NM"], z["H"]
    return [
        ("embed", (z["V"], D), "embed:1.0"),
        ("attn.ln", (NL, D), "gamma"),
        ("attn.wqa", (NL, z["rq"], D), "matrix"),
        ("attn.q_norm", (NL, z["rq"]), LATENT_SCALE),
        ("attn.wqb", (NL, H * (z["dn"] + z["dr"]), z["rq"]), "matrix"),
        ("attn.wkc", (NL, z["rkv"], D), "matrix"),
        ("attn.wkr", (NL, z["dr"], D), "matrix"),
        ("attn.kv_norm", (NL, z["rkv"]), LATENT_SCALE),
        ("attn.wkn", (NL, H, z["dn"], z["rkv"]), "matrix"),
        ("attn.wv", (NL, H, z["dv"], z["rkv"]), "matrix"),
        ("attn.wo", (NL, D, H * z["dv"]), "matrix"),
        ("dense.ln", (ND, D), "gamma"),
        ("dense.wg", (ND, z["FD"], D), "matrix"),
        ("dense.wu", (ND, z["FD"], D), "matrix"),
        ("dense.wd", (ND, D, z["FD"]), "matrix"),
        ("moe.ln", (NM, D), "gamma"),
        ("moe.router", (NM, z["E"], D), "matrix"),
        ("moe.wg", (NM, z["EH"], z["F"], D), "matrix"),
        ("moe.wu", (NM, z["EH"], z["F"], D), "matrix"),
        ("moe.wd", (NM, z["EH"], D, z["F"]), "matrix"),
        ("moe.shared_wg", (NM, z["FS"], D), "matrix"),
        ("moe.shared_wu", (NM, z["FS"], D), "matrix"),
        ("moe.shared_wd", (NM, D, z["FS"]), "matrix"),
        ("norm", (D,), "gamma"),
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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def yarn(z):
    """(the rope / 2 frequencies f, the scale of cos and sin, sigma)."""
    dr, theta, y = z["dr"], z["theta"], z["yarn"]
    sigma = (z["dn"] + dr) ** -0.5
    f = [theta ** (-2.0 * i / dr) for i in range(dr // 2)]
    if not y:
        return jnp.asarray(f, jnp.float32), 1.0, sigma
    where = lambda turns: dr * math.log(
        y["original_max_position_embeddings"] / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    low = max(math.floor(where(y["beta_fast"])), 0)
    high = min(math.ceil(where(y["beta_slow"])), dr - 1)
    span = (high - low) or 0.001
    out = []
    for i, plain in enumerate(f):
        stretched = min(max((i - low) / span, 0.0), 1.0)
        out.append(plain / y["factor"] * stretched
                   + plain * (1.0 - stretched))
    m = lambda s: 0.1 * s * math.log(y["factor"]) + 1.0 \
        if y["factor"] > 1 else 1.0
    m_all = m(y.get("mscale_all_dim", 0))
    return (jnp.asarray(out, jnp.float32), m(y.get("mscale", 1)) / m_all,
            sigma * m_all * m_all)


def rotary(x, pos, f, scale):
    """x (T, heads, rope) at positions pos (T,): pair (2i, 2i + 1) turns by
    pos * f_i, in place."""
    ang = pos.astype(jnp.float32)[:, None] * f[None, :]         # (T, rope/2)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def swiglu(x, wg, wu, wd, quant):
    return dense(jax.nn.silu(dense(x, wg, quant)) * dense(x, wu, quant),
                 wd, quant)


def route(r, z):
    """r (T, E) the softmax over all experts -> (gate (T, top), expert
    (T, top)): group-limited greedy, ties to the lower index."""
    T, E = r.shape
    size = E // z["NG"]
    best = jnp.max(r.reshape(T, z["NG"], size), -1)             # (T, NG)
    order = jnp.argsort(-best, axis=-1, stable=True)[:, :z["TOPG"]]
    kept = jnp.zeros((T, z["NG"]), bool).at[
        jnp.arange(T)[:, None], order].set(True)
    inside = jnp.where(jnp.repeat(kept, size, axis=1), r, -jnp.inf)
    expert = jnp.argsort(-inside, axis=-1, stable=True)[:, :z["TOPE"]]
    return z["gate"] * jnp.take_along_axis(r, expert, -1), expert


def experts(h, p, m, z, quant):
    """The expert half of expert layer m (from 0) over tokens h (T, D)."""
    x = norm(h, p["moe.ln"][m], z["eps"])
    gate, top_e = route(
        jax.nn.softmax(dense(x, p["moe.router"][m], quant), axis=-1), z)

    def held(out, e):
        g_e = jnp.sum(jnp.where(top_e == z["E0"] + e, gate, 0.0), -1)
        return out + g_e[:, None] * swiglu(
            x, p["moe.wg"][m, e], p["moe.wu"][m, e], p["moe.wd"][m, e],
            quant), None

    out, _ = jax.lax.scan(held, jnp.zeros_like(x), jnp.arange(z["EH"]))
    return h + out + swiglu(x, p["moe.shared_wg"][m], p["moe.shared_wu"][m],
                            p["moe.shared_wd"][m], quant)


def attention(h, p, l, z, quant):
    """Layer l's attention over h (T, D), expanded."""
    T, H, dn, dr, dv = h.shape[0], z["H"], z["dn"], z["dr"], z["dv"]
    hb, rb = math.gcd(H, HEAD_BLOCK), math.gcd(T, ROW_BLOCK)
    f, rot_scale, sigma = yarn(z)
    pos = jnp.arange(T)
    x = norm(h, p["attn.ln"][l], z["eps"])
    cq = norm(dense(x, p["attn.wqa"][l], quant), p["attn.q_norm"][l],
              z["eps"])
    c = norm(dense(x, p["attn.wkc"][l], quant), p["attn.kv_norm"][l],
             z["eps"])
    kr = rotary(dense(x, p["attn.wkr"][l], quant)[:, None, :], pos, f,
                rot_scale)[:, 0]                                # (T, dr)
    wqb = p["attn.wqb"][l].reshape(H // hb, hb * (dn + dr), -1)
    wkn = p["attn.wkn"][l].reshape(H // hb, hb * dn, -1)
    wv = p["attn.wv"][l].reshape(H // hb, hb * dv, -1)

    def heads(w):
        """`hb` heads over all rows: (T, hb, dv)."""
        q = dense(cq, w[0], quant).reshape(T, hb, dn + dr)
        qn, qr = q[..., :dn], rotary(q[..., dn:], pos, f, rot_scale)
        kn = dense(c, w[1], quant).reshape(T, hb, dn)
        v = dense(c, w[2], quant).reshape(T, hb, dv)

        def rows(r0):
            at = r0 + jnp.arange(rb)
            s = (jnp.einsum("qhd,khd->hqk", qn[at], kn)
                 + jnp.einsum("qhd,kd->hqk", qr[at], kr)) * sigma
            s = jnp.where(pos[None, None, :] <= at[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

        return jax.lax.map(rows, jnp.arange(0, T, rb)).reshape(T, hb, dv)

    o = jax.lax.map(heads, (wqb, wkn, wv))                      # (H/hb, T, ..)
    o = o.transpose(1, 0, 2, 3).reshape(T, H * dv)
    return h + dense(o, p["attn.wo"][l], quant)


def forward(p, cfg, tokens, quant=None):
    """Logits (T, V) of one sequence `tokens` (T,)."""
    z = sizes(cfg)

    def sparse(m, h):
        return experts(attention(h, p, z["ND"] + m, z, quant), p, m, z,
                       quant)

    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        for l in range(z["ND"]):
            h = attention(h, p, l, z, quant)
            h = h + swiglu(norm(h, p["dense.ln"][l], z["eps"]),
                           p["dense.wg"][l], p["dense.wu"][l],
                           p["dense.wd"][l], quant)
        h = jax.lax.fori_loop(0, z["NM"], sparse, h)
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
