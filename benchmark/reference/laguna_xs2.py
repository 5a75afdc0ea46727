"""Plain reference of `laguna_xs2`: Laguna-XS.2 as one chip of eight that
share each layer holds it, float32, `jax.numpy` only.  Imports nothing of
the program.  No cache: every layer attends over the whole sequence under
its mask, full or banded.

Pre-norm, no biases, eps `rms_norm_eps`.  N(x; w) = w x / sqrt(mean(x^2) +
eps).  D hidden, G key/value heads of d, layer l of kind k_l
(`layer_types`) with H_l query heads (`num_attention_heads_per_layer`).

    h = E[token]
    for l in 0..L-1:
      x = N(h; ln_l)
      q = Wq_l x (H_l x d), k = Wk_l x, v = Wv_l x (G x d),
      g = sigmoid(Wgate_l x) (H_l)
      q, k take rotary positions from the kind's `rope_parameters`: pair
        (i, i + r/2) of the first r = partial_rotary_factor x d dims turns by
        t f_i, cos and sin times the kind's attention_factor (1 for a
        default kind); dims r.. pass.  YaRN frequencies f: plain
        theta^(-2i/r); a frequency that turns more than beta_fast times in
        the original context stays, one that turns fewer than beta_slow
        times is divided by factor, between the two indices a linear ramp
      o_t = sum_j softmax_j(q_t . k_j / sqrt(d)) v_j over j <= t (full) or
        t - sliding_window < j <= t (sliding); query head h reads key/value
        head floor(h / (H_l / G))
      h = h + Wo_l [g_1 o_1 .. g_H o_H]
      m = N(h; ln2_l)
      dense (mlp_layer_types): h = h + Wd (silu(Wg m) * Wu m)
      sparse: p = softmax(Wr m) over all num_experts; S = the top
        num_experts_per_tok (ties to the lower expert); w_e = scale p_e /
        sum_S p; h = h + sum over e in S that this chip holds of
        w_e SwiGLU_e(m) + SwiGLU_shared(m)
    logits = Whead N(h; norm)

Terms of experts held elsewhere are left out: their chips add them in the
deployment.  `assumed` and `departures` are listed in
configs/laguna_xs2.json.  A request is its prompt followed by the tokens
served: the logit row at position n_prompt - 1 + j is read against served
token j.  Attention runs in blocks of query rows, so that no (H, T, T)
array is ever whole.

`quant="int8"` is the control: every matrix product with a weight computes
in int8 (weights per output channel, activations per row, symmetric), the
nearest precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 128
FULL, WINDOW = "full_attention", "sliding_attention"
PREFIX = {FULL: "full", WINDOW: "window"}


def sizes(cfg):
    """The sizes the equations use, under short names; `layers` is each
    layer's (kind, index among the layers of its kind)."""
    L = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:L]
    seen, layers = {FULL: 0, WINDOW: 0}, []
    for t in types:
        layers.append((t, seen[t]))
        seen[t] += 1
    heads = dict(zip(types, cfg["num_attention_heads_per_layer"][:L]))
    ND = sum(1 for t in cfg["mlp_layer_types"][:L] if t == "dense")
    return {"D": cfg["hidden_size"], "L": L, "layers": layers,
            "count": seen, "heads": heads, "ND": ND, "NM": L - ND,
            "G": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "V": cfg["vocab_size"], "FD": cfg["intermediate_size"],
            "E": cfg["num_experts"], "EH": cfg["num_local_experts"],
            "E0": cfg["first_local_expert"],
            "F": cfg["moe_intermediate_size"],
            "FS": cfg["shared_expert_intermediate_size"],
            "TOPE": cfg["num_experts_per_tok"],
            "gate": float(cfg["moe_routed_scaling_factor"]),
            "window": cfg["sliding_window"], "rope": cfg["rope_parameters"],
            "eps": float(cfg["rms_norm_eps"])}


def spec(cfg):
    """Ordered (name, shape, kind) of every parameter.  A block's parameters
    are stacked on a leading axis of ITS layers: `full.*` over the full
    layers, `window.*` over the sliding ones, `dense.*` over the leading
    dense layers, `moe.*` over the sparse ones."""
    z = sizes(cfg)
    D, G, d = z["D"], z["G"], z["d"]
    out = [("embed", (z["V"], D), "embed:1.0")]
    for kind in (FULL, WINDOW):
        n, H, pre = z["count"][kind], z["heads"][kind], PREFIX[kind]
        out += [(pre + ".ln", (n, D), "gamma"),
                (pre + ".wq", (n, H * d, D), "matrix"),
                (pre + ".wk", (n, G * d, D), "matrix"),
                (pre + ".wv", (n, G * d, D), "matrix"),
                (pre + ".wgate", (n, H, D), "matrix"),
                (pre + ".wo", (n, D, H * d), "matrix")]
    ND, NM, F, FS = z["ND"], z["NM"], z["F"], z["FS"]
    return out + [
        ("dense.ln", (ND, D), "gamma"),
        ("dense.wg", (ND, z["FD"], D), "matrix"),
        ("dense.wu", (ND, z["FD"], D), "matrix"),
        ("dense.wd", (ND, D, z["FD"]), "matrix"),
        ("moe.ln", (NM, D), "gamma"),
        ("moe.router", (NM, z["E"], D), "matrix"),
        ("moe.wg", (NM, z["EH"], F, D), "matrix"),
        ("moe.wu", (NM, z["EH"], F, D), "matrix"),
        ("moe.wd", (NM, z["EH"], D, F), "matrix"),
        ("moe.shared_wg", (NM, FS, D), "matrix"),
        ("moe.shared_wu", (NM, FS, D), "matrix"),
        ("moe.shared_wd", (NM, D, FS), "matrix"),
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


def rope(z, kind):
    """(the r / 2 frequencies f, the scale of cos and sin, r) of a kind."""
    prm, d = z["rope"][kind], z["d"]
    r = int(round(d * prm.get("partial_rotary_factor", 1.0)))
    theta = float(prm["rope_theta"])
    f = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if prm.get("rope_type", "default") != "yarn":
        return jnp.asarray(f, jnp.float32), 1.0, r
    where = lambda turns: r * math.log(
        prm["original_max_position_embeddings"] / (2 * math.pi * turns)) \
        / (2 * math.log(theta))
    low = max(math.floor(where(prm["beta_fast"])), 0)
    high = min(math.ceil(where(prm["beta_slow"])), r - 1)
    span = (high - low) or 0.001
    out = []
    for i, plain in enumerate(f):
        stretched = min(max((i - low) / span, 0.0), 1.0)
        out.append(plain / prm["factor"] * stretched
                   + plain * (1.0 - stretched))
    return jnp.asarray(out, jnp.float32), float(prm["attention_factor"]), r


def rotary(x, pos, f, scale, r):
    """x (T, heads, d) at positions pos (T,): pair (i, i + r/2) turns by
    pos * f_i; dims r.. pass."""
    ang = pos.astype(jnp.float32)[:, None] * f[None, :]          # (T, r/2)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           -1)


def swiglu(x, wg, wu, wd, quant):
    return dense(jax.nn.silu(dense(x, wg, quant)) * dense(x, wu, quant),
                 wd, quant)


def attention(h, p, kind, i, z, quant):
    """Layer `i` of kind `kind` (among its kind's layers) over h (T, D)."""
    T, H, G, d = h.shape[0], z["heads"][kind], z["G"], z["d"]
    w = lambda n: p[PREFIX[kind] + "." + n][i]
    rb = math.gcd(T, ROW_BLOCK)
    pos = jnp.arange(T)
    f, scale, r = rope(z, kind)
    x = norm(h, w("ln"), z["eps"])
    q = rotary(dense(x, w("wq"), quant).reshape(T, H, d), pos, f, scale, r)
    k = rotary(dense(x, w("wk"), quant).reshape(T, G, d), pos, f, scale, r)
    v = dense(x, w("wv"), quant).reshape(T, G, d)
    g = jax.nn.sigmoid(dense(x, w("wgate"), quant))              # (T, H)
    # query head h reads key/value head h // (H / G)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)

    def rows(r0):
        at = r0 + jnp.arange(rb)
        s = jnp.einsum("qhd,khd->hqk", q[at], k) / math.sqrt(d)
        seen = pos[None, :] <= at[:, None]
        if kind == WINDOW:
            seen = seen & (pos[None, :] > at[:, None] - z["window"])
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(rows, jnp.arange(0, T, rb)).reshape(T, H, d)
    return h + dense((o * g[..., None]).reshape(T, H * d), w("wo"), quant)


def route(r, z):
    """r (T, E) the softmax over all experts -> (gate (T, top), expert
    (T, top)): the top ones, ties to the lower index, renormalised over
    them and scaled."""
    expert = jnp.argsort(-r, axis=-1, stable=True)[:, :z["TOPE"]]
    top = jnp.take_along_axis(r, expert, -1)
    return z["gate"] * top / jnp.sum(top, -1, keepdims=True), expert


def experts(h, p, m, z, quant):
    """The expert half of sparse layer m (from 0) over tokens h (T, D)."""
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


def forward(p, cfg, tokens, quant=None):
    """Logits (T, V) of one sequence `tokens` (T,)."""
    z = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        for l, (kind, i) in enumerate(z["layers"]):
            h = attention(h, p, kind, i, z, quant)
            if l < z["ND"]:
                h = h + swiglu(norm(h, p["dense.ln"][l], z["eps"]),
                               p["dense.wg"][l], p["dense.wu"][l],
                               p["dense.wd"][l], quant)
            else:
                h = experts(h, p, l - z["ND"], z, quant)
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
