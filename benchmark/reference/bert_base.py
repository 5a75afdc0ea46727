"""Plain reference of `bert_base`: the BERT-base encoder with a masked-LM
head, its loss, gradients and the Adam step, float32, `jax.numpy` only.
Imports nothing of the program.

What the configuration states and this file follows: post-LN blocks with
an exact GELU, learned positions, a LayerNorm after the embedding sum,
the MLM transform (Dense, GELU, LayerNorm) and an untied vocabulary
projection with a bias, the loss as the mean over the masked positions
of the batch; MXNet's form of Adam; parameters and optimizer state
stored in bfloat16 with no float32 copy, so every stored value is rounded
to bfloat16 after each step while all arithmetic here is float32.

`quant="int8"` is the control: every Dense multiplies in int8, forward
and backward (weights per output channel, activations and output
gradients per row).  `fault="half_batch"` leaves the second half of the
rows out and takes the mean over the rest.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 8


def spec(cfg):
    H, I, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    P = cfg["max_position_embeddings"]
    out = [("word_embed", (V, H), "embed:0.05"),
           ("pos_embed", (P, H), "embed:0.05")]

    def ln(p):
        out.extend([(p + ".g", (H,), "gamma"), (p + ".b", (H,), "beta")])

    def dense(p, o, i):
        out.extend([(p + ".w", (o, i), "matrix"), (p + ".b", (o,), "bias")])

    ln("embed_ln")
    for i in range(cfg["num_hidden_layers"]):
        p = "layer.%d" % i
        for n in ("q", "k", "v", "o"):
            dense(p + ".attn." + n, H, H)
        dense(p + ".ffn1", I, H)
        dense(p + ".ffn2", H, I)
        ln(p + ".ln1")
        ln(p + ".ln2")
    dense("mlm_dense", H, H)
    ln("mlm_ln")
    dense("vocab", V, H)
    return out


def _q8(x):
    """Symmetric int8 over the last axis, as float32 values."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _qmatmul(x, w):
    return jnp.einsum("...i,oi->...o", _q8(x), _q8(w))


def _qmatmul_fwd(x, w):
    return _qmatmul(x, w), (x, w)


def _qmatmul_bwd(res, g):
    x, w = res
    gq = _q8(g)
    dx = jnp.einsum("...o,oi->...i", gq, _q8(w))
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    dw = jnp.einsum("no,ni->oi", _q8(g2.T).T, _q8(x2.T).T)
    return dx, dw


_qmatmul.defvjp(_qmatmul_fwd, _qmatmul_bwd)


def dense(x, w, b, quant=None):
    if quant == "int8":
        return _qmatmul(x, w) + b
    return jnp.einsum("...i,oi->...o", x, w) + b


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def hidden(p, cfg, tokens, quant=None):
    """(B, T) tokens -> (B, T, H) after the MLM transform."""
    H, A = cfg["hidden_size"], cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    B, T = tokens.shape
    d = H // A
    x = p["word_embed"][tokens] + p["pos_embed"][:T][None]
    x = layer_norm(x, p["embed_ln.g"], p["embed_ln.b"], eps)

    def split(t):
        return t.reshape(B, T, A, d).transpose(0, 2, 1, 3)

    for i in range(cfg["num_hidden_layers"]):
        pre = "layer.%d" % i
        q, k, v = (split(dense(x, p[pre + ".attn.%s.w" % n],
                               p[pre + ".attn.%s.b" % n], quant))
                   for n in ("q", "k", "v"))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, T, H)
        h = dense(ctx, p[pre + ".attn.o.w"], p[pre + ".attn.o.b"], quant)
        x = layer_norm(x + h, p[pre + ".ln1.g"], p[pre + ".ln1.b"], eps)
        h = jax.nn.gelu(dense(x, p[pre + ".ffn1.w"], p[pre + ".ffn1.b"],
                              quant), approximate=False)
        h = dense(h, p[pre + ".ffn2.w"], p[pre + ".ffn2.b"], quant)
        x = layer_norm(x + h, p[pre + ".ln2.g"], p[pre + ".ln2.b"], eps)
    h = jax.nn.gelu(dense(x, p["mlm_dense.w"], p["mlm_dense.b"], quant),
                    approximate=False)
    return layer_norm(h, p["mlm_ln.g"], p["mlm_ln.b"], eps)


def block_loss_sum(p, cfg, tokens, positions, labels, quant=None):
    """Sum over the masked positions of a block of rows of the loss.
    `positions` index the flattened (rows*T) block."""
    h = hidden(p, cfg, tokens, quant).reshape(-1, cfg["hidden_size"])
    g = h[positions]
    logits = dense(g, p["vocab.w"], p["vocab.b"], quant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


def loss_and_grads(p, cfg, batch, quant=None, fault=None):
    """Mean masked-LM loss of the batch and its gradient, by blocks of
    rows so that the float32 activations fit beside the parameters."""
    tokens, positions, labels = batch["tokens"], batch["positions"], \
        batch["labels"]
    B, T = tokens.shape
    K = positions.shape[0] // B
    if fault == "half_batch":
        B = B // 2
        tokens, positions, labels = tokens[:B], positions[:B * K], \
            labels[:B * K]
    rows = math.gcd(B, ROW_BLOCK)
    nb = B // rows
    tok = tokens.reshape(nb, rows, T)
    pos = positions.reshape(nb, rows * K) \
        - (jnp.arange(nb) * rows * T)[:, None]
    lab = labels.reshape(nb, rows * K)
    vg = jax.value_and_grad(functools.partial(block_loss_sum, cfg=cfg,
                                              quant=quant))

    def body(carry, xs):
        ls, gs = carry
        l, g = vg(p, tokens=xs[0], positions=xs[1], labels=xs[2])
        return (ls + l, jax.tree_util.tree_map(jnp.add, gs, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, p)
    (ls, gs), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), (tok, pos, lab))
    n = float(B * K)
    return ls / n, jax.tree_util.tree_map(lambda g: g / n, gs)


def _store(x, dtype):
    """A value as the configuration stores it, widened again.  Not a pair
    of casts: XLA on the TPU is allowed to drop a float32 -> bfloat16 ->
    float32 round trip (`xla_allow_excess_precision`), and did."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def adam_step(p, m, v, g, t, opt, store):
    lr = opt["learning_rate"] * math.sqrt(1.0 - opt["beta2"] ** t) \
        / (1.0 - opt["beta1"] ** t)
    out_p, out_m, out_v = {}, {}, {}
    for k in p:
        mk = opt["beta1"] * m[k] + (1.0 - opt["beta1"]) * g[k]
        vk = opt["beta2"] * v[k] + (1.0 - opt["beta2"]) * jnp.square(g[k])
        out_p[k] = _store(p[k] - lr * mk / (jnp.sqrt(vk) + opt["epsilon"]),
                          store)
        out_m[k], out_v[k] = _store(mk, store), _store(vk, store)
    return out_p, out_m, out_v


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in tree.items()}


def follow(p0, cfg, batch, steps=3, quant=None, fault=None):
    """The first `steps` steps from `p0` (float32 values as stored).
    Returns the readings the comparison is made of: each step's loss, the
    norm of each leaf's first gradient, the norm of each leaf's change
    after the steps."""
    opt = cfg["training"]
    store = jnp.dtype(cfg["dtype"])

    @jax.jit
    def one(p):
        with jax.default_matmul_precision("highest"):
            return loss_and_grads(p, cfg, batch, quant, fault)

    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, gnorm = [], None
    upd = jax.jit(functools.partial(adam_step, opt=opt, store=store),
                  static_argnames=("t",))
    for t in range(1, steps + 1):
        loss, g = one(p)
        if fault == "state_unchanged":
            g = jax.tree_util.tree_map(jnp.zeros_like, g)
        losses.append(float(loss))
        if t == 1:
            gnorm = {k: float(x) for k, x in jax.jit(leaf_norms)(g).items()}
        if fault != "state_unchanged":
            p, m, v = upd(p, m, v, g, t=t)
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(p, p0)
    return {"loss": losses, "grad_norm": gnorm,
            "delta_norm": {k: float(x) for k, x in delta.items()}}
