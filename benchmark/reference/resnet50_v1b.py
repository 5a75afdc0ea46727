"""Plain reference of `resnet50_v1b`: He et al. 2015 bottleneck ResNet-50
with the stride on the 3x3 convolution (GluonCV's v1b), BatchNorm over
the whole (global) batch, softmax cross-entropy, SGD with momentum and
weight decay.  float32, `jax.numpy`/`lax` only; imports nothing of the
program.

What the configuration states and this file follows: float32 master
parameters and optimizer state; weight decay on every parameter; the
momentum form m <- mu m - lr (g + wd w), w <- w + m; BatchNorm with
biased batch variance and eps 1e-5 (the running statistics do not enter
the training forward and are not compared); the mixed-precision policy
(convolutions and the classifier multiply in bfloat16) is the precision
the reference is *compared against*, not one it takes: everything here
multiplies in float32 at `highest`.

`quant="int8"` is the control (convolutions and the classifier in int8,
forward and backward inputs); `fault="half_batch"` leaves the second half
of the images out; `fault="no_exchange"` leaves the exchange between
chips out: each of `shards` chips keeps the gradient of its own images,
and chip 0's is taken.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
STAGES = ((3, 256), (4, 512), (6, 1024), (3, 2048))


def KEEP_F32(cfg):
    return [n for n, _, _ in spec(cfg)]


def spec(cfg):
    C = cfg["num_classes"]
    out = []

    def conv(p, o, i, k):
        out.append((p + ".w", (o, i, k, k), "conv"))

    def bn(p, c, gamma="gamma"):
        out.extend([(p + ".g", (c,), gamma), (p + ".b", (c,), "beta"),
                    (p + ".rm", (c,), "zeros"), (p + ".rv", (c,), "ones")])

    conv("stem.conv", 64, 3, 7)
    bn("stem.bn", 64)
    cin = 64
    for s, (blocks, ch) in enumerate(STAGES):
        for b in range(blocks):
            p = "s%d.b%d" % (s, b)
            mid = ch // 4
            conv(p + ".conv1", mid, cin, 1)
            bn(p + ".bn1", mid)
            conv(p + ".conv2", mid, mid, 3)
            bn(p + ".bn2", mid)
            conv(p + ".conv3", ch, mid, 1)
            # a small last scale in each block (Goyal et al. 2017): the
            # net starts near the identity, so the first steps at lr 0.1
            # are stable and the comparison is well conditioned
            bn(p + ".bn3", ch, "gamma:0.2")
            if b == 0:
                conv(p + ".down.conv", ch, cin, 1)
                bn(p + ".down.bn", ch)
            cin = ch
    out.extend([("fc.w", (C, cin), "matrix"), ("fc.b", (C,), "bias")])
    return out


def _q8_all(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return x + lax.stop_gradient(
        jnp.clip(jnp.round(x / scale), -127, 127) * scale - x)


def conv(x, w, stride, pad, quant=None):
    if quant == "int8":
        x, w = _q8_all(x), _q8_all(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def batch_norm(x, g, b):
    """Training BatchNorm over (N, H, W): returns (out, mean, var)."""
    mean = jnp.mean(x, (0, 2, 3))
    var = jnp.mean(jnp.square(x - mean[None, :, None, None]), (0, 2, 3))
    inv = lax.rsqrt(var + BN_EPS)
    out = (x - mean[None, :, None, None]) * (g * inv)[None, :, None, None] \
        + b[None, :, None, None]
    return out, mean, var


def bottleneck(p, x, pre, stride, first, quant):
    def bn(name, t):
        return batch_norm(t, p[name + ".g"], p[name + ".b"])[0]

    h = jax.nn.relu(bn(pre + ".bn1", conv(x, p[pre + ".conv1.w"], 1, 0, quant)))
    h = jax.nn.relu(bn(pre + ".bn2",
                       conv(h, p[pre + ".conv2.w"], stride, 1, quant)))
    h = bn(pre + ".bn3", conv(h, p[pre + ".conv3.w"], 1, 0, quant))
    if first:
        x = bn(pre + ".down.bn",
               conv(x, p[pre + ".down.conv.w"], stride, 0, quant))
    return jax.nn.relu(h + x)


def logits(p, x, quant=None):
    """(N, 3, H, W) -> (N, classes).  Each block is rematerialised in the
    backward pass, so that the float32 activations of the whole batch fit
    (BatchNorm couples the rows, so the batch cannot be cut into blocks)."""
    x = conv(x, p["stem.conv.w"], 2, 3, quant)
    x = jax.nn.relu(batch_norm(x, p["stem.bn.g"], p["stem.bn.b"])[0])
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for s, (blocks, ch) in enumerate(STAGES):
        for b in range(blocks):
            pre = "s%d.b%d" % (s, b)
            stride = 2 if (b == 0 and s > 0) else 1
            blk = {k: v for k, v in p.items() if k.startswith(pre + ".")}
            x = jax.checkpoint(functools.partial(
                bottleneck, pre=pre, stride=stride, first=b == 0,
                quant=quant))(blk, x=x)
    x = jnp.mean(x, (2, 3))
    w = p["fc.w"]
    if quant == "int8":
        x, w = _q8_all(x), _q8_all(w)
    return x @ w.T + p["fc.b"]


def mean_loss(p, images, labels, quant=None):
    logp = jax.nn.log_softmax(logits(p, images, quant), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a))) for k, a in tree.items()}


def follow(p0, cfg, batch, steps=3, quant=None, fault=None, shards=4):
    """The first `steps` steps from `p0`; the readings of train.compare."""
    opt = cfg["training"]
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["weight_decay"]
    images, labels = batch["images"].astype(jnp.float32), batch["labels"]
    n = images.shape[0]
    if fault == "half_batch":
        images, labels = images[:n // 2], labels[:n // 2]
    stat_names = [k for k in p0 if k.endswith((".rm", ".rv"))]

    @jax.jit
    def grads(p):
        with jax.default_matmul_precision("highest"):
            if fault == "no_exchange":
                # each chip normalises and differentiates its own shard;
                # nothing is summed across chips
                m = n // shards
                loss, g = jax.value_and_grad(functools.partial(
                    mean_loss, quant=quant))(p, images[:m], labels[:m])
            else:
                loss, g = jax.value_and_grad(functools.partial(
                    mean_loss, quant=quant))(p, images, labels)
        return loss, g

    @jax.jit
    def update(p, m, g):
        out_p, out_m = {}, {}
        for k in p:
            if k in stat_names:
                out_p[k], out_m[k] = p[k], m[k]
                continue
            out_m[k] = mu * m[k] - lr * (g[k] + wd * p[k])
            out_p[k] = p[k] + out_m[k]
        return out_p, out_m

    p, m = p0, jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, gnorm = [], None
    for t in range(1, steps + 1):
        loss, g = grads(p)
        losses.append(float(loss))
        if t == 1:
            eff = jax.jit(lambda g_, p_: leaf_norms(
                {k: g_[k] + wd * p_[k] for k in g_}))(g, p)
            gnorm = {k: float(v) for k, v in eff.items()}
        if fault != "state_unchanged":
            p, m = update(p, m, g)
    delta = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))(p, p0)
    out = {"loss": losses, "grad_norm": gnorm,
           "delta_norm": {k: float(v) for k, v in delta.items()}}
    # running statistics move by the forward, not by a gradient, and do
    # not enter the training forward: they are not compared
    for k in stat_names:
        out["grad_norm"].pop(k, None)
        out["delta_norm"].pop(k, None)
    return out
