"""Builder and work counters of `bert_base`: the program's
`models.transformer.BERTModel` + `FusedMLMCELoss` under `gluon.Trainer`,
stepped through `autograd.record()` over the hybridized blocks: the
imperative path a user of the framework writes.

The masked positions are gathered inside the (hybridized) loss block, so
that net and loss still fuse into the program's one train-step
executable.  Program defaults for every `MXNET_*` switch.
"""
from __future__ import annotations

import gc


def _dense(out, pre, blk):
    out[pre + ".w"] = blk.weight
    out[pre + ".b"] = blk.bias


def _ln(out, pre, blk):
    out[pre + ".g"] = blk.gamma
    out[pre + ".b"] = blk.beta


def param_map(net, loss):
    """{reference name: program Parameter}."""
    out = {"word_embed": net.word_embed.weight,
           "pos_embed": net.pos_embed.weight}
    _ln(out, "embed_ln", net.ln)
    for i, layer in enumerate(net.encoder.layers._children.values()):
        p = "layer.%d" % i
        _dense(out, p + ".attn.q", layer.attn.query)
        _dense(out, p + ".attn.k", layer.attn.key)
        _dense(out, p + ".attn.v", layer.attn.value)
        _dense(out, p + ".attn.o", layer.attn.proj)
        _dense(out, p + ".ffn1", layer.ffn.ffn1)
        _dense(out, p + ".ffn2", layer.ffn.ffn2)
        _ln(out, p + ".ln1", layer.ln1)
        _ln(out, p + ".ln2", layer.ln2)
    _dense(out, "mlm_dense", net.mlm_dense)
    _ln(out, "mlm_ln", net.mlm_ln)
    out["vocab.w"] = loss.ce.weight
    out["vocab.b"] = loss.ce.bias
    return out


def _gathered_loss(vocab, units):
    from incubator_mxnet_tpu.gluon.block import HybridBlock
    from incubator_mxnet_tpu.models.transformer import FusedMLMCELoss

    class GatheredMLMLoss(HybridBlock):
        """Hidden states (B, T, D) -> the rows at `positions` (flat
        indices) -> the fused vocabulary projection and cross entropy."""

        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            self.ce = FusedMLMCELoss(vocab, units)

        def hybrid_forward(self, F, h, positions, label):
            rows = F.take(F.reshape(h, (-3, -2)), positions, axis=0)
            return self.ce(rows, label)

    return GatheredMLMLoss()


class TrainSystem:
    """The system under test of a training cell."""

    kind = "train"

    def __init__(self, net, loss, trainer, pmap, feed, n_rows, items):
        self.net, self.loss, self.trainer = net, loss, trainer
        self.pmap = pmap
        self.feed = feed
        self.n_rows = n_rows
        self.items_per_step = items

    def step(self):
        """Dispatch one step; returns the handle of its loss."""
        from incubator_mxnet_tpu import autograd as ag
        tokens, positions, labels = self.feed
        with ag.record():
            l = self.loss(self.net(tokens), positions, labels)
            l.backward()
        self.trainer.step(self.n_rows)
        return l

    def wait(self, handle):
        import jax
        jax.block_until_ready(handle._data)

    def fence(self):
        import jax
        jax.block_until_ready([p.data()._data for p in self.pmap.values()])

    def loss_value(self, handle):
        import numpy as np
        return float(np.asarray(handle._data.astype("float32")).mean())

    def params(self):
        return {k: p.data()._data for k, p in self.pmap.items()}

    def first_gradients(self):
        """The first gradient as the optimizer got it, from Adam's first
        moment after one step: m1 = (1 - beta1) g."""
        index = {id(p): i for i, p in enumerate(self.trainer._params)}
        states = self.trainer._updaters[0].states
        b1 = self.trainer._optimizer.beta1
        return {k: states[index[id(p)]][0]._data.astype("float32") / (1.0 - b1)
                for k, p in self.pmap.items()}

    def close(self):
        self.net = self.loss = self.trainer = self.pmap = self.feed = None
        gc.collect()


def build(config, traffic, weights, batch, devices, ctx):
    import jax
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.models import transformer as tfm

    V, H = config["vocab_size"], config["hidden_size"]
    net = tfm.BERTModel(
        vocab_size=V, units=H, hidden_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        max_length=config["max_position_embeddings"], dropout=0.0,
        output_hidden=True)
    loss = _gathered_loss(V, H)
    net.initialize(ctx=ctx)
    loss.initialize(ctx=ctx)
    dev = devices[0]
    tokens = nd.NDArray(jax.device_put(batch["tokens"], dev), ctx=ctx)
    positions = nd.NDArray(jax.device_put(batch["positions"], dev), ctx=ctx)
    labels = nd.NDArray(jax.device_put(batch["labels"], dev), ctx=ctx)
    # shapes are deferred until a first forward
    loss(net(tokens[0:1]), positions[0:1], labels[0:1])
    net.cast(config["dtype"])
    loss.cast(config["dtype"])
    net.hybridize(static_alloc=True, static_shape=True)
    loss.hybridize()
    pmap = param_map(net, loss)
    params = {**net.collect_params(), **loss.collect_params()}
    if set(pmap) != set(weights) or len(pmap) != len(params):
        raise ValueError("weights and program parameters differ: %s"
                         % sorted(set(pmap) ^ set(weights))[:8])
    for name, param in pmap.items():
        param.set_data(nd.NDArray(weights[name], ctx=ctx))
    opt = config["training"]
    trainer = gluon.Trainer(params, opt["optimizer"], {
        "learning_rate": opt["learning_rate"], "beta1": opt["beta1"],
        "beta2": opt["beta2"], "epsilon": opt["epsilon"]})
    return TrainSystem(net, loss, trainer, pmap, (tokens, positions, labels),
                       int(batch["labels"].shape[0]), batch["items_per_step"])


def reference_place(weights, batch, devices):
    """The weights and the same batch where the plain reference runs."""
    import jax
    return weights, {k: jax.device_put(batch[k], devices[0])
                     for k in ("tokens", "positions", "labels")}


# ---- work the algorithm needs, from shapes (never from XLA's counts) ----

def step_flops(config, traffic):
    """Forward + backward (3 x forward) of one step.  The MLM transform
    and the vocabulary projection are needed at the masked positions
    only; recomputation is not counted."""
    H, I, V = config["hidden_size"], config["intermediate_size"], \
        config["vocab_size"]
    B, T, K = traffic["batch"], traffic["seq_len"], traffic["mlm_positions"]
    tokens = B * T
    per_token = config["num_hidden_layers"] * (
        4 * 2 * H * H + 2 * 2 * H * I + 2 * 2 * T * H)
    head = B * K * (2 * H * H + 2 * H * V)
    return 3 * (tokens * per_token + head)
