"""Builder and work counters of `nmt_base`: the program's
`models.transformer.TransformerNMT` behind `serving.GenerationEngine`.

The benchmark makes the weights (weights.py, from the reference's spec)
and this file loads them into the program's blocks by walking the block
tree: a wrong mapping shows as `correct` false.
"""
from __future__ import annotations

import gc


def _dense(out, pre, blk):
    out[pre + ".w"] = blk.weight
    out[pre + ".b"] = blk.bias


def _ln(out, pre, blk):
    out[pre + ".g"] = blk.gamma
    out[pre + ".b"] = blk.beta


def _attn(out, pre, blk):
    _dense(out, pre + ".q", blk.query)
    _dense(out, pre + ".k", blk.key)
    _dense(out, pre + ".v", blk.value)
    _dense(out, pre + ".o", blk.proj)


def param_map(net):
    """{reference name: program Parameter}."""
    out = {"src_embed": net.src_embed.weight, "tgt_embed": net.tgt_embed.weight,
           "pos_embed": net.pos_embed.weight}
    _ln(out, "enc_ln", net.enc_ln)
    _ln(out, "dec_ln", net.dec_ln)
    for i, layer in enumerate(net.encoder.layers._children.values()):
        p = "enc.%d" % i
        _attn(out, p + ".attn", layer.attn)
        _dense(out, p + ".ffn1", layer.ffn.ffn1)
        _dense(out, p + ".ffn2", layer.ffn.ffn2)
        _ln(out, p + ".ln1", layer.ln1)
        _ln(out, p + ".ln2", layer.ln2)
    for i, layer in enumerate(net.decoder.layers._children.values()):
        p = "dec.%d" % i
        _attn(out, p + ".self", layer.self_attn)
        _attn(out, p + ".cross", layer.cross_attn)
        _dense(out, p + ".ffn1", layer.ffn.ffn1)
        _dense(out, p + ".ffn2", layer.ffn.ffn2)
        _ln(out, p + ".ln1", layer.ln1)
        _ln(out, p + ".ln2", layer.ln2)
        _ln(out, p + ".ln3", layer.ln3)
    _dense(out, "out", net.out_proj)
    return out


class ServeSystem:
    """The system under test of a serving cell: `submit` and `close`."""

    kind = "serve"

    def __init__(self, engine, net, info):
        self.engine = engine
        self._net = net
        self.info = info

    def submit(self, prompt, max_new):
        return self.engine.submit(prompt, max_new_tokens=max_new)

    def warmup(self):
        return self.engine.warmup()

    def close(self):
        """Stop the engine (leftover unmeasured requests are dropped) and
        free its device state, so the reference has the chip."""
        if self.engine is not None:
            self.engine.close(timeout=2.0)
        self.engine = None
        self._net = None
        gc.collect()


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) loaded."""
    import numpy as np
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models import transformer as tfm
    from incubator_mxnet_tpu.serving import GenerationEngine

    if config["encoder_layers"] != config["decoder_layers"]:
        raise ValueError("TransformerNMT takes one layer count")
    sv = config["serving"]
    V = config["vocab_size"]
    net = tfm.TransformerNMT(
        V, V, units=config["d_model"], hidden_size=config["d_ff"],
        num_layers=config["encoder_layers"], num_heads=config["num_heads"],
        max_length=config["max_position_embeddings"], dropout=0.0)
    net.initialize(ctx=ctx)
    # shapes are deferred until a first forward
    one = nd.array(np.full((1, 2), config["bos_token_id"], np.int32), ctx=ctx,
                   dtype="int32")
    net(one, one)
    net.cast(config["dtype"])
    pmap = param_map(net)
    missing = set(pmap) ^ set(weights)
    if missing:
        raise ValueError("weights and program parameters differ: %s"
                         % sorted(missing)[:8])
    for name, param in pmap.items():
        param.set_data(nd.NDArray(weights[name], ctx=ctx))
    engine = GenerationEngine(
        net, bos=config["bos_token_id"], eos=config["eos_token_id"], ctx=ctx,
        slots=sv["slots"], max_len=sv["max_len"],
        prompt_buckets=tuple(sv["prompt_buckets"]), continuous=True,
        queue_cap=sv["queue_cap"])
    info = {"slots": sv["slots"], "max_len": sv["max_len"],
            "kv_cache": engine.kv_cache_bytes()}
    return ServeSystem(engine, net, info)


# ---- work the algorithm needs, from shapes (never from XLA's counts) ----

def prefill_flops(config, src_len):
    """Encoder over `src_len` tokens and the decoder layers' memory K/V."""
    U, F = config["d_model"], config["d_ff"]
    s = int(src_len)
    enc = config["encoder_layers"] * (
        4 * 2 * U * U * s + 2 * 2 * U * F * s + 2 * 2 * U * s * s)
    mem = config["decoder_layers"] * 2 * 2 * U * U * s
    return enc + mem


def decode_flops(config, src_len, pos):
    """One output token at target position `pos` (0-based) against a source
    of `src_len` tokens: decoder layers, attention over live positions,
    output projection."""
    U, F, V = config["d_model"], config["d_ff"], config["vocab_size"]
    per_layer = (4 * 2 * U * U            # self q, k, v, o
                 + 2 * 2 * U * U          # cross q, o
                 + 2 * 2 * U * F          # ffn
                 + 2 * 2 * U * (pos + 1)  # self scores + values
                 + 2 * 2 * U * src_len)   # cross scores + values
    return config["decoder_layers"] * per_layer + 2 * U * V


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus tokens first..n_tokens-1."""
    total = prefill_flops(config, src_len) if first == 0 else 0
    n, f = int(n_tokens), int(first)
    U = config["d_model"]
    base = decode_flops(config, src_len, -1)      # without the self term
    total += (n - f) * base
    # sum over pos in [f, n) of 4 U (pos + 1) per layer
    total += config["decoder_layers"] * 4 * U * (n * (n + 1) - f * (f + 1)) // 2
    return total


def decode_weight_bytes(config):
    """Weights one decode step reads once, in the served type (2 bytes)."""
    U, F, V = config["d_model"], config["d_ff"], config["vocab_size"]
    per_layer = 6 * U * U + 2 * U * F
    return 2 * (config["decoder_layers"] * per_layer + U * V)


def decode_state_bytes(config, src_len, pos):
    """K/V rows one live slot needs read at `pos`: its own positions and
    its memory rows, in the served type."""
    U = config["d_model"]
    return 2 * config["decoder_layers"] * 2 * U * ((pos + 1) + src_len)
