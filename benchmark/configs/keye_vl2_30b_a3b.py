"""Builder and work counters of `keye_vl2_30b_a3b`: the program's
`models.sparse_decoder.SparseDecoder` behind `serving.GenerationEngine`.

The benchmark makes the weights on the device (weights.py, from the
reference's spec) and the program's parameters adopt those arrays as they
are: nothing is filled on the host first.  A wrong mapping shows as
`correct` false.

The counters give the work the algorithm needs, from shapes.  Weights are
counted once a step (all of them, the held experts' too).  The experts' work
is counted at its expectation under uniform routing: a token's
`num_experts_per_tok` picks fall on a held expert with probability
`num_local_experts / num_experts`, here 8 x 16 / 128 = 1 held expert a
token.  A position at context c (itself included) needs c indexer keys and
min(topk, c) rows of K and of V in every layer.
"""
from __future__ import annotations

import harness
# the program's block, imported as the builder is loaded: a program that lacks
# it fails then, before the driver has made 3.3 GB of weights for it
from incubator_mxnet_tpu.models.sparse_decoder import SparseDecoder


def sizes(config):
    sa = config["sa_config"]
    H, G, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    J, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts_per_tok"] * config["num_local_experts"] \
        / config["num_experts"]
    return {
        "D": D, "F": F, "H": H, "G": G, "d": d, "J": J, "di": di,
        "topk": sa["topk"], "NL": config["num_hidden_layers"],
        "V": config["vocab_size"], "E": config["num_experts"],
        "EH": config["num_local_experts"], "held_per_token": held,
        # parameters of one layer outside its experts: q, k, v, o; the
        # indexer's q, k, w; the router (norm scales left out: 4 K values)
        "layer_dense": D * (2 * H * d + 2 * G * d) + D * (J * di + di + J)
        + D * config["num_experts"],
        "expert": 3 * D * F}


def param_map(net):
    """{reference name: program Parameter}."""
    a, e = net.attn, net.experts
    return {"embed": net.embed, "head": net.head, "norm.g": net.norm.gamma,
            "ln1.g": a.ln, "attn.wq": a.wq, "attn.wk": a.wk, "attn.wv": a.wv,
            "attn.wo": a.wo, "attn.gq": a.gq, "attn.gk": a.gk,
            "idx.wq": a.iwq, "idx.wk": a.iwk, "idx.ww": a.iww,
            "idx.ln.g": a.ilg, "idx.ln.b": a.ilb,
            "ln2.g": e.ln, "moe.router": e.router, "moe.wg": e.wg,
            "moe.wu": e.wu, "moe.wd": e.wd}


class ServeSystem(harness.load_module("configs", "nmt_base").ServeSystem):
    """`nmt_base`'s system under test, whose `close` also releases the
    weights by hand: the driver holds the collector frozen while it closes
    the system, a block is full of cycles, and the reference that runs next
    needs the 3.3 GB."""

    def close(self):
        net = self._net
        super().close()
        if net is not None:
            for param in net.collect_params().values():
                param.release()


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) adopted."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.serving import GenerationEngine

    sv, sa = config["serving"], config["sa_config"]
    net = SparseDecoder(
        config["vocab_size"], config["hidden_size"],
        config["num_hidden_layers"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["moe_intermediate_size"], config["num_experts"],
        config["num_experts_per_tok"], sa["indexer_num_heads"],
        sa["indexer_head_dim"], sa["topk"],
        first_held=config["first_local_expert"],
        experts_held=config["num_local_experts"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        # the config's tile sizes: two query tiles a block, one key tile a
        # chunk (they do not enter the mathematics)
        query_block=2 * sa["q_chunk_size"], key_chunk=sa["kv_chunk_size"],
        expert_tile=config.get("expert_tile", 256))
    pmap = param_map(net)
    missing = set(pmap) ^ set(weights)
    if missing:
        raise ValueError("weights and program parameters differ: %s"
                         % sorted(missing)[:8])
    for name, param in pmap.items():
        param.grad_req = "null"         # served, never trained
        param.adopt(nd.NDArray(weights[name], ctx=ctx))
    engine = GenerationEngine(
        net, bos=config["bos_token_id"], eos=config["eos_token_id"], ctx=ctx,
        slots=sv["slots"], max_len=sv["max_len"],
        prompt_buckets=tuple(sv["prompt_buckets"]), continuous=True,
        queue_cap=sv["queue_cap"])
    info = {"slots": sv["slots"], "max_len": sv["max_len"],
            "kv_cache": engine.kv_cache_bytes()}
    return ServeSystem(engine, net, info)


# ---- work the algorithm needs, from shapes (never from XLA's counts) ----

def _token_flops(z):
    """One token through one layer, without its attention and index scores."""
    return 2 * z["layer_dense"] + 2 * z["held_per_token"] * z["expert"]


def _context_flops(z, c):
    """A position's index scores and attention at context c (itself
    included), one layer: J heads of di against c keys, then H heads of d
    against min(topk, c) keys, scores and values."""
    return 2 * z["J"] * z["di"] * c + 4 * z["H"] * z["d"] * min(z["topk"], c)


def _context_flops_sum(z, c0, c1):
    """Sum of `_context_flops` over contexts c0 <= c < c1."""
    tri = lambda n: n * (n - 1) // 2            # sum of 0..n-1
    k = z["topk"]
    lo, hi = min(c0, k), min(c1, k)             # contexts below topk
    sel = tri(hi) - tri(lo) + k * ((c1 - c0) - (hi - lo))
    return 2 * z["J"] * z["di"] * (tri(c1) - tri(c0)) \
        + 4 * z["H"] * z["d"] * sel


def prefill_flops(config, src_len):
    """The prompt's `src_len` positions through every layer.  No logits: the
    first new token comes from the first decode step."""
    z, n = sizes(config), int(src_len)
    return z["NL"] * (n * _token_flops(z) + _context_flops_sum(z, 1, n + 1))


def prefill_bytes(config, src_len):
    """Bytes a prefill needs moved: every layer's weights once (not the
    head), the prompt's embedding rows, its cache rows written and read once
    a layer (K, V and the indexer's key), in the served type."""
    z, n = sizes(config), int(src_len)
    row = 2 * z["G"] * z["d"] + z["di"]
    return 2 * z["NL"] * (z["layer_dense"] + z["EH"] * z["expert"]) \
        + 2 * n * z["D"] + 2 * 2 * z["NL"] * n * row


def decode_flops(config, src_len, pos):
    """The step that yields new token number `pos` (0-based) of a stream whose
    prompt had `src_len` tokens: it reads at context src_len + pos."""
    z = sizes(config)
    return z["NL"] * (_token_flops(z)
                      + _context_flops(z, int(src_len) + int(pos))) \
        + 2 * z["D"] * z["V"]


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus new tokens first..n_tokens-1."""
    z, s, n, f = sizes(config), int(src_len), int(n_tokens), int(first)
    total = prefill_flops(config, s) if f == 0 else 0
    total += (n - f) * (z["NL"] * _token_flops(z) + 2 * z["D"] * z["V"])
    return total + z["NL"] * _context_flops_sum(z, s + f, s + n)


def decode_weight_bytes(config):
    """Weights one decode step reads once, in the served type (2 bytes): every
    layer's attention, indexer and router, all held experts, the output
    head.  Of the embedding a step reads one row a slot, not counted."""
    z = sizes(config)
    return 2 * (z["NL"] * (z["layer_dense"] + z["EH"] * z["expert"])
                + z["D"] * z["V"])


def decode_state_bytes(config, src_len, pos):
    """Cache rows one live slot needs read at new token `pos`: at context
    c = src_len + pos, c indexer keys and min(topk, c) rows of K and of V in
    every layer, in the served type."""
    z = sizes(config)
    c = int(src_len) + int(pos)
    return 2 * z["NL"] * (c * z["di"]
                          + min(z["topk"], c) * 2 * z["G"] * z["d"])
