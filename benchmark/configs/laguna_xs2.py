"""Builder and work counters of `laguna_xs2`: the program's
`models.window_decoder.WindowDecoder` behind `serving.GenerationEngine`.

The benchmark makes the weights on the device (weights.py, from the
reference's spec) and the program's parameters adopt those arrays as they
are: nothing is filled on the host first.  A wrong mapping shows as
`correct` false.

The counters give the work the algorithm needs, from shapes.  Weights are
counted once a step (all of them, the held experts' too).  The experts' work
is counted at its expectation under uniform routing: a token's
`num_experts_per_tok` picks fall on a held expert with probability
`num_local_experts / num_experts`, here 8 x 32 / 256 = 1 held expert a
token, plus the shared one.  Attention is counted over the keys each kind
needs: at context c (itself included) c keys in a full layer and
min(c, sliding_window) in a window layer, a key scored and summed by every
query head of the layer over its d dims.  So a program that computed the
causal triangle in window layers reads low in the prefill's roofline.
"""
from __future__ import annotations

import harness
# the program's block, imported as the builder is loaded: a program that lacks
# it fails then, before the driver has made 2.5 GB of weights for it
from incubator_mxnet_tpu.models.window_decoder import WindowDecoder

# its `close` releases the adopted weights by hand (the driver closes the
# system with the collector frozen, and the reference needs the memory)
ServeSystem = harness.load_module("configs", "keye_vl2_30b_a3b").ServeSystem

BYTES = 2           # the served type, bfloat16
FULL, WINDOW = "full_attention", "sliding_attention"


def sizes(config):
    L = config["num_hidden_layers"]
    types = config["layer_types"][:L]
    heads = dict(zip(types, config["num_attention_heads_per_layer"][:L]))
    D, G, d = (config["hidden_size"], config["num_key_value_heads"],
               config["head_dim"])
    ND = sum(1 for t in config["mlp_layer_types"][:L] if t == "dense")
    E, F = config["num_experts"], config["moe_intermediate_size"]

    def attn(kind):
        # q, o; k, v; the gate's one row a query head
        return 2 * D * heads[kind] * d + 2 * D * G * d + heads[kind] * D

    return {
        "D": D, "d": d, "V": config["vocab_size"],
        "NF": types.count(FULL), "NW": types.count(WINDOW),
        "HF": heads[FULL], "HW": heads[WINDOW], "ND": ND, "NM": L - ND,
        "W": config["sliding_window"], "EH": config["num_local_experts"],
        "held_per_token": config["num_experts_per_tok"]
        * config["num_local_experts"] / E,
        # parameters in matrix products (norm scales left out: 4 K a layer)
        "attn_full": attn(FULL), "attn_window": attn(WINDOW),
        "ffn_dense": 3 * D * config["intermediate_size"],
        # the router and the shared expert
        "moe_dense": D * E + 3 * D * config["shared_expert_intermediate_size"],
        "expert": 3 * D * F,
        # one position's K and V rows of one layer, bytes
        "row": 2 * G * d * BYTES}


def param_map(net):
    """{reference name: program Parameter}."""
    f, e = net.ffn, net.experts
    out = {"embed": net.embed, "head": net.head, "norm": net.norm.gamma,
           "moe.shared_wg": e.sg, "moe.shared_wu": e.su,
           "moe.shared_wd": e.sd}
    for pre, block in (("full", net.full), ("window", net.sliding)):
        for n in ("ln", "wq", "wk", "wv", "wgate", "wo"):
            out[pre + "." + n] = getattr(block, n)
    for n in ("ln", "wg", "wu", "wd"):
        out["dense." + n] = getattr(f, n)
    for n in ("ln", "router", "wg", "wu", "wd"):
        out["moe." + n] = getattr(e, n)
    return out


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) adopted."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.serving import GenerationEngine

    sv, L = config["serving"], config["num_hidden_layers"]
    net = WindowDecoder(
        config["vocab_size"], config["hidden_size"],
        config["layer_types"][:L], config["mlp_layer_types"][:L],
        config["num_attention_heads_per_layer"][:L],
        config["num_key_value_heads"], config["head_dim"],
        config["sliding_window"], config["intermediate_size"],
        config["moe_intermediate_size"], config["num_experts"],
        config["num_experts_per_tok"], config["rope_parameters"],
        shared_hidden=config["shared_expert_intermediate_size"],
        routed_scale=config["moe_routed_scaling_factor"],
        first_held=config["first_local_expert"],
        experts_held=config["num_local_experts"],
        eps=config["rms_norm_eps"],
        # tile sizes of the computation (they do not enter the mathematics);
        # a tiny preset gives its own
        query_block=config.get("query_block", 512),
        key_chunk=config.get("key_chunk", 512),
        expert_tile=config.get("expert_tile", 256))
    pmap = param_map(net)
    missing = set(pmap) ^ set(weights)
    if missing:
        raise ValueError("weights and program parameters differ: %s"
                         % sorted(missing)[:8])
    for name, param in pmap.items():
        param.grad_req = "null"         # served, never trained
        param.adopt(nd.NDArray(weights[name], ctx=ctx))
    # `ignore_eos`: an answer runs to its budget, as the traffic sets it
    eos = None if sv.get("ignore_eos") else config["eos_token_id"]
    engine = GenerationEngine(
        net, bos=config["bos_token_id"], eos=eos, ctx=ctx,
        slots=sv["slots"], max_len=sv["max_len"],
        prompt_buckets=tuple(sv["prompt_buckets"]), continuous=True,
        queue_cap=sv["queue_cap"])
    info = {"slots": sv["slots"], "max_len": sv["max_len"],
            "kv_cache": engine.kv_cache_bytes()}
    return ServeSystem(engine, net, info)


# ---- work the algorithm needs, from shapes (never from XLA's counts) ----

def _token_flops(z):
    """One token through every layer, without the attention's context."""
    moe = 2 * z["moe_dense"] + 2 * z["held_per_token"] * z["expert"]
    return 2 * (z["NF"] * z["attn_full"] + z["NW"] * z["attn_window"]) \
        + z["ND"] * 2 * z["ffn_dense"] + z["NM"] * moe


def _key_flops(z, heads):
    """One key of one layer for one position: every query head scores its
    d dims and sums its d."""
    return 4 * heads * z["d"]


def _capped(x, cap):
    """sum of min(c, cap) over c = 0 .. x - 1."""
    x = int(x)
    if x <= cap:
        return x * (x - 1) // 2
    return cap * (cap - 1) // 2 + (x - cap) * cap


def _keys(z, lo, hi):
    """FLOPs of the attention of the positions whose contexts are
    lo .. hi - 1 keys (a context counts the position itself)."""
    full = _capped(hi, hi) - _capped(lo, lo)
    band = _capped(hi, z["W"]) - _capped(lo, z["W"])
    return z["NF"] * _key_flops(z, z["HF"]) * full \
        + z["NW"] * _key_flops(z, z["HW"]) * band


def _layer_weights(z):
    """Parameters of all layers, the held experts' among them."""
    return z["NF"] * z["attn_full"] + z["NW"] * z["attn_window"] \
        + z["ND"] * z["ffn_dense"] \
        + z["NM"] * (z["moe_dense"] + z["EH"] * z["expert"])


def prefill_flops(config, src_len):
    """The prompt's `src_len` positions through every layer, position t
    attending over t + 1 keys in a full layer and min(t + 1, window) in a
    window layer.  No logits: the first new token comes from the first
    decode step."""
    z, n = sizes(config), int(src_len)
    return n * _token_flops(z) + _keys(z, 1, n + 1)


def prefill_bytes(config, src_len):
    """Bytes a prefill needs moved: every layer's weights once (not the
    head), the prompt's embedding rows, and the K/V rows it hands over: its
    rows at every full layer, the last min(n, window) at every window
    layer."""
    z, n = sizes(config), int(src_len)
    return BYTES * _layer_weights(z) + BYTES * n * z["D"] \
        + (z["NF"] * n + z["NW"] * min(n, z["W"])) * z["row"]


def decode_flops(config, src_len, pos):
    """The step that yields new token number `pos` (0-based) of a stream whose
    prompt had `src_len` tokens: it reads at context src_len + pos."""
    z, c = sizes(config), int(src_len) + int(pos)
    return _token_flops(z) + _keys(z, c, c + 1) + 2 * z["D"] * z["V"]


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus new tokens first..n_tokens-1."""
    z, s, n, f = sizes(config), int(src_len), int(n_tokens), int(first)
    total = prefill_flops(config, s) if f == 0 else 0
    total += (n - f) * (_token_flops(z) + 2 * z["D"] * z["V"])
    return total + _keys(z, s + f, s + n)


def decode_weight_bytes(config):
    """Weights one decode step reads once, in the served type: every layer's
    attention, the dense layer, router and shared experts, all held experts,
    the output head.  Of the embedding a step reads one row a slot, not
    counted."""
    z = sizes(config)
    return BYTES * (_layer_weights(z) + z["D"] * z["V"])


def decode_state_bytes(config, src_len, pos):
    """Cache bytes one live slot needs moved at new token `pos`: at context
    c = src_len + pos, c rows of K and V in every full layer and
    min(c, window) in every window layer."""
    z, c = sizes(config), int(src_len) + int(pos)
    return (z["NF"] * c + z["NW"] * min(c, z["W"])) * z["row"]
