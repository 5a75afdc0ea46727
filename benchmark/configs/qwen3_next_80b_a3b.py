"""Builder and work counters of `qwen3_next_80b_a3b`: the program's
`models.hybrid_decoder.HybridDecoder` behind `serving.GenerationEngine`.

The benchmark makes the weights on the device (weights.py, from the
reference's spec) and the program's parameters adopt those arrays as they
are: nothing is filled on the host first.  A wrong mapping shows as
`correct` false.

The counters give the work the algorithm needs, from shapes.  Weights are
counted once a step (all of them, the held experts' too).  The experts' work
is counted at its expectation under uniform routing: a token's
`num_experts_per_tok` picks fall on a held expert with probability
`num_local_experts / num_experts`, here 10 x 64 / 512 = 1.25 held experts a
token, plus the shared one.  A Gated-DeltaNet layer needs, a token and value
head, the recurrence as written: three products of the (key dim x value dim)
state with a vector and its decay, 7 operations an entry, and its state read
and written once.  A full-attention layer needs, at context c (itself
included), c rows of K and of V.
"""
from __future__ import annotations

import harness
# the program's block, imported as the builder is loaded: a program that lacks
# it fails then, before the driver has made 4 GB of weights for it
from incubator_mxnet_tpu.models.hybrid_decoder import HybridDecoder

# its `close` releases the adopted weights by hand (the driver closes the
# system with the collector frozen, and the reference needs the memory)
ServeSystem = harness.load_module("configs", "keye_vl2_30b_a3b").ServeSystem

BYTES = 2           # the served type, bfloat16
STATE_BYTES = 4     # the recurrent state, float32


def sizes(config):
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    H, G, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    HK, HV = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    NL, P = config["num_hidden_layers"], config["full_attention_interval"]
    conv = 2 * HK * dk + HV * dv
    held = config["num_experts_per_tok"] * config["num_local_experts"] \
        / config["num_experts"]
    return {
        "D": D, "F": F, "H": H, "G": G, "d": d, "HV": HV, "dk": dk, "dv": dv,
        "NL": NL, "NF": NL // P, "NG": NL - NL // P,
        "V": config["vocab_size"], "EH": config["num_local_experts"],
        "held_per_token": held, "conv": conv,
        "K": config["linear_conv_kernel_dim"],
        # parameters in matrix products of one layer's mixer (norm scales,
        # A_log, dt_bias left out: a few thousand values)
        "gdn_dense": D * (2 * HK * dk + 2 * HV * dv + 2 * HV) + HV * dv * D,
        "attn_dense": D * (2 * H * d + 2 * G * d) + H * d * D,
        # the router, the shared expert and its gate
        "moe_dense": D * config["num_experts"]
        + 3 * D * config["shared_expert_intermediate_size"] + D,
        "expert": 3 * D * F,
        # one slot's recurrent and convolution state of one layer, bytes
        "state": HV * dk * dv * STATE_BYTES,
        "conv_rows": (config["linear_conv_kernel_dim"] - 1) * conv * BYTES,
        # one position's K and V rows of one layer, bytes
        "kv_row": 2 * G * d * BYTES,
        # what the delta rule takes and gives a token in one layer: q, k,
        # v, o, decay and strength of every value head, float32 bytes
        "rule_io": HV * (2 * dk + 2 * dv + 2) * STATE_BYTES}


def param_map(net):
    """{reference name: program Parameter}."""
    g, a, e = net.gdn, net.attn, net.experts
    out = {"embed": net.embed, "head": net.head, "norm": net.norm.gamma,
           "gdn.norm": g.gn, "moe.shared_gate": e.sgate,
           "moe.shared_wg": e.sg, "moe.shared_wu": e.su,
           "moe.shared_wd": e.sd}
    for n in ("ln", "wq", "wk", "wv", "wz", "wb", "wa", "conv", "a_log",
              "dt_bias", "wo"):
        out["gdn." + n] = getattr(g, n)
    for n in ("ln", "wq", "wk", "wv", "wo", "gq", "gk"):
        out["attn." + n] = getattr(a, n)
    for n in ("ln", "router", "wg", "wu", "wd"):
        out["moe." + n] = getattr(e, n)
    return out


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) adopted."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.serving import GenerationEngine

    sv = config["serving"]
    net = HybridDecoder(
        config["vocab_size"], config["hidden_size"],
        config["num_hidden_layers"], config["full_attention_interval"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"],
        int(config["head_dim"] * config["partial_rotary_factor"]),
        config["linear_num_key_heads"], config["linear_num_value_heads"],
        config["linear_key_head_dim"], config["linear_value_head_dim"],
        config["linear_conv_kernel_dim"], config["moe_intermediate_size"],
        config["num_experts"], config["num_experts_per_tok"],
        shared_hidden=config["shared_expert_intermediate_size"],
        first_held=config["first_local_expert"],
        experts_held=config["num_local_experts"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"],
        # tile sizes of the computation (they do not enter the mathematics);
        # a tiny preset gives its own
        chunk=config.get("chunk", 64),
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

def _gdn_rule_flops(z):
    """The recurrence of one token in one layer: decay, S^T k, the rank-one
    write, S^T q over every value head's state."""
    return 7 * z["HV"] * z["dk"] * z["dv"]


def _token_flops(z):
    """One token through every layer, without the attention's context."""
    moe = 2 * z["moe_dense"] + 2 * z["held_per_token"] * z["expert"]
    gdn = 2 * z["gdn_dense"] + 2 * z["conv"] * z["K"] + _gdn_rule_flops(z)
    return z["NG"] * gdn + z["NF"] * 2 * z["attn_dense"] + z["NL"] * moe


def _context_flops(z, c):
    """A position's scores and values at context c, every full layer."""
    return z["NF"] * 4 * z["H"] * z["d"] * c


def _context_flops_sum(z, c0, c1):
    """Sum of `_context_flops` over contexts c0 <= c < c1."""
    tri = lambda n: n * (n - 1) // 2            # sum of 0..n-1
    return z["NF"] * 4 * z["H"] * z["d"] * (tri(c1) - tri(c0))


def _layer_weights(z):
    """Parameters of all layers, the held experts' among them."""
    return z["NG"] * z["gdn_dense"] + z["NF"] * z["attn_dense"] \
        + z["NL"] * (z["moe_dense"] + z["EH"] * z["expert"])


def prefill_flops(config, src_len):
    """The prompt's `src_len` positions through every layer.  No logits: the
    first new token comes from the first decode step."""
    z, n = sizes(config), int(src_len)
    return n * _token_flops(z) + _context_flops_sum(z, 1, n + 1)


def prefill_bytes(config, src_len):
    """Bytes a prefill needs moved: every layer's weights once (not the
    head), the prompt's embedding rows, the K/V rows written and read once a
    full layer, each DeltaNet layer's state and convolution rows written
    once."""
    z, n = sizes(config), int(src_len)
    return BYTES * _layer_weights(z) + BYTES * n * z["D"] \
        + 2 * z["NF"] * n * z["kv_row"] \
        + z["NG"] * (z["state"] + z["conv_rows"])


def decode_flops(config, src_len, pos):
    """The step that yields new token number `pos` (0-based) of a stream whose
    prompt had `src_len` tokens: it reads at context src_len + pos."""
    z = sizes(config)
    return _token_flops(z) + _context_flops(z, int(src_len) + int(pos)) \
        + 2 * z["D"] * z["V"]


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus new tokens first..n_tokens-1."""
    z, s, n, f = sizes(config), int(src_len), int(n_tokens), int(first)
    total = prefill_flops(config, s) if f == 0 else 0
    total += (n - f) * (_token_flops(z) + 2 * z["D"] * z["V"])
    return total + _context_flops_sum(z, s + f, s + n)


def decode_weight_bytes(config):
    """Weights one decode step reads once, in the served type: every layer's
    mixer, router and shared expert, all held experts, the output head.  Of
    the embedding a step reads one row a slot, not counted."""
    z = sizes(config)
    return BYTES * (_layer_weights(z) + z["D"] * z["V"])


def decode_state_bytes(config, src_len, pos):
    """Cache bytes one live slot needs moved at new token `pos`: every
    DeltaNet layer's state and convolution rows read AND written, and at
    context c = src_len + pos, c rows of K and of V in every full layer."""
    z = sizes(config)
    return 2 * z["NG"] * (z["state"] + z["conv_rows"]) \
        + z["NF"] * (int(src_len) + int(pos)) * z["kv_row"]


# ---- the two kernels of ops/linear_attention.py --------------------------

def gdn_step_bytes(config, slots):
    """`gated_delta_step` over `slots` slots, all DeltaNet layers of one
    decode step: each state read and written once, and q, k, v, decay,
    strength in and o out, float32."""
    z = sizes(config)
    return int(slots) * z["NG"] * (2 * z["state"] + z["rule_io"])


def gdn_step_flops(config, slots):
    z = sizes(config)
    return int(slots) * z["NG"] * _gdn_rule_flops(z)


def gdn_prefill_flops(config, src_len):
    """The recurrence over a prompt, all DeltaNet layers: what the rule
    needs a token, whatever the chunked form spends on top."""
    z = sizes(config)
    return int(src_len) * z["NG"] * _gdn_rule_flops(z)


def gdn_prefill_bytes(config, src_len):
    """q, k, v in and o out a token and value head, float32; the state
    written once a layer."""
    z = sizes(config)
    return z["NG"] * (int(src_len) * z["rule_io"] + z["state"])
