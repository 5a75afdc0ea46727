"""Builder and work counters of `deepseek_v2`: the program's
`models.latent_decoder.LatentDecoder` behind `serving.GenerationEngine`.

The benchmark makes the weights on the device (weights.py, from the
reference's spec) and the program's parameters adopt those arrays as they
are: nothing is filled on the host first.  A wrong mapping shows as
`correct` false.

The counters give the work the algorithm needs, from shapes.  Weights are
counted once a step (all of them, the held experts' too).  The experts' work
is counted at its expectation under uniform routing: a token's
`num_experts_per_tok` picks fall on a held expert with probability
`num_local_experts / n_routed_experts`, here 6 x 10 / 160 = 0.375 held
experts a token, plus the shared ones.  Attention is counted in the cheaper
of its two forms for the phase.  A prompt (expanded): every matrix once a
token, the keys' and values' maps among them, and at context c (itself
included) c keys of nope + rope dims and c values a head.  A step
(absorbed): every matrix once a token again (the keys' map on the query's
side, the values' on the context's), and at context c, c latent rows of
kv_lora_rank + rope values read once, each scored by every head over all
its values and summed by every head over its kv_lora_rank.
"""
from __future__ import annotations

import harness
# the program's block, imported as the builder is loaded: a program that lacks
# it fails then, before the driver has made 4.4 GB of weights for it
from incubator_mxnet_tpu.models.latent_decoder import LatentDecoder

# its `close` releases the adopted weights by hand (the driver closes the
# system with the collector frozen, and the reference needs the memory)
ServeSystem = harness.load_module("configs", "keye_vl2_30b_a3b").ServeSystem

BYTES = 2           # the served type, bfloat16


def sizes(config):
    D, H = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    NL, ND = config["num_hidden_layers"], config["first_k_dense_replace"]
    F = config["moe_intermediate_size"]
    held = config["num_experts_per_tok"] * config["num_local_experts"] \
        / config["n_routed_experts"]
    return {
        "D": D, "H": H, "rkv": rkv, "dn": dn, "dr": dr, "dv": dv,
        "NL": NL, "ND": ND, "NM": NL - ND, "V": config["vocab_size"],
        "EH": config["num_local_experts"], "held_per_token": held,
        # parameters in matrix products (norm scales left out: 7 K a layer):
        # the query's two maps, the latent row's and the rotary key's, the
        # keys' and the values' a head, the output projection
        "attn_dense": D * rq + rq * H * (dn + dr) + D * (rkv + dr)
        + H * (dn + dv) * rkv + H * dv * D,
        "ffn_dense": 3 * D * config["intermediate_size"],
        # the router and the shared experts
        "moe_dense": D * config["n_routed_experts"]
        + 3 * D * config["n_shared_experts"] * F,
        "expert": 3 * D * F,
        # one position's latent row and rotary key of one layer, bytes
        "row": (rkv + dr) * BYTES}


def param_map(net):
    """{reference name: program Parameter}."""
    a, f, e = net.attn, net.ffn, net.experts
    out = {"embed": net.embed, "head": net.head, "norm": net.norm.gamma,
           "attn.q_norm": a.gq, "attn.kv_norm": a.gkv,
           "moe.shared_wg": e.sg, "moe.shared_wu": e.su,
           "moe.shared_wd": e.sd}
    for n in ("ln", "wqa", "wqb", "wkc", "wkr", "wkn", "wv", "wo"):
        out["attn." + n] = getattr(a, n)
    for n in ("ln", "wg", "wu", "wd"):
        out["dense." + n] = getattr(f, n)
    for n in ("ln", "router", "wg", "wu", "wd"):
        out["moe." + n] = getattr(e, n)
    return out


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) adopted."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.serving import GenerationEngine

    sv = config["serving"]
    net = LatentDecoder(
        config["vocab_size"], config["hidden_size"],
        config["num_hidden_layers"], config["first_k_dense_replace"],
        config["num_attention_heads"], config["q_lora_rank"],
        config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        config["intermediate_size"], config["moe_intermediate_size"],
        config["n_routed_experts"], config["num_experts_per_tok"],
        config["n_group"], config["topk_group"],
        routed_scale=config["routed_scaling_factor"],
        shared_hidden=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        first_held=config["first_local_expert"],
        experts_held=config["num_local_experts"],
        rope_theta=config["rope_theta"], rope_scaling=config["rope_scaling"],
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
    """One token through every layer, without the attention's context."""
    moe = 2 * z["moe_dense"] + 2 * z["held_per_token"] * z["expert"]
    return z["NL"] * 2 * z["attn_dense"] + z["ND"] * 2 * z["ffn_dense"] \
        + z["NM"] * moe


def _step_row_flops(z):
    """One cached row of one layer in a step (absorbed): every head scores
    its kv_lora_rank + rope values and sums its kv_lora_rank."""
    return z["H"] * (2 * (z["rkv"] + z["dr"]) + 2 * z["rkv"])


def _prompt_row_flops(z):
    """One earlier position of one layer for a prompt's position
    (expanded): a key of nope + rope dims and a value a head."""
    return z["H"] * 2 * (z["dn"] + z["dr"] + z["dv"])


def _tri(n):
    return n * (n - 1) // 2                     # sum of 0..n-1


def _layer_weights(z):
    """Parameters of all layers, the held experts' among them."""
    return z["NL"] * z["attn_dense"] + z["ND"] * z["ffn_dense"] \
        + z["NM"] * (z["moe_dense"] + z["EH"] * z["expert"])


def prefill_flops(config, src_len):
    """The prompt's `src_len` positions through every layer.  No logits: the
    first new token comes from the first decode step."""
    z, n = sizes(config), int(src_len)
    return n * _token_flops(z) \
        + z["NL"] * _prompt_row_flops(z) * _tri(n + 1)


def prefill_bytes(config, src_len):
    """Bytes a prefill needs moved: every layer's weights once (not the
    head), the prompt's embedding rows, and its latent rows written once a
    layer."""
    z, n = sizes(config), int(src_len)
    return BYTES * _layer_weights(z) + BYTES * n * z["D"] \
        + z["NL"] * n * z["row"]


def decode_flops(config, src_len, pos):
    """The step that yields new token number `pos` (0-based) of a stream whose
    prompt had `src_len` tokens: it reads at context src_len + pos."""
    z = sizes(config)
    return _token_flops(z) \
        + z["NL"] * _step_row_flops(z) * (int(src_len) + int(pos)) \
        + 2 * z["D"] * z["V"]


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus new tokens first..n_tokens-1."""
    z, s, n, f = sizes(config), int(src_len), int(n_tokens), int(first)
    total = prefill_flops(config, s) if f == 0 else 0
    total += (n - f) * (_token_flops(z) + 2 * z["D"] * z["V"])
    return total + z["NL"] * _step_row_flops(z) * (_tri(s + n) - _tri(s + f))


def decode_weight_bytes(config):
    """Weights one decode step reads once, in the served type: every layer's
    attention, the dense layer, router and shared experts, all held experts,
    the output head.  Of the embedding a step reads one row a slot, not
    counted."""
    z = sizes(config)
    return BYTES * (_layer_weights(z) + z["D"] * z["V"])


def decode_state_bytes(config, src_len, pos):
    """Cache bytes one live slot needs moved at new token `pos`: at context
    c = src_len + pos, c latent rows (with their rotary keys) in every
    layer."""
    z = sizes(config)
    return z["NL"] * (int(src_len) + int(pos)) * z["row"]
