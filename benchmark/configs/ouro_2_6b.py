"""Builder and work counters of `ouro_2_6b`: the program's
`models.looped_decoder.LoopedDecoder` behind `serving.GenerationEngine`.

The benchmark makes the weights on the device (weights.py, from the
reference's spec) and the program's parameters adopt those arrays as they
are: nothing is filled on the host first.  A wrong mapping shows as
`correct` false.

The counters give the work the algorithm needs, from shapes.  The stack of
L layers runs R = `total_ut_steps` times a token over the same weights, and
every pass keeps K/V rows of its own, so a token costs R x L layer bodies
and R x L rows of K and of V.  The layers' weights are counted R times a
step and R times a prefill: a pass needs all 2.47 GB of them, nothing on
the chip holds that much between two passes, so each pass's need crosses
the memory bus again.  The head is counted once; of the embedding a step
reads one row a slot, not counted; the gate (2 D FLOPs a pass) and the norm
scales (4 D values a layer) are left out.
"""
from __future__ import annotations

import harness
# the program's block, imported as the builder is loaded: a program that lacks
# it fails then, before the driver has made 2.9 GB of weights for it
from incubator_mxnet_tpu.models.looped_decoder import LoopedDecoder

# its `close` releases the adopted weights by hand (the driver closes the
# system with the collector frozen, and the reference needs the memory)
ServeSystem = harness.load_module("configs", "keye_vl2_30b_a3b").ServeSystem

BYTES = 2           # the served type, bfloat16


def sizes(config):
    D, H, d = (config["hidden_size"], config["num_attention_heads"],
               config["head_dim"])
    return {
        "D": D, "H": H, "d": d, "V": config["vocab_size"],
        # layer bodies a token runs
        "bodies": config["total_ut_steps"] * config["num_hidden_layers"],
        # parameters of one layer in matrix products: q, k, v, o and the
        # SwiGLU's three
        "layer_dense": 4 * D * H * d + 3 * D * config["intermediate_size"],
        # one position's K and V rows of one (pass, layer), bytes
        "row": 2 * H * d * BYTES}


def param_map(net):
    """{reference name: program Parameter}."""
    a, f = net.attn, net.ffn
    return {"embed": net.embed, "head": net.head, "norm": net.norm.gamma,
            "gate.w": net.we, "gate.b": net.be,
            "ln1": a.ln1, "wq": a.wq, "wk": a.wk, "wv": a.wv, "wo": a.wo,
            "ln2": a.ln2, "ln3": f.ln, "wg": f.wg, "wu": f.wu, "wd": f.wd,
            "ln4": f.ln_post}


def build(config, weights, ctx):
    """The engine with `weights` ({reference name: device array}) adopted."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.serving import GenerationEngine

    sv = config["serving"]
    net = LoopedDecoder(
        config["vocab_size"], config["hidden_size"],
        config["num_hidden_layers"], config["num_attention_heads"],
        config["head_dim"], config["intermediate_size"],
        loops=config["total_ut_steps"],
        exit_threshold=config["early_exit_threshold"],
        rope_theta=config["rope_theta"], eps=config["rms_norm_eps"])
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

def _tri(n):
    return n * (n - 1) // 2                     # sum of 0..n-1


def _token_flops(z):
    """One token through every pass of every layer, without its attention's
    context."""
    return z["bodies"] * 2 * z["layer_dense"]


def _row_flops(z):
    """One earlier position of one (pass, layer): every head scores its d
    and sums its d."""
    return 4 * z["H"] * z["d"]


def prefill_flops(config, src_len):
    """The prompt's `src_len` positions through every pass.  No logits: the
    first new token comes from the first decode step."""
    z, n = sizes(config), int(src_len)
    return n * _token_flops(z) + z["bodies"] * _row_flops(z) * _tri(n + 1)


def prefill_bytes(config, src_len):
    """Bytes a prefill needs moved: every layer's weights once a pass (not
    the head), the prompt's embedding rows, and its K/V rows written once
    at every (pass, layer)."""
    z, n = sizes(config), int(src_len)
    return BYTES * z["bodies"] * z["layer_dense"] + BYTES * n * z["D"] \
        + z["bodies"] * n * z["row"]


def decode_flops(config, src_len, pos):
    """The step that yields new token number `pos` (0-based) of a stream whose
    prompt had `src_len` tokens: it reads at context src_len + pos."""
    z = sizes(config)
    return _token_flops(z) \
        + z["bodies"] * _row_flops(z) * (int(src_len) + int(pos)) \
        + 2 * z["D"] * z["V"]


def request_flops(config, src_len, n_tokens, first=0):
    """Prefill (when `first` is 0) plus new tokens first..n_tokens-1."""
    z, s, n, f = sizes(config), int(src_len), int(n_tokens), int(first)
    total = prefill_flops(config, s) if f == 0 else 0
    total += (n - f) * (_token_flops(z) + 2 * z["D"] * z["V"])
    return total + z["bodies"] * _row_flops(z) * (_tri(s + n) - _tri(s + f))


def decode_weight_bytes(config):
    """Weights one decode step needs moved, in the served type: every
    layer's R times (once a pass: the need of a pass is all of them, and
    nothing on the chip holds 2.47 GB between passes), the output head
    once."""
    z = sizes(config)
    return BYTES * (z["bodies"] * z["layer_dense"] + z["D"] * z["V"])


def decode_state_bytes(config, src_len, pos):
    """Cache bytes one live slot needs moved at new token `pos`: at context
    c = src_len + pos, c rows of K and of V at every (pass, layer)."""
    z = sizes(config)
    return z["bodies"] * (int(src_len) + int(pos)) * z["row"]


# ---- the step's attention kernel (`ragged_decode_attention`) -------------

def attn_step_bytes(config, slots):
    """The attention of one decode step over `slots` live slots, all
    R x L layer bodies: each slot's K and V rows up to its context, taken
    at the cell's mean context (`serving.mean_context`: a stream's context
    grows all its life, and a step holds streams of every age)."""
    return int(slots) * decode_state_bytes(
        config, config["serving"]["mean_context"], 0)


def attn_step_flops(config, slots):
    z = sizes(config)
    return int(slots) * z["bodies"] * _row_flops(z) \
        * config["serving"]["mean_context"]
