"""The device's time by model part, program side: `costs.part` (the one
scope, its vocabulary), `costs.op_parts` (compiled instruction -> part) on
a toy and on the tiny forms of every served and trained model, and the
guard that a scope changes no program.  CPU-only: names, never a time."""
import contextlib
import gc
import os
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.serving import GenerationEngine
from incubator_mxnet_tpu.telemetry import costs, flightrec

PKG = os.path.dirname(os.path.abspath(mx.__file__))


@pytest.fixture(autouse=True)
def _fresh_registry():
    costs.reset()
    yield
    gc.collect()
    costs.reset()


# -- the scope --------------------------------------------------------------

def test_a_name_outside_the_vocabulary_raises():
    assert costs.PARTS == ("embed", "head", "proj", "attn", "index", "state",
                           "cache", "experts", "ffn", "window")
    with pytest.raises(ValueError, match="nonsense"):
        costs.part("nonsense")
    with costs.part("attn"):
        pass


def test_part_is_the_only_way_to_name_a_scope():
    """No second `named_scope` in the package, no switch for this one."""
    hits = []
    for root, _, files in os.walk(PKG):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    hits += ["%s:%d" % (os.path.relpath(f.name, PKG), i)
                             for i, line in enumerate(f, 1)
                             if re.search(r"named_scope\(", line)]
    assert {h.split(":")[0] for h in hits} == {"telemetry/costs.py"}, hits
    with open(os.path.join(PKG, "config.py")) as f:
        assert "op_parts" not in f.read()


# -- op_parts on a toy ------------------------------------------------------

def _toy(x, w):
    def body(h, wi):
        with costs.part("proj"):
            q = h @ wi
            with costs.part("attn"):        # the innermost scope names an op
                s = jax.nn.softmax(q @ q.T) @ q
        return s, None
    h, _ = jax.lax.scan(body, x, w)
    return jnp.cos(h).sum()


@pytest.mark.parametrize("recorder", [True, False])
def test_a_scanned_body_under_vmap_and_grad_maps_to_its_innermost_part(
        recorder):
    flightrec.enable(recorder)
    try:
        step = costs.metered_jit(jax.vmap(jax.grad(_toy), in_axes=(0, None)),
                                 label="toy.step", role="toy_step")
        step(jnp.ones((2, 8, 8)), jnp.ones((3, 8, 8)))
    finally:
        flightrec.enable(True)
    (entry,) = costs.op_parts("toy_step")
    assert entry["name"] == "jit__traced_toy_step" and not entry["stale"]
    parts = entry["instructions"]
    assert set(parts.values()) == {None, "proj", "attn"}
    dots = {n: p for n, p in parts.items() if n.startswith("dot")}
    # forward and backward: h @ wi twice over, the attention's three products
    assert sorted(dots.values()).count("proj") == 2 and \
        set(dots.values()) == {"proj", "attn"}
    assert all(p == "attn" for n, p in parts.items() if "exponential" in n)
    assert any(p == "attn" for n, p in parts.items() if "fusion" in n)
    # outside the scopes: the cosine's derivative, the loops themselves
    assert [p for n, p in parts.items() if n.startswith("sin")] == [None]
    loops = [p for n, p in parts.items() if n.startswith("while")]
    assert loops and set(loops) == {None}
    assert costs.op_parts("toy_step")[0] is entry       # built once, kept
    assert costs.op_parts("no_such_role") == []
    # the newest dead executable of a role still answers
    del step
    gc.collect()
    assert costs.op_parts("toy_step") == [entry]


def test_an_executable_without_a_scope_is_flagged_stale():
    """What a compile cache filled before the program had scopes hands
    back: the cache's key leaves metadata out."""
    plain = costs.metered_jit(lambda a: jnp.tanh(a @ a), label="toy.plain")
    plain(jnp.ones((4, 4)))
    (entry,) = costs.op_parts("toy_plain")
    assert entry["stale"] and entry["instructions"]
    assert set(entry["instructions"].values()) == {None}


def test_an_instruction_without_metadata_has_no_part():
    """Lines of `nmt_base`'s decode step as the v5e's compiler prints them:
    the scatter fusion and its root keep no `op_name`, and what the fused
    computation's other lines bear is the rows' producer, not the write."""
    text = """
%fused_computation.15 (param_0.23: f32[524288,128], param_1.47: s32[2048]) -> f32[524288,128] {
  %param_0.23 = f32[524288,128]{1,0:T(8,128)} parameter(0)
  %reshape.1220 = f32[512,4,128]{2,1,0:T(4,128)} reshape(%param_2.59), metadata={op_name="jit(_traced_gen_decode)/mx.proj/add"}
  ROOT %scatter.39 = f32[524288,128]{1,0:T(8,128)} scatter(%param_0.23, %transpose.311, %transpose.312), update_window_dims={2}
}

ENTRY %main.1 (Arg_0.1: f32[8]) -> f32[8] {
  %fusion.15 = f32[524288,128]{1,0:T(8,128)} fusion(%bitcast.95, %reshape.1149), kind=kCustom, calls=%fused_computation.15, backend_config={"flag_configs":[]}
  %fusion.694 = s32[512,256]{1,0:T(8,128)S(1)} fusion(%copy-done.41), kind=kLoop, calls=%fused_computation.793, metadata={op_name="jit(_traced_gen_decode)/mx.proj/mx.cache/add" source_file="x.py"}
  %copy-done.41 = s32[512,256]{1,0} copy-done(%copy-start.41)
  ROOT %while.3 = (s32[], f32[8]) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(_traced_gen_decode)/while"}
}
"""
    assert costs._parse_parts(text) == {
        "param_0.23": None, "reshape.1220": "proj", "scatter.39": None,
        "fusion.15": None, "fusion.694": "cache", "copy-done.41": None,
        "while.3": None}


def test_the_kept_program_lowers_as_the_call_did():
    """The first call of a signature notes where its arguments lay, so the
    compile that `op_parts` asks for is one the compile cache knows."""
    dev = jax.devices()[0]
    f = costs.metered_jit(lambda p, c, k: {"x": jnp.tanh(p["w"] @ c["x"])
                                           + k[0]},
                          donate_argnums=(1,), label="toy.placed")
    p = {"w": jax.device_put(jnp.ones((8, 8)), dev)}    # committed
    c = {"x": jax.device_put(jnp.ones((8, 8)), dev)}    # committed, donated
    k = onp.zeros((2,), onp.float32)                    # the host's
    want = f.lower(p, c, k).as_text()
    f(p, c, k)
    assert c["x"].is_deleted()
    ((traced, _),) = f._programs
    assert traced.lower().as_text() == want
    assert "sdy.sharding" in want or "mhlo.sharding" in want


# -- the models -------------------------------------------------------------

def _keye():
    from incubator_mxnet_tpu.models.sparse_decoder import SparseDecoder
    return SparseDecoder(128, 64, 2, 4, 2, 16, 32, 8, 2, 2, 8, 8,
                         first_held=0, experts_held=4, query_block=8,
                         key_chunk=4, expert_tile=8)


def _qwen3_next():
    from incubator_mxnet_tpu.models.hybrid_decoder import HybridDecoder
    return HybridDecoder(128, 64, 4, 2, 4, 2, 16, 8, 2, 4, 16, 16, 4, 32, 8,
                         2, shared_hidden=32, first_held=0, experts_held=4,
                         chunk=8, expert_tile=8)


def _deepseek_v2():
    from incubator_mxnet_tpu.models.latent_decoder import LatentDecoder
    return LatentDecoder(128, 64, 3, 1, 4, 24, 16, 16, 8, 16, 96, 32, 8, 2,
                         4, 2, routed_scale=2.0, shared_hidden=64,
                         first_held=0, experts_held=4, query_block=8,
                         key_chunk=8, expert_tile=8)


def _ouro():
    from incubator_mxnet_tpu.models.looped_decoder import LoopedDecoder
    return LoopedDecoder(128, 64, 2, 4, 16, 96, loops=3, exit_threshold=0.5)


def _laguna():
    from incubator_mxnet_tpu.models.window_decoder import WindowDecoder
    types = ["full_attention"] + ["sliding_attention", "full_attention"] * 2
    rope = {"full_attention": {"rope_type": "yarn", "rope_theta": 5e5,
                               "factor": 64,
                               "original_max_position_embeddings": 4096,
                               "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_theta": 1e4}}
    return WindowDecoder(128, 64, types, ["dense"] + ["sparse"] * 4,
                         [4, 6, 4, 6, 4], 2, 16, 8, 96, 16, 16, 4, rope,
                         shared_hidden=24, routed_scale=2.5, first_held=0,
                         experts_held=4, query_block=8, key_chunk=8,
                         expert_tile=8)


def _nmt():
    from incubator_mxnet_tpu.models.transformer import transformer_nmt_small
    return transformer_nmt_small(128, 128, dropout=0.0)


EVERY_STEP = {"embed", "proj", "attn", "cache"}
SERVED = {          # model -> (builder, the parts its layers add)
    "keye_vl2_30b_a3b": (_keye, {"index", "experts"}),
    "qwen3_next_80b_a3b": (_qwen3_next, {"state", "experts"}),
    "deepseek_v2": (_deepseek_v2, {"experts", "ffn"}),
    "nmt_base": (_nmt, {"ffn"}),
    "ouro_2_6b": (_ouro, {"ffn"}),
    "laguna_xs2": (_laguna, {"window", "experts", "ffn"}),
}


def _engine(net):
    return GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=3,
                            max_len=48, prompt_buckets=(16, 32), queue_cap=8)


def _served_net(build):
    net = build()
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    return net


def _found(role):
    entries = costs.op_parts(role)
    assert entries and not any(e["stale"] for e in entries)
    return [set(e["instructions"].values()) - {None} for e in entries]


def _bert_steps(steps=3):
    """`bert_small` through the imperative loop; the fused train step is
    built on the second step."""
    from incubator_mxnet_tpu.models import transformer as tfm
    net = tfm.bert_small(vocab_size=128, dropout=0.0, output_hidden=True)
    loss = tfm.FusedMLMCELoss(128, 64)
    net.initialize()
    loss.initialize()
    tok = nd.array(onp.ones((2, 16)), dtype="int32")
    lab = nd.array(onp.ones((2, 16)))
    loss(net(tok), lab)
    net.hybridize()
    loss.hybridize()
    trainer = gluon.Trainer({**net.collect_params(), **loss.collect_params()},
                            "adam", {"learning_rate": 1e-3})
    for _ in range(steps):
        with autograd.record():
            out = loss(net(tok), lab)
            out.backward()
        trainer.step(2)
    out.asnumpy()
    return net, loss, trainer


@pytest.mark.parametrize("model", sorted(SERVED) + ["bert_small"])
def test_every_executable_of_the_model_names_its_parts(model):
    if model == "bert_small":
        held = _bert_steps()
        (found,) = _found("gluon_train_step")
        assert {"embed", "head", "proj", "attn", "ffn"} <= found
        assert found <= set(costs.PARTS)
        # the trainer and its blocks gone, as a benchmark's reader finds it
        del held
        gc.collect()
        assert _found("gluon_train_step") == [found]
        return
    build, own = SERVED[model]
    eng = _engine(_served_net(build))
    eng.warmup()
    # the readers run once the engine is shut, and gone
    eng.close()
    del eng
    gc.collect()
    prefills = _found("gen_prefill")
    assert len(prefills) == 2               # a bucket an executable
    # a prefill projects no logits; the looped model's final norm closes
    # every pass, a prefill's too
    closing = {"head"} if model == "ouro_2_6b" else set()
    for found in prefills:
        assert EVERY_STEP | own | closing <= found \
            <= set(costs.PARTS) - ({"head"} - closing)
    (decode,) = _found("gen_decode")
    assert EVERY_STEP | own | {"head"} <= decode <= set(costs.PARTS)
    assert _found("gen_join") == [{"cache"}]


# -- a scope changes no program ---------------------------------------------

def _served_texts(net):
    """The lowered text (no debug info) of a fresh engine's prefill and
    decode step over `net`."""
    eng = _engine(net)
    try:
        eng._init_cache_arrays()
        src = jnp.ones((1, 16), jnp.int32)
        prefill = eng._prefill.lower(eng._params, src,
                                     jnp.full((1,), 16, jnp.int32))
        decode = eng._decode.lower(eng._params, eng._cache)
        return prefill.as_text(), decode.as_text()
    finally:
        eng.close()


@pytest.mark.parametrize("model", sorted(SERVED) + ["bert_small"])
def test_the_scopes_change_no_lowered_program(model, monkeypatch):
    """With `part` a null context the lowered program is the same, letter
    for letter: a scope is metadata, and a later PR may add one without a
    chip run."""
    def texts():
        if model == "bert_small":
            held = _bert_steps()
            (low,) = costs.lowerings("gluon.train_step")
            del held
            return (low.as_text(),)
        return _served_texts(net)

    net = None if model == "bert_small" else _served_net(SERVED[model][0])
    scoped = texts()
    entered = []
    monkeypatch.setattr(costs, "part", lambda name: (
        entered.append(name), contextlib.nullcontext())[1])
    gc.collect()
    costs.reset()
    bare = texts()
    assert entered and set(entered) <= set(costs.PARTS)
    assert all("mx." not in t for t in scoped + bare)
    assert scoped == bare
