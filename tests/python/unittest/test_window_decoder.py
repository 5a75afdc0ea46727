"""models.window_decoder behind serving.GenerationEngine, at a small size on
the CPU: hidden 64, a window of 8, a leading full dense layer and two
periods of (window, window, window, full), 4 query heads in full layers and
6 in window layers over 2 key/value heads of 16, YaRN on half of a full
layer's head, 16 experts of which 4 are held, 97 tokens.  The oracle is the
benchmark's plain reference (benchmark/reference/laguna_xs2.py: float32
jax.numpy, no cache, every layer over the whole sequence under its mask),
on the same seeded weights.  Also: the ring wraps and its rows are the
band's; the four shares of the experts add up to the uncut layer; a pattern
that does not repeat is refused; an engine with no end token runs an answer
to its budget; and the other served decoders lower to the
programs they lowered to before this model's arguments were added to the
code they share."""
import hashlib
import json
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(REPO, "tests", "benchmark", "data", "laguna_root",
                    "benchmark", "configs", "laguna_tiny.json")

pytestmark = pytest.mark.gen

# float32 weights and activations on both sides: what differs is the order
# of sums (blocked attention, the experts' tiles, the ring's order of keys),
# a few float32 roundings of logits of magnitude ~4
TOL = 2e-4


def _bench(kind, name):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    return harness.load_module(kind, name) if kind else __import__(name)


def _config(**changes):
    with open(TINY) as f:
        return dict(json.load(f), **changes)


_SYSTEM = []


def _system():
    """(config, reference module, float32 weights, system) of laguna_tiny."""
    import jax
    import jax.numpy as jnp
    if not _SYSTEM:
        cfg = _config()
        ref = _bench("reference", "laguna_xs2")
        w = _bench(None, "weights").make(ref.spec(cfg), 11, jnp.float32,
                                         jax.devices("cpu")[0])
        _SYSTEM.append((cfg, ref, w, _bench("configs", "laguna_xs2").build(
            cfg, w, mx.cpu(0))))
    return _SYSTEM[0]


@pytest.fixture(scope="module", autouse=True)
def _closed():
    yield
    for *_, system in _SYSTEM:
        system.close()
    _SYSTEM.clear()


def _ref_logits(ref, w, cfg, seq):
    import jax.numpy as jnp
    return onp.asarray(ref.forward(w, cfg, jnp.asarray(seq, jnp.int32)))


def _model_fns(net, cfg, max_len=None):
    """The model's `init_cache` and `decode_step` as the engine traces them
    (pure functions of the parameters), jitted; slots of `max_len` rows, or
    the configuration's."""
    import jax
    from incubator_mxnet_tpu.parallel.functional import extract_params
    from incubator_mxnet_tpu.serving.generation import _pure_method
    L = max_len or cfg["serving"]["max_len"]
    pure = _pure_method(net, "init_cache")
    params = extract_params(net)
    init = jax.jit(lambda pv, tok, n: pure(pv, tok, n, L, None))
    step = jax.jit(_pure_method(net, "decode_step"))
    return (lambda tok, n: init(params, tok, n),
            lambda *a: step(params, *a))


def _served_logits(fns, seq, n_prompt, bucket, rs, poison=None):
    """Logits of positions n_prompt - 1 .. len(seq) - 1 by a prefill of
    seq[:n_prompt] in a bucket whose padding is noise, then one step a later
    token; `poison(cache, pos)` may spoil the cache before each step.
    Returns (logits, the prefill's leaves, the last cache)."""
    import jax.numpy as jnp
    padded = rs.randint(3, 97, size=(1, bucket)).astype(onp.int32)
    padded[0, :n_prompt] = seq[:n_prompt]
    cache = dict(fns[0](jnp.asarray(padded),
                        jnp.asarray([n_prompt], jnp.int32)))
    tok, pos = cache.pop("start_tok"), cache.pop("start_pos")
    first = {k: onp.asarray(v) for k, v in cache.items()}
    out = []
    for t in range(n_prompt - 1, len(seq)):
        assert int(tok[0]) == seq[t] and int(pos[0]) == t
        if poison is not None:
            cache = poison(cache, t)
        logits, cache = fns[1](tok, pos, cache, jnp.asarray([True]))
        out.append(onp.asarray(logits)[0])
        cache = dict(cache)
        if t + 1 < len(seq):
            tok, pos = jnp.asarray([seq[t + 1]], jnp.int32), pos + 1
    return onp.stack(out), first, cache


@pytest.mark.parametrize("length", [5, 16, 32])
def test_forward_is_the_reference(length):
    from incubator_mxnet_tpu import nd
    cfg, ref, w, system = _system()
    seq = onp.random.RandomState(length).randint(3, 97, size=length)
    got = system._net.forward(nd.array(seq[None].astype(onp.int32),
                                       ctx=mx.cpu(0), dtype="int32"))
    want = _ref_logits(ref, w, cfg, seq)
    assert got.shape == (1, length, 97)
    assert onp.abs(got.asnumpy()[0] - want).max() < TOL


# a prompt inside the window; longer than the window in a bucket it does
# not fill (the ring takes its last 8 valid rows, never the noise after
# them); a whole bucket; one in the bucket of 32.  Each then decodes three
# windows' worth of tokens and more, so the ring wraps three times
@pytest.mark.parametrize("n_prompt,bucket", [(5, 16), (13, 16), (16, 16),
                                             (21, 32)])
def test_prefill_then_decode_is_the_full_forward(n_prompt, bucket):
    cfg, ref, w, system = _system()
    rs = onp.random.RandomState(n_prompt)
    seq = rs.randint(3, 97, size=n_prompt + 3 * 8 + 2)
    got, first, cache = _served_logits(_model_fns(system._net, cfg), seq,
                                       n_prompt, bucket, rs)
    want = _ref_logits(ref, w, cfg, seq)[n_prompt - 1:]
    assert onp.abs(got - want).max() < TOL
    assert cache["kf"].shape == (1, 3, 2, 48, 16)
    assert cache["kw"].shape == cache["vw"].shape == (1, 6, 2, 8, 16)
    # the prefill's rings: position t at t mod 8 for the last min(n, 8)
    # valid positions, zeros where no position is; its full rows at t
    for t in range(8):
        held = onp.abs(first["kw"][0, :, :, t]).max()
        assert (held > 0) == (t < n_prompt)
    counts = dict(zip(system._net.step_counts,
                      onp.asarray(cache["counts"])[0]))
    last = len(seq) - 1             # the position of the last step
    assert counts["gen.attn_context"] == 3 * (last + 1)
    # leaves of 16-wide heads are no kernel's: the einsums read all 48 rows
    assert counts["gen.attn_rows_read"] == 3 * 48
    assert counts["window.rows_needed"] == 6 * 8
    assert counts["window.rows_read"] == 6 * 8
    assert counts["moe.picks"] == 8 * 4


def test_a_ring_row_outside_the_band_is_never_read():
    """Before every step, what the step is about to overwrite is poisoned:
    the full leaves' rows at and past the position, and the ring's row at
    the position's ring index (the row that leaves the band).  The logits
    do not move.  Poisoning a row INSIDE the band moves them."""
    cfg, ref, w, system = _system()
    fns = _model_fns(system._net, cfg)
    seq = onp.random.RandomState(3).randint(3, 97, size=36)

    def leaving(cache, t):
        kf, kw = onp.array(cache["kf"]), onp.array(cache["kw"])
        kf[:, :, :, t:] = 1e4
        kw[:, :, :, t % 8] = 1e4
        return dict(cache, kf=kf, kw=kw)

    clean, _, _ = _served_logits(fns, seq, 12, 16, onp.random.RandomState(4))
    got, _, _ = _served_logits(fns, seq, 12, 16, onp.random.RandomState(5),
                               leaving)
    assert onp.abs(got - clean).max() < 1e-5

    def inside(cache, t):
        kw = onp.array(cache["kw"])
        kw[:, :, :, (t - 3) % 8] = 1e4          # position t - 3: in the band
        return dict(cache, kw=kw)

    moved, _, _ = _served_logits(fns, seq, 12, 16, onp.random.RandomState(4),
                                 inside)
    assert onp.abs(moved - clean).max() > 1e-2


@pytest.mark.parametrize("tokens", [3, 40])
def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(tokens):
    """16 experts held four ways, four by each share (first_held 0, 4, 8,
    12), the few-token form (3 tokens) and the many-token form (40): each
    share's result less what every share computes alike (the residual and
    the shared expert) is its held experts' terms, and those four sums with
    the common part once are the reference's layer with all 16 held."""
    import functools
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.sparse_decoder import HeldExperts
    from incubator_mxnet_tpu.parallel import moe
    cfg = _config(num_local_experts=16)
    ref = _bench("reference", "laguna_xs2")
    w = _bench(None, "weights").make(ref.spec(cfg), 5, jnp.float32,
                                     jax.devices("cpu")[0])
    z = ref.sizes(cfg)
    h = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64))
    m = 2                                        # a sparse layer
    with jax.default_matmul_precision("highest"):
        want = onp.asarray(ref.experts(h, w, m, z, None))
    p = {"ln": w["moe.ln"][m], "router": w["moe.router"][m],
         "sg": w["moe.shared_wg"][m], "su": w["moe.shared_wu"][m],
         "sd": w["moe.shared_wd"][m]}
    route = functools.partial(moe.topk_route, scale=2.5)
    common = None
    terms = []
    for first in (0, 4, 8, 12):
        share = HeldExperts(1, 64, 16, 16, 4, first, 4, tile=8,
                            shared_hidden=24, route=route, shared_gate=False)
        own = dict(p, **{n: w["moe." + n][m, first:first + 4]
                         for n in ("wg", "wu", "wd")})
        out, held, _ = share.apply(own, h)
        none = dict(own, **{n: jnp.zeros_like(own[n])
                            for n in ("wg", "wu", "wd")})
        alike = onp.asarray(share.apply(none, h)[0])
        common = alike if common is None else common
        assert onp.abs(alike - common).max() < 1e-6
        terms.append(onp.asarray(out) - alike)
        assert int(jnp.sum(held)) <= 4 * tokens
    got = common + sum(terms)
    assert onp.abs(got - want).max() < 1e-4 * max(1.0, onp.abs(want).max())


@pytest.mark.parametrize("layer_types,mlp,heads,why", [
    # one period after the dense layer is no repeat
    (["full_attention", "sliding_attention", "full_attention"],
     ["dense", "sparse", "sparse"], [4, 6, 4], "two"),
    # the kinds after the dense layer do not repeat
    (["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"] * 2,
     ["dense"] + ["sparse"] * 5, [4, 6, 6, 6, 4, 4], "two"),
    # a dense layer after a sparse one
    (["full_attention", "sliding_attention", "full_attention"] * 2,
     ["dense", "sparse", "dense", "sparse", "sparse", "sparse"],
     [4, 6, 4, 6, 4, 6], "dense layers before"),
    # two head counts in one kind
    (["full_attention"] + ["sliding_attention", "full_attention"] * 2,
     ["dense"] + ["sparse"] * 4, [4, 6, 4, 8, 4], "one count a kind"),
])
def test_a_pattern_that_does_not_repeat_is_refused(layer_types, mlp, heads,
                                                   why):
    from incubator_mxnet_tpu.models import WindowDecoder
    cfg = _config()
    with pytest.raises(ValueError, match=why):
        WindowDecoder(97, 64, layer_types, mlp, heads, 2, 16, 8, 96, 16, 16,
                      4, cfg["rope_parameters"], shared_hidden=24)


def test_an_engine_with_no_end_token_runs_every_answer_to_its_budget():
    # the tiny preset is built as the cell is (`serving.ignore_eos`): an
    # answer is as long as its budget; an engine over the same model whose
    # end token is one the answer holds stops right after it
    from incubator_mxnet_tpu.serving import GenerationEngine
    cfg, _, _, system = _system()
    assert cfg["serving"]["ignore_eos"] and system.engine._eos is None
    prompt = onp.arange(3, 23, dtype=onp.int32)
    full = [int(t) for t in system.submit(prompt, 12).result(timeout=300)]
    assert len(full) == 12
    end = full[5]
    eng = GenerationEngine(system._net, bos=cfg["bos_token_id"], eos=end,
                           ctx=mx.cpu(0), slots=3, max_len=48,
                           prompt_buckets=(16, 32), queue_cap=8)
    try:
        short = [int(t) for t in eng.submit(prompt, max_new_tokens=12)
                 .result(timeout=300)]
    finally:
        eng.close()
    assert short == full[:full.index(end) + 1]


def _interpret_kernels(monkeypatch, on):
    from incubator_mxnet_tpu import config
    monkeypatch.setattr(config, "_OVERRIDES", dict(
        {k: v for k, v in config._OVERRIDES.items()
         if k != "MXNET_PALLAS_INTERPRET"},
        **({"MXNET_PALLAS_INTERPRET": True} if on else {})))
    if not on:
        monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)


def test_a_step_through_the_grouped_kernel_is_the_masked_step(monkeypatch):
    """A model whose leaves the kernel tiles (heads of 128, a ring of 256,
    slots of 768 rows: three row blocks of 256), float32: a prefill of 300
    tokens in a bucket of 512 (past a block border; the ring wrapped), then
    three steps.  With the kernel itself (interpret mode) the logits are
    the masked einsums' (the CPU's path); tracing the decode step counts
    five layer bodies lowered with the kernel (the leading full layer and
    the period's four), and none on the CPU; `gen.attn_rows_read` is each
    full layer's length rounded up to the row block, `gen.attn_context`
    the length itself."""
    from incubator_mxnet_tpu.models import WindowDecoder
    from incubator_mxnet_tpu.monitor import events
    cfg = _config()
    types = ["full_attention"] + (["sliding_attention"] * 3
                                  + ["full_attention"]) * 2
    net = WindowDecoder(97, 64, types, ["dense"] + ["sparse"] * 8,
                        [4 if t == "full_attention" else 6 for t in types],
                        2, 128, 256, 96, 16, 16, 4, cfg["rope_parameters"],
                        shared_hidden=24, routed_scale=2.5, first_held=0,
                        experts_held=4, expert_tile=8)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    seq = onp.random.RandomState(9).randint(3, 97, size=303)
    runs = {}
    for on in (False, True):
        _interpret_kernels(monkeypatch, on)
        traced = events.get("attn.grouped_kernel_traces") or 0
        got, _, cache = _served_logits(_model_fns(net, cfg, 768), seq, 300,
                                       512, onp.random.RandomState(1))
        runs[on] = got
        assert (events.get("attn.grouped_kernel_traces") or 0) - traced == \
            (5 if on else 0)
        counts = dict(zip(net.step_counts, onp.asarray(cache["counts"])[0]))
        assert counts["gen.attn_context"] == 3 * 303
        assert counts["gen.attn_rows_read"] == 3 * 512
        assert counts["window.rows_needed"] == counts["window.rows_read"] \
            == 6 * 256
    assert onp.abs(runs[True] - runs[False]).max() < \
        1e-5 * max(1.0, onp.abs(runs[False]).max())


# -- the other served decoders lower as they did -----------------------------

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


# sha256 of the lowered text (no debug info) of prefill (16-token bucket),
# join and decode step of each tiny decoder, as the tree lowered them before
# `masked_decode_attention` took `part`, `topk_route` took `scale` and
# `costs.PARTS` took `window`; the prefills of the three with experts as
# they lower since the many-token form of `moe.held_experts` is one jitted
# function (`moe._held_many`) whose kernel form ends in the combine: their
# joins and decode steps are the same programs as before
LOWERED = {
    "keye_vl2_30b_a3b": (
        "3fb87e597024c772717ddc3b9c419eb333600bd58fb59b5c18acb4155c2dec4a",
        "1379ddef5ab750d512dc75b5a4971316879e9b69c1cf76af42df5a6e051c7c94",
        "0891656ad5cb6bb3e2cb8e07ad338bcdcfaea7576843c9c5ff0a43e94c270f0e"),
    "qwen3_next_80b_a3b": (
        "93401fd3931c4ff1cac5125c369faabb2b38b59ade008bfc37da2e914e5597cf",
        "15d53285859d63dcad7d4ed7d4b562ee736ddd92546ee3927e12e7e06c0441b1",
        "f4288a75bf10ed3c8be6d21b137a0037446f815e3df3b8c3719dc9e0bd85cc5c"),
    "deepseek_v2": (
        "a6aac5d3ac113c5cdcb6e9f1bf4f9834b0140805a55feab9c56926917fb55e80",
        "aeb0c75f20ae9ce5c06bce374c9908013515909007a94bfbe349a9c50f0580d2",
        "16296867cc51a7cb18761fcf11c6e97d384d8205afead011556d38260aebd8eb"),
    "ouro_2_6b": (
        "3a62df0d81f4b6a673c7c1d9b7ada4192de10963bbea318fb7758a87e578c5f1",
        "3762c0cc888399a9e1a2439e330f208dd54240a88129a9e5285d4a740feca134",
        "b35db9172c80cedce1cebd70d8e06cc1b23243cb3e9e57c5892644e1dbe466bf"),
}
BUILD = {"keye_vl2_30b_a3b": _keye, "qwen3_next_80b_a3b": _qwen3_next,
         "deepseek_v2": _deepseek_v2, "ouro_2_6b": _ouro}


def _lowered(net):
    """sha256 of the lowered prefill, join and decode of a fresh engine."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.serving import GenerationEngine
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=3,
                           max_len=48, prompt_buckets=(16, 32), queue_cap=8)
    try:
        eng._init_cache_arrays()
        src = jnp.ones((1, 16), jnp.int32)
        n = jnp.full((1,), 16, jnp.int32)
        prefill = eng._prefill.lower(eng._params, src, n)
        row = eng._prefill(eng._params, src, n)
        join = eng._join.lower(eng._cache, row, jnp.zeros((2,), jnp.int32))
        decode = eng._decode.lower(eng._params, eng._cache)
        return tuple(hashlib.sha256(low.as_text().encode()).hexdigest()
                     for low in (prefill, join, decode))
    finally:
        eng.close()


@pytest.mark.parametrize("model", sorted(LOWERED))
def test_the_other_decoders_lower_to_the_same_programs(model, monkeypatch):
    # a benchmark rehearsal earlier in this process leaves
    # MXNET_PALLAS_INTERPRET set, and the kernels' interpreters lower to
    # other programs: the recorded ones are the compiled kernels' calls
    from incubator_mxnet_tpu import config
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(config, "_OVERRIDES", {
        k: v for k, v in config._OVERRIDES.items()
        if k != "MXNET_PALLAS_INTERPRET"})
    assert _lowered(BUILD[model]()) == LOWERED[model]
