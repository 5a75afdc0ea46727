"""models.sparse_decoder behind serving.GenerationEngine, at a small size on
the CPU: 2 layers, hidden 64, 8 experts top-2 with 4 held, top-k 8 against
contexts up to 48.  The oracle is the benchmark's plain reference
(benchmark/reference/keye_vl2_30b_a3b.py: float32 jax.numpy, a stable
sort for the selection, every expert over every token), on the same seeded
weights.  Also: the selection against a sort with ties and short rows, the
expert layer's shares adding up to the uncut layer, and the one engine
contract holding for an encoder-decoder model as before."""
import json
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.serving import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(REPO, "tests", "benchmark", "data", "keye_root",
                    "benchmark", "configs", "keye_tiny.json")

pytestmark = pytest.mark.gen


def _bench(kind, name):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    return harness.load_module(kind, name) if kind else \
        __import__(name)


@pytest.fixture(scope="module")
def tiny():
    """(config, reference module, float32 weights, system) of keye_tiny."""
    import jax
    import jax.numpy as jnp
    with open(TINY) as f:
        cfg = json.load(f)
    ref = _bench("reference", "keye_vl2_30b_a3b")
    builder = _bench("configs", "keye_vl2_30b_a3b")
    w = _bench(None, "weights").make(ref.spec(cfg), 11, jnp.float32,
                                     jax.devices("cpu")[0])
    system = builder.build(cfg, w, mx.cpu(0))
    system.warmup()
    yield cfg, ref, w, system
    system.close()


def _ref_logits(ref, w, cfg, seq):
    """Reference logits of `seq`, padded to the tiny max_len."""
    import jax.numpy as jnp
    full = onp.full(cfg["serving"]["max_len"], cfg["eos_token_id"], onp.int32)
    full[:len(seq)] = seq
    return onp.asarray(ref.forward(w, cfg, jnp.asarray(full)))[:len(seq)]


# prompts shorter than top-k 8, around it, and longer; buckets 16 and 32
@pytest.mark.parametrize("n_prompt,n_new", [(3, 14), (8, 9), (13, 16),
                                            (21, 16), (32, 16)])
def test_engine_tokens_are_the_references_best(tiny, n_prompt, n_new):
    """submit -> _admit -> prefill -> join -> decode_step: every served
    token is the reference's best at its position (gap 0), through the
    cache, across the point where the context passes top-k."""
    cfg, ref, w, system = tiny
    rs = onp.random.RandomState(n_prompt)
    prompt = rs.randint(3, cfg["vocab_size"], n_prompt).astype(onp.int32)
    toks = system.engine.submit(prompt, max_new_tokens=n_new).result(120)
    assert 1 <= len(toks) <= n_new
    assert len(toks) == n_new or toks[-1] == cfg["eos_token_id"]
    logits = _ref_logits(ref, w, cfg, onp.concatenate([prompt, toks[:-1]]))
    at = n_prompt - 1 + onp.arange(len(toks))
    gap = logits[at].max(-1) - logits[at, toks]
    assert gap.max() <= 1e-4, gap


@pytest.mark.parametrize("n_prompt", [5, 16, 30])
def test_prefill_then_decode_logits_match_the_reference(tiny, n_prompt):
    """The model's own contract, logits compared: init_cache over a padded
    bucket, then decode_step fed the reference's sequence."""
    cfg, ref, w, system = tiny
    net = system._net
    L = cfg["serving"]["max_len"]
    rs = onp.random.RandomState(100 + n_prompt)
    seq = rs.randint(3, cfg["vocab_size"], n_prompt + 10).astype(onp.int32)
    want = _ref_logits(ref, w, cfg, seq)
    bucket = 16 if n_prompt <= 16 else 32
    padded = onp.zeros((1, bucket), onp.int32)
    padded[0, :n_prompt] = seq[:n_prompt]
    cache = net.init_cache(nd.array(padded, dtype="int32"),
                           nd.array([n_prompt], dtype="int32"), L)
    assert int(cache.pop("start_tok").asnumpy()[0]) == seq[n_prompt - 1]
    assert int(cache.pop("start_pos").asnumpy()[0]) == n_prompt - 1
    assert cache["k"].shape == (1, 2, 2, L, 16)          # head-major
    assert cache["ki"].shape == (1, 2, L, 8)
    for pos in range(n_prompt - 1, len(seq)):
        logits, cache = net.decode_step(
            nd.array([seq[pos]], dtype="int32"),
            nd.array([pos], dtype="int32"), cache,
            nd.array([True], dtype="bool"))
        assert onp.abs(logits.asnumpy()[0] - want[pos]).max() < 2e-4, pos
    counts = cache["counts"].asnumpy()[0]
    ctx = len(seq)
    assert list(counts[:3]) == [2 * ctx, 2 * min(8, ctx), 2 * 2]


def test_forward_matches_the_reference(tiny):
    cfg, ref, w, system = tiny
    tok = onp.random.RandomState(5).randint(3, 128, (2, 32)).astype(onp.int32)
    out = system._net(nd.array(tok, dtype="int32")).asnumpy()
    for r in range(2):
        want = _ref_logits(ref, w, cfg, tok[r])
        assert onp.abs(out[r] - want).max() < 2e-4


def test_counters_and_prefill_rows(tiny):
    """The step's counts reach the counters once a step, summed over live
    slots, and a gen.prefill row carries the prompt's tokens."""
    from incubator_mxnet_tpu.telemetry import spans
    cfg, ref, w, system = tiny
    names = system._net.step_counts
    before = {n: events.get(n) for n in names}
    t0 = spans._now()
    prompt = onp.arange(3, 3 + 11, dtype=onp.int32)
    toks = system.engine.submit(prompt, max_new_tokens=5).result(120)
    d = {n: events.get(n) - before[n] for n in names}
    n, layers = len(toks), cfg["num_hidden_layers"]
    ctx = [11 + j for j in range(n)]
    assert d["gen.attn_context"] == layers * sum(ctx)
    assert d["gen.attn_selected"] == layers * sum(min(8, c) for c in ctx)
    assert d["moe.picks"] == layers * 2 * n
    assert 0 <= d["moe.expert_max"] <= d["moe.picks_held"] <= d["moe.picks"]
    rows = [r for r in spans.phase_log(since=t0, prefix="gen.prefill")]
    assert [r[5] for r in rows] == [11]


def test_a_stream_retires_when_its_position_reaches_max_len(tiny):
    """Prompt and new tokens share the slot's max_len rows: the budget is by
    position, whatever max_new asks."""
    cfg, ref, w, system = tiny
    L = cfg["serving"]["max_len"]
    prompt = onp.random.RandomState(9).randint(3, 128, 32).astype(onp.int32)
    toks = system.engine.submit(prompt, max_new_tokens=L).result(120)
    assert len(toks) <= L - 32 + 1
    assert len(toks) == L - 32 + 1 or toks[-1] == cfg["eos_token_id"]


# ---- the selection -----------------------------------------------------

def _sorted_topk(scores, valid, k):
    """Mask by a stable sort: best first, ties to the lower index."""
    out = onp.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        idx = onp.nonzero(valid[r])[0]
        order = idx[onp.argsort(-scores[r, idx], kind="stable")]
        out[r, order[:k]] = True
    return out


@pytest.mark.parametrize("case", ["distinct", "ties", "all_equal", "short",
                                  "negative_zero", "large"])
def test_select_mask_equals_a_sort(case):
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    rs = onp.random.RandomState(3)
    n, k = 40, 8
    x = rs.randn(6, n).astype(onp.float32)
    valid = onp.ones((6, n), bool)
    if case == "ties":
        x = onp.round(x * 2) / 2            # many equal scores at the border
    elif case == "all_equal":
        x[:] = 0.25
    elif case == "short":                   # t < k: fewer valid than k
        for r in range(6):
            valid[r, r + 1:] = False
    elif case == "negative_zero":
        x = onp.where(rs.rand(6, n) < 0.5, 0.0, -0.0).astype(onp.float32) \
            * onp.sign(rs.randn(6, n)).astype(onp.float32)
    elif case == "large":                   # the one-bit-a-pass loop
        n, k = 1 << 18, 2048
        x = onp.round(rs.randn(6, n).astype(onp.float32) * 64) / 64
        valid = onp.ones((6, n), bool)
        valid[0, 1000:] = False
    want = _sorted_topk(x, valid, k)
    got = onp.asarray(A.select_mask(jnp.asarray(x), jnp.asarray(valid), k))
    assert (got == want).all()


# ---- the expert layer ----------------------------------------------------

def _interpret_kernels(monkeypatch):
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.parallel import moe
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    assert moe._interpret()


@pytest.mark.parametrize("tile,kernel", [(256, False), (8, False), (8, True),
                                         (16, True)])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        tiny, tile, kernel, monkeypatch):
    """Held 0-3 plus held 4-7 is what the reference gives for all 8, through
    the forms of the layer: every expert over every token (few tokens),
    sorted runs in tiles by the loop (many), and by the grouped kernel
    itself in interpret mode."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    cfg, ref, w, _ = tiny
    if kernel:
        _interpret_kernels(monkeypatch)
    rs = onp.random.RandomState(2)
    D, F, E, k = 64, 32, 8, 2
    x = jnp.asarray(rs.randn(40, D).astype(onp.float32))
    p = {"moe.router": jnp.asarray(rs.randn(1, E, D).astype(onp.float32)),
         "moe.wg": jnp.asarray(rs.randn(1, E, F, D).astype(onp.float32)) / 8,
         "moe.wu": jnp.asarray(rs.randn(1, E, F, D).astype(onp.float32)) / 8,
         "moe.wd": jnp.asarray(rs.randn(1, E, D, F).astype(onp.float32)) / 6}
    z = dict(ref.sizes(cfg), EH=E, E0=0)
    whole = onp.asarray(ref.experts(x, p, 0, z, None))
    gate, expert = moe.topk_route(x @ p["moe.router"][0].T, k)
    assert onp.allclose(onp.asarray(gate).sum(-1), 1.0, atol=1e-6)
    shares = [onp.asarray(moe.held_experts(
        x, gate, expert, p["moe.wg"][0, lo:lo + 4], p["moe.wu"][0, lo:lo + 4],
        p["moe.wd"][0, lo:lo + 4], lo, tile=tile)) for lo in (0, 4)]
    assert onp.abs(shares[0]).max() > 0.1 and onp.abs(shares[1]).max() > 0.1
    assert onp.abs(shares[0] + shares[1] - whole).max() < 1e-4
    # and the load the counters report
    picks, fullest = moe.held_load(expert, 0, 4)
    local = onp.asarray(expert)
    assert int(picks.sum()) == int((local < 4).sum())
    assert int(fullest.sum()) == max((local == e).sum() for e in range(4))


@pytest.mark.parametrize("kernel", [False, True])
def test_every_pick_on_one_held_expert_is_not_dropped(kernel, monkeypatch):
    """Dropless: all tokens routed to the same two experts, far past any
    even share, still get their full terms, from the loop over tiles and
    from the grouped kernel (in interpret mode) alike."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    if kernel:
        _interpret_kernels(monkeypatch)
    rs = onp.random.RandomState(4)
    T, D, F = 50, 16, 8
    x = jnp.asarray(rs.randn(T, D).astype(onp.float32))
    wg, wu = (jnp.asarray(rs.randn(4, F, D).astype(onp.float32)) / 4
              for _ in range(2))
    wd = jnp.asarray(rs.randn(4, D, F).astype(onp.float32)) / 3
    gate = jnp.full((T, 2), 0.5)
    expert = jnp.tile(jnp.asarray([[2, 1]], jnp.int32), (T, 1))
    got = moe.held_experts(x, gate, expert, wg, wu, wd, 0, tile=8)
    one = lambda e: (jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T)) @ wd[e].T
    assert onp.abs(onp.asarray(got - 0.5 * (one(1) + one(2)))).max() < 1e-4


# ---- the one contract ----------------------------------------------------

def test_an_encoder_decoder_row_starts_at_bos_zero_and_streams_as_before():
    """The join takes tok/pos from the prefilled row: a model that names no
    start gets (bos, 0), and its stream is what the greedy oracle gives."""
    from incubator_mxnet_tpu.contrib.text.decode import greedy_translate
    from incubator_mxnet_tpu.models.transformer import transformer_nmt_small
    mx.random.seed(5)
    V, BOS, EOS = 23, 1, 2
    net = transformer_nmt_small(V, V, dropout=0.0)
    net.initialize(force_reinit=True)
    eng = GenerationEngine(net, bos=BOS, eos=EOS, slots=2, max_len=16,
                           prompt_buckets=(4, 8))
    try:
        eng.warmup()
        import jax
        src = onp.array([[5, 6, 7, 0]], onp.int32)
        row = eng._prefill(eng._params, jax.numpy.asarray(src),
                           jax.numpy.asarray([3], onp.int32))
        assert set(row) == {"m", "tok", "pos"}
        assert int(row["tok"][0]) == BOS and int(row["pos"][0]) == 0
        assert "start_tok" not in row["m"]
        prompt = onp.array([5, 6, 7], onp.int32)
        toks = [int(t) for t in eng.submit(prompt, max_new_tokens=9).result(60)]
        want = [int(t) for t in greedy_translate(
            net, nd.array(prompt[None], dtype="int32"), BOS, EOS,
            max_len=9)[0]]
        if EOS in want:
            want = want[:want.index(EOS) + 1]
        assert toks == want
    finally:
        eng.close()
