"""models.hybrid_decoder behind serving.GenerationEngine, at a small size on
the CPU: one period of four layers (three Gated DeltaNet, one gated
attention), hidden 64, 16 experts top-3 with 8 held and a shared one, chunks
of 8.  The oracle is the benchmark's plain reference
(benchmark/reference/qwen3_next_80b_a3b.py: float32 jax.numpy, the recurrence
as a scan over positions, no cache), on the same seeded weights.  Also: the
three forms of the gated delta rule against each other, the kernels in
interpret mode, the expert layer's shares with the shared expert counted
once, and a slot's recurrent state not outliving its stream."""
import json
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.monitor import events

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(REPO, "tests", "benchmark", "data", "qwen3_next_root",
                    "benchmark", "configs", "qwen3_next_tiny.json")

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
    """(config, reference module, float32 weights, system) of
    qwen3_next_tiny."""
    import jax
    import jax.numpy as jnp
    with open(TINY) as f:
        cfg = json.load(f)
    ref = _bench("reference", "qwen3_next_80b_a3b")
    builder = _bench("configs", "qwen3_next_80b_a3b")
    w = _bench(None, "weights").make(ref.spec(cfg), 11, jnp.float32,
                                     jax.devices("cpu")[0])
    system = builder.build(cfg, w, mx.cpu(0))
    system.warmup()
    yield cfg, ref, w, system
    system.close()


_FORWARD = {}


def _ref_logits(ref, w, cfg, seq):
    """Reference logits of `seq`, padded to the tiny max_len (one shape,
    one compile; the model is causal)."""
    import jax
    import jax.numpy as jnp
    if "fn" not in _FORWARD:
        _FORWARD["fn"] = jax.jit(lambda w_, t: ref.forward(w_, cfg, t))
    full = onp.full(cfg["serving"]["max_len"], cfg["eos_token_id"], onp.int32)
    full[:len(seq)] = seq
    return onp.asarray(_FORWARD["fn"](w, jnp.asarray(full)))[:len(seq)]


_JITTED = {}


def _model_fns(net, cfg):
    """The model's `init_cache` and `decode_step` as the engine traces them
    (pure functions of the parameters), jitted: called eagerly, every step
    would compile its scan anew."""
    import jax
    from incubator_mxnet_tpu.parallel.functional import extract_params
    from incubator_mxnet_tpu.serving.generation import _pure_method
    if "fns" not in _JITTED:
        L = cfg["serving"]["max_len"]
        pure = _pure_method(net, "init_cache")
        params = extract_params(net)
        init = jax.jit(lambda pv, tok, n: pure(pv, tok, n, L, None))
        step = jax.jit(_pure_method(net, "decode_step"))
        _JITTED["fns"] = (lambda tok, n: init(params, tok, n),
                          lambda *a: step(params, *a))
    return _JITTED["fns"]


def _prefill(net, cfg, prompt, bucket, pad=None):
    """A fresh row of `prompt` in a bucket, its padding noise if given:
    (cache leaves, start token, start position)."""
    import jax.numpy as jnp
    padded = onp.zeros((1, bucket), onp.int32) if pad is None else pad.copy()
    padded[0, :len(prompt)] = prompt
    cache = dict(_model_fns(net, cfg)[0](
        jnp.asarray(padded), jnp.asarray([len(prompt)], jnp.int32)))
    return cache, cache.pop("start_tok"), cache.pop("start_pos")


def _greedy(net, cfg, prompt, n_new, bucket):
    """The model's own contract with no engine: a fresh cache, greedy."""
    import jax.numpy as jnp
    step = _model_fns(net, cfg)[1]
    cache, tok, pos = _prefill(net, cfg, prompt, bucket)
    out = []
    for _ in range(n_new):
        logits, cache = step(tok, pos, cache, jnp.asarray([True]))
        out.append(int(onp.asarray(logits)[0].argmax()))
        if out[-1] == cfg["eos_token_id"]:
            break
        tok, pos = jnp.asarray([out[-1]], jnp.int32), pos + 1
    return out


# ---- the gated delta rule, three forms -------------------------------------

def _rule_inputs(T, H=3, dk=8, dv=16, seed=0):
    import jax.numpy as jnp
    rs = onp.random.RandomState(seed)
    unit = lambda a: a / onp.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(rs.randn(T, H, dk)), unit(rs.randn(T, H, dk))
    v = rs.randn(T, H, dv)
    g = -onp.exp(rs.randn(T, H) * 0.5 - 3.0)
    beta = 1.0 / (1.0 + onp.exp(-rs.randn(T, H)))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


# lengths that are and are not multiples of the chunk of 8; valid_len at the
# bucket's end, inside it, at a chunk's border, and a prompt of one token
@pytest.mark.parametrize("T,valid_len", [(32, None), (29, None), (5, None),
                                         (32, 32), (32, 21), (32, 17),
                                         (16, 9), (16, 1), (24, 2)])
def test_chunked_rule_is_the_sequential_recurrence(T, valid_len):
    from incubator_mxnet_tpu.ops import linear_attention as la
    q, k, v, g, beta = _rule_inputs(T, seed=T)
    o, s = la.gated_delta_chunked(q, k, v, g, beta, valid_len, chunk=8)
    n = T if valid_len is None else valid_len - 1
    want_o, want_s = la.gated_delta_scan(q[:n], k[:n], v[:n], g[:n], beta[:n])
    assert onp.abs(onp.asarray(s - want_s)).max() < 1e-5
    assert n == 0 or onp.abs(onp.asarray(o[:n] - want_o)).max() < 1e-5


def test_step_is_one_position_of_the_recurrence_and_touches_one_layer():
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import linear_attention as la
    B, layers = 3, 4
    q, k, v, g, beta = _rule_inputs(B, seed=7)
    rs = onp.random.RandomState(8)
    states = jnp.asarray(rs.randn(B, layers, 3, 8, 16), jnp.float32)
    o, new = la.gated_delta_step(q, k, v, g, beta, states, jnp.int32(2))
    for b in range(B):
        want_o, want_s = la.gated_delta_scan(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            states[b, 2])
        assert onp.abs(onp.asarray(o[b] - want_o[0])).max() < 1e-5
        assert onp.abs(onp.asarray(new[b, 2] - want_s)).max() < 1e-5
    others = onp.asarray(new)[:, [0, 1, 3]] == onp.asarray(states)[:, [0, 1, 3]]
    assert others.all()


def _interpret_kernels(monkeypatch):
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import linear_attention as la
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    assert la._interpret()


def _delta_rule_forms(monkeypatch):
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import linear_attention as la
    q, k, v, g, beta = _rule_inputs(29, H=2, dk=8, dv=128, seed=3)
    want_o, want_s = la.gated_delta_chunked(q, k, v, g, beta, 25, chunk=8)
    states = jnp.asarray(onp.random.RandomState(4).randn(3, 2, 2, 8, 128),
                         jnp.float32)
    want = la.gated_delta_step(q[:3], k[:3], v[:3], g[:3], beta[:3], states,
                               jnp.int32(1))
    _interpret_kernels(monkeypatch)
    o, s = la.gated_delta_chunked(q, k, v, g, beta, 25, chunk=8)
    assert onp.abs(onp.asarray(o - want_o)).max() < 1e-5
    assert onp.abs(onp.asarray(s - want_s)).max() < 1e-5
    got = la.gated_delta_step(q[:3], k[:3], v[:3], g[:3], beta[:3], states,
                              jnp.int32(1))
    assert onp.abs(onp.asarray(got[0] - want[0])).max() < 1e-5
    assert onp.abs(onp.asarray(got[1] - want[1])).max() < 1e-5


# the grouped kernel's cases: T tokens of width D, top k of E experts, `held`
# of them held from `first` on, width F, tiles of `tile` rows; `route` says
# how the picks fall, `layer` of `layers` reads stacked weights
_GROUPED = {
    # held expert 1 gets no token at all
    "an_empty_expert": dict(T=40, E=8, k=2, held=4, tile=8, route="skip_1"),
    # a run of 80 picks and three empty experts: ten whole tiles
    "every_pick_on_one": dict(T=40, E=8, k=2, held=4, tile=8, route="all_2"),
    # 37 tokens x 3: runs of any length against tiles of 16
    "ragged_runs": dict(T=37, E=16, k=3, held=8, tile=16, route="random"),
    # the held experts are 12..15 and nobody picks them: all zeros
    "no_held_pick": dict(T=40, E=16, k=2, held=4, first=12, tile=8,
                         route="low"),
    # 25 x 3 = 75 picks, no multiple of 8
    "picks_no_multiple_of_the_tile": dict(T=25, E=8, k=3, held=5, tile=8,
                                          route="random"),
    "stacked_layer_0": dict(T=40, E=8, k=2, held=4, tile=8, route="random",
                            layers=3, layer=0),
    "stacked_last_layer": dict(T=40, E=8, k=2, held=4, tile=8,
                               route="random", layers=3, layer=2),
    # the tiny presets of the two served configurations
    "keye_tiny": dict(T=48, D=64, F=32, E=8, k=2, held=4, tile=8,
                      route="random"),
    "qwen3_next_tiny": dict(T=32, D=64, F=32, E=16, k=3, held=8, tile=8,
                            route="random", layers=4, layer=3),
    # a hidden width in two blocks: the down projection summed over them
    "hidden_in_blocks": dict(T=40, D=128, F=256, E=8, k=2, held=4, tile=8,
                             route="random",
                             weight_bytes=2 * 3 * 128 * 128 * 4),
}


def _grouped_is_the_loop(monkeypatch, T, E, k, held, tile, route, D=32, F=16,
                         first=0, layers=None, layer=None, weight_bytes=None):
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    rs = onp.random.RandomState(T + E)
    rand = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32))
    lead = () if layers is None else (layers,)
    x = rand(T, D)
    wg, wu = rand(*lead, held, F, D) / 4, rand(*lead, held, F, D) / 4
    wd = rand(*lead, held, D, F) / 3
    scores = rs.randn(T, E).astype(onp.float32)
    if route == "skip_1":
        scores[:, first + 1] = -9.0
    elif route == "all_2":
        scores[:, first + 2] = 9.0
    elif route == "low":
        scores[:, first:first + held] = -9.0
    gate, expert = moe.topk_route(jnp.asarray(scores), k)
    run = lambda: onp.asarray(moe.held_experts(
        x, gate, expert, wg, wu, wd, first, tile=tile,
        layer=None if layer is None else jnp.int32(layer)))
    want = run()
    local = onp.asarray(expert) - first
    n_held = int(((local >= 0) & (local < held)).sum())
    assert (n_held == 0) == (route == "low")
    assert (onp.abs(want).max() > 0.05) == (n_held > 0)
    if weight_bytes:
        monkeypatch.setattr(moe, "_GROUPED_WEIGHT_BYTES", weight_bytes)
        assert moe._grouped_hidden_block(F, D, 4) == F // 2
    traced = events.get("moe.grouped_traces") or 0
    combined = events.get("moe.combine_traces") or 0
    _interpret_kernels(monkeypatch)
    # the counters count traces of the many-token form, which the call sites
    # of one shape share: a case of the same shapes before this one left its
    # trace behind
    moe._held_many.clear_cache()
    got = run()
    # the counters that say the kernels engage: this trace, and not the
    # CPU's of the loop above
    assert (events.get("moe.grouped_traces") or 0) == traced + 1
    assert (events.get("moe.combine_traces") or 0) == combined + 1
    assert onp.isfinite(got).all()
    # float32 rounding: the blocks of F are summed in another order
    assert onp.abs(got - want).max() < 2e-6 * max(1.0, onp.abs(want).max())


@pytest.mark.parametrize("case", ["delta_rule"] + sorted(_GROUPED))
def test_the_kernels_in_interpret_mode_are_the_jax_forms(monkeypatch, case):
    """Each Pallas kernel, run itself in interpret mode, against the
    `jax.numpy` form the CPU runs: the delta rule's two, and
    `moe.held_experts_grouped` against the loop over tiles in each of
    `_GROUPED`'s cases, to float32 rounding."""
    if case == "delta_rule":
        _delta_rule_forms(monkeypatch)
    else:
        _grouped_is_the_loop(monkeypatch, **_GROUPED[case])


def test_causal_conv_rows_are_those_before_the_last_token():
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import linear_attention as la
    rs = onp.random.RandomState(1)
    x = jnp.asarray(rs.randn(20, 6), jnp.float32)
    w = jnp.asarray(rs.randn(6, 4), jnp.float32)
    y, rows = la.causal_conv(x, w, valid_len=11)
    c = jnp.zeros((1, 3, 6))
    for t in range(10):                 # positions 0..9: before the last
        y_t, c = la.causal_conv_step(x[t][None], c, w)
        assert onp.abs(onp.asarray(y_t[0] - y[t])).max() < 1e-5
    assert (onp.asarray(rows) == onp.asarray(c[0])).all()
    y_t, _ = la.causal_conv_step(x[10][None], rows[None], w)
    assert onp.abs(onp.asarray(y_t[0] - y[10])).max() < 1e-5
    _, rows = la.causal_conv(x, w, valid_len=2)         # zeros before start
    assert (onp.asarray(rows)[:2] == 0).all()
    assert (onp.asarray(rows)[2] == onp.asarray(x[0])).all()


# ---- the model against the plain reference --------------------------------

# the whole forward pass: lengths that are no multiple of the chunk of 8
@pytest.mark.parametrize("T", [32, 29, 7])
def test_forward_matches_the_reference(tiny, T):
    cfg, ref, w, system = tiny
    tok = onp.random.RandomState(T).randint(3, 128, (2, T)).astype(onp.int32)
    out = system._net(nd.array(tok, dtype="int32")).asnumpy()
    for r in range(2):
        assert onp.abs(out[r] - _ref_logits(ref, w, cfg, tok[r])).max() < 2e-4


# valid_len below, at and above a chunk's border, in both buckets
@pytest.mark.parametrize("n_prompt", [1, 5, 9, 16, 21, 30])
def test_prefill_then_decode_logits_match_the_reference(tiny, n_prompt):
    """The model's own contract, logits compared: init_cache over a padded
    bucket, then decode_step fed the reference's sequence.  The first step
    reads the prompt's last token again: a state that had already taken it
    would apply it twice, and a scan that ran on through the padding would
    carry it; either fails here."""
    import jax.numpy as jnp
    cfg, ref, w, system = tiny
    L = cfg["serving"]["max_len"]
    rs = onp.random.RandomState(100 + n_prompt)
    seq = rs.randint(3, cfg["vocab_size"], n_prompt + 10).astype(onp.int32)
    want = _ref_logits(ref, w, cfg, seq)
    bucket = 16 if n_prompt <= 16 else 32
    noise = rs.randint(3, 128, (1, bucket)).astype(onp.int32)
    cache, tok, pos = _prefill(system._net, cfg, seq[:n_prompt], bucket,
                               noise)
    assert int(tok[0]) == seq[n_prompt - 1] and int(pos[0]) == n_prompt - 1
    assert cache["k"].shape == (1, 1, 2, L, 16)         # one full layer
    assert cache["s"].shape == (1, 3, 4, 8, 16)         # no time axis
    assert cache["c"].shape == (1, 3, 3, 2 * 16 + 64)
    step = _model_fns(system._net, cfg)[1]
    for at in range(n_prompt - 1, len(seq)):
        logits, cache = step(jnp.asarray([seq[at]], jnp.int32),
                             jnp.asarray([at], jnp.int32), cache,
                             jnp.asarray([True]))
        assert onp.abs(onp.asarray(logits)[0] - want[at]).max() < 2e-4, at


@pytest.mark.parametrize("n_prompt,n_new", [(3, 14), (8, 9), (13, 16),
                                            (21, 16), (32, 16)])
def test_engine_tokens_are_the_references_best(tiny, n_prompt, n_new):
    """submit -> _admit -> prefill -> join -> decode_step: every served
    token is the reference's best at its position (gap 0)."""
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


def test_a_retaken_slot_gives_the_tokens_a_fresh_cache_gives(tiny):
    """A recurrent state has no position to hide behind: what the slot's
    earlier stream left must be gone when the next request starts.  Every
    slot is used, then used again."""
    cfg, ref, w, system = tiny
    rs = onp.random.RandomState(77)
    first = [rs.randint(3, 128, n).astype(onp.int32) for n in (30, 12, 19)]
    for s in [system.engine.submit(p, max_new_tokens=12) for p in first]:
        s.result(120)
    again = [rs.randint(3, 128, n).astype(onp.int32) for n in (7, 25, 16)]
    streams = [system.engine.submit(p, max_new_tokens=10) for p in again]
    for p, s in zip(again, streams):
        want = _greedy(system._net, cfg, p, 10, 16 if len(p) <= 16 else 32)
        assert [int(t) for t in s.result(120)] == want


def test_counters_and_prefill_rows(tiny):
    """The step's counts reach the counters once a step, summed over live
    slots, and a gen.prefill row carries the prompt's tokens."""
    from incubator_mxnet_tpu.telemetry import spans
    cfg, ref, w, system = tiny
    names = system._net.step_counts
    assert names == ("gen.attn_context", "gdn.state_kib", "gen.cache_kib",
                     "moe.picks", "moe.picks_held", "moe.expert_max")
    before = {n: events.get(n) for n in names}
    t0 = spans._now()
    prompt = onp.arange(3, 3 + 11, dtype=onp.int32)
    toks = system.engine.submit(prompt, max_new_tokens=5).result(120)
    d = {n: events.get(n) - before[n] for n in names}
    n = len(toks)
    ctx = [11 + j for j in range(n)]
    # a slot's state: 3 layers x (4 heads x 8 x 16 float32 + 3 rows of 96
    # float32), read and written; K/V rows of 2 x 2 x 16 float32, one layer
    state = 2 * 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert d["gen.attn_context"] == sum(ctx)
    assert d["gdn.state_kib"] == n * (state // 1024)
    assert d["gen.cache_kib"] == sum((state + 256 * (c + 1)) // 1024
                                     for c in ctx)
    assert d["moe.picks"] == 4 * 3 * n
    assert 0 <= d["moe.expert_max"] <= d["moe.picks_held"] <= d["moe.picks"]
    rows = [r for r in spans.phase_log(since=t0, prefix="gen.prefill")]
    assert [r[5] for r in rows] == [11]


# ---- the expert layer: shares and the shared expert ------------------------

@pytest.mark.parametrize("tile,kernel", [(256, False), (8, False), (8, True)])
def test_the_shares_add_up_with_the_shared_expert_counted_once(
        tiny, tile, kernel, monkeypatch):
    """Held 0-3, 4-7, 8-11 and 12-15, each with the shared expert that every
    chip computes alike: their sum less three shared terms is the reference's
    uncut layer, through the forms of `held_experts`: every expert over every
    token, the loop over tiles, and the grouped kernel in interpret mode."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.sparse_decoder import HeldExperts
    cfg, ref, w, _ = tiny
    if kernel:
        _interpret_kernels(monkeypatch)
    z = dict(ref.sizes(cfg), EH=16, E0=0)
    rs = onp.random.RandomState(2)
    D, F, E = 64, 32, 16
    rand = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32))
    p = {"moe.ln": 0.1 * rand(1, D), "moe.router": rand(1, E, D),
         "moe.wg": rand(1, E, F, D) / 8, "moe.wu": rand(1, E, F, D) / 8,
         "moe.wd": rand(1, E, D, F) / 6, "moe.shared_gate": rand(1, 1, D) / 8,
         "moe.shared_wg": rand(1, F, D) / 8, "moe.shared_wu": rand(1, F, D) / 8,
         "moe.shared_wd": rand(1, D, F) / 6}
    h = rand(40, D)
    whole = onp.asarray(ref.experts(h, p, 0, z, None) - h)
    x = ref.norm(h, p["moe.ln"][0], z["eps"])
    shared = onp.asarray(
        jax.nn.sigmoid(ref.dense(x, p["moe.shared_gate"][0]))
        * ref.swiglu(x, p["moe.shared_wg"][0], p["moe.shared_wu"][0],
                     p["moe.shared_wd"][0], None))
    assert onp.abs(shared).max() > 0.05
    total = 0.0
    for lo in (0, 4, 8, 12):
        blk = HeldExperts(1, D, F, E, 3, first_held=lo, held=4, tile=tile,
                          shared_hidden=F, norm_offset=1.0)
        share = {"ln": p["moe.ln"][0], "router": p["moe.router"][0],
                 "sgate": p["moe.shared_gate"][0],
                 "sg": p["moe.shared_wg"][0], "su": p["moe.shared_wu"][0],
                 "sd": p["moe.shared_wd"][0]}
        share.update({"w" + c: p["moe.w" + c][0, lo:lo + 4] for c in "gud"})
        out = onp.asarray(blk.apply(share, h)[0] - h)
        assert onp.abs(out - shared).max() > 0.05       # its experts' terms
        total = total + out
    assert onp.abs(total - 3 * shared - whole).max() < 1e-4
