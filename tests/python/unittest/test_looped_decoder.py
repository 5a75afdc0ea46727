"""models.looped_decoder behind serving.GenerationEngine, at a small size on
the CPU: hidden 64, 4 heads of 16, feed-forward 96, 3 layers run 3 times,
97 tokens.  The oracle is the benchmark's plain reference
(benchmark/reference/ouro_2_6b.py: float32 jax.numpy, no cache, every pass
causal attention over the whole sequence), on the same seeded weights.
Also: pass r of layer l reads only the rows (r, l) of the cache; one pass
is the plain stack and three are not; the exit rule at thresholds 0.5 and
1; the stacked leaves through the attention's kernel in interpret mode; and
the engine's step updates the cache in place."""
import json
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.monitor import events

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(REPO, "tests", "benchmark", "data", "ouro_root",
                    "benchmark", "configs", "ouro_tiny.json")

pytestmark = pytest.mark.gen


def _bench(kind, name):
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    return harness.load_module(kind, name) if kind else __import__(name)


def _config(**changes):
    with open(TINY) as f:
        return dict(json.load(f), **changes)


_SYSTEMS = {}


def _system(loops=3, threshold=1.0):
    """(config, reference module, float32 weights, system) of ouro_tiny with
    `loops` passes and the exit threshold `threshold`; the weights are the
    same whatever the two are (no parameter's shape depends on them)."""
    import jax
    import jax.numpy as jnp
    key = (loops, threshold)
    if key not in _SYSTEMS:
        cfg = _config(total_ut_steps=loops, early_exit_threshold=threshold)
        ref = _bench("reference", "ouro_2_6b")
        w = _bench(None, "weights").make(ref.spec(cfg), 11, jnp.float32,
                                         jax.devices("cpu")[0])
        _SYSTEMS[key] = (cfg, ref, w,
                         _bench("configs", "ouro_2_6b").build(cfg, w,
                                                              mx.cpu(0)))
    return _SYSTEMS[key]


@pytest.fixture(scope="module", autouse=True)
def _closed():
    yield
    for *_, system in _SYSTEMS.values():
        system.close()
    _SYSTEMS.clear()


def _ref_logits(ref, w, cfg, seq):
    import jax.numpy as jnp
    return onp.asarray(ref.forward(w, cfg, jnp.asarray(seq, jnp.int32)))


def _model_fns(net, cfg):
    """The model's `init_cache` and `decode_step` as the engine traces them
    (pure functions of the parameters), jitted."""
    import jax
    from incubator_mxnet_tpu.parallel.functional import extract_params
    from incubator_mxnet_tpu.serving.generation import _pure_method
    L = cfg["serving"]["max_len"]
    pure = _pure_method(net, "init_cache")
    params = extract_params(net)
    init = jax.jit(lambda pv, tok, n: pure(pv, tok, n, L, None))
    step = jax.jit(_pure_method(net, "decode_step"))
    return (lambda tok, n: init(params, tok, n),
            lambda *a: step(params, *a))


def _prefill(fns, prompt, bucket, rs):
    """A fresh row of `prompt` in a bucket whose padding is noise: (cache
    leaves, start token, start position)."""
    import jax.numpy as jnp
    padded = rs.randint(3, 97, size=(1, bucket)).astype(onp.int32)
    padded[0, :len(prompt)] = prompt
    cache = dict(fns[0](jnp.asarray(padded),
                        jnp.asarray([len(prompt)], jnp.int32)))
    return cache, cache.pop("start_tok"), cache.pop("start_pos")


def _served_logits(fns, seq, n_prompt, bucket, rs, poison=None):
    """Logits of positions n_prompt - 1 .. len(seq) - 1 by a prefill of
    seq[:n_prompt] and one step a later token; `poison(cache, pos)` may
    spoil the cache before each step."""
    import jax.numpy as jnp
    cache, tok, pos = _prefill(fns, seq[:n_prompt], bucket, rs)
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
    return onp.stack(out), cache


@pytest.mark.parametrize("length", [5, 16, 29])
def test_forward_is_the_reference(length):
    from incubator_mxnet_tpu import nd
    cfg, ref, w, system = _system()
    seq = onp.random.RandomState(length).randint(3, 97, size=length)
    got = system._net.forward(nd.array(seq[None].astype(onp.int32),
                                       ctx=mx.cpu(0), dtype="int32"))
    want = _ref_logits(ref, w, cfg, seq)
    assert got.shape == (1, length, 97)
    assert onp.abs(got.asnumpy()[0] - want).max() < 2e-4


# two prompt lengths in the bucket of 16 (one a whole bucket), one in the
# bucket of 32
@pytest.mark.parametrize("n_prompt,bucket", [(9, 16), (16, 16), (21, 32)])
def test_prefill_then_decode_is_the_full_forward(n_prompt, bucket):
    cfg, ref, w, system = _system()
    rs = onp.random.RandomState(n_prompt)
    seq = rs.randint(3, 97, size=n_prompt + 12)
    got, cache = _served_logits(_model_fns(system._net, cfg), seq, n_prompt,
                                bucket, rs)
    want = _ref_logits(ref, w, cfg, seq)[n_prompt - 1:]
    assert onp.abs(got - want).max() < 2e-4
    assert cache["k"].shape == (1, 9, 4, 48, 16)
    counts = dict(zip(system._net.step_counts, onp.asarray(cache["counts"])[0]))
    assert counts["loop.passes"] == 3 and counts["loop.tokens"] == 1
    assert counts["loop.exit_pass"] == 2
    assert counts["gen.attn_context"] == 9 * len(seq)


@pytest.mark.parametrize("leaf", ["k", "v"])
def test_a_pass_of_a_layer_reads_only_its_own_rows(leaf):
    """Before every step, the rows at and past the position are poisoned at
    every (pass, layer) (the step writes its own before it reads), and in a
    second run ALL rows of the indices a given (pass, layer) does not own
    are swapped round among each other: pass r of layer l then reads other
    indices' rows wherever it does not go by r * L + l."""
    cfg, ref, w, system = _system()
    fns = _model_fns(system._net, cfg)
    rs = onp.random.RandomState(3)
    seq = rs.randint(3, 97, size=20)

    def past(cache, t):
        a = onp.array(cache[leaf])
        a[:, :, :, t:] = 1e4
        return dict(cache, **{leaf: a})

    clean, _ = _served_logits(fns, seq, 8, 16, onp.random.RandomState(4))
    got, _ = _served_logits(fns, seq, 8, 16, onp.random.RandomState(5), past)
    assert onp.abs(got - clean).max() < 1e-5

    def rolled(cache, t):
        # every index's rows moved to the next index: what (r, l) wrote is
        # no longer where (r, l) reads
        return dict(cache, **{leaf: onp.roll(onp.array(cache[leaf]), 1, 1)})

    moved, _ = _served_logits(fns, seq, 8, 16, onp.random.RandomState(4),
                              rolled)
    assert onp.abs(moved[1:] - clean[1:]).max() > 1e-2


def test_one_pass_is_the_plain_stack_and_three_are_not():
    """R = 1: embedding, the L layers once, the final norm, the head.  With
    the same weights, R = 3 gives other logits."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd
    cfg, ref, w, once = _system(loops=1)
    seq = onp.random.RandomState(8).randint(3, 97, size=14)
    z = ref.sizes(cfg)
    with jax.default_matmul_precision("highest"):
        h = w["embed"][jnp.asarray(seq)]
        for l in range(z["L"]):
            h = ref.layer(h, w, l, z, None)
        plain = onp.asarray(ref.dense(ref.norm(h, w["norm"], z["eps"]),
                                      w["head"]))
    tokens = nd.array(seq[None].astype(onp.int32), ctx=mx.cpu(0),
                      dtype="int32")
    got = once._net.forward(tokens).asnumpy()[0]
    assert onp.abs(got - plain).max() < 2e-4
    thrice = _system(loops=3)[3]._net.forward(tokens).asnumpy()[0]
    assert onp.abs(thrice - plain).max() > 0.1


@pytest.mark.parametrize("threshold", [0.5, 1.0])
def test_the_exit_rule_picks_the_pass_the_reference_picks(threshold):
    """At 1 the last pass whatever the gates are; at 0.5 the first pass
    whose exit distribution has summed to it, token by token, through the
    prompt's forward and through the cache alike."""
    import jax.numpy as jnp
    cfg, ref, w, system = _system(threshold=threshold)
    rs = onp.random.RandomState(17)
    seq = rs.randint(3, 97, size=30)
    _, lam = ref.passes(w, cfg, jnp.asarray(seq, jnp.int32))
    want_pass = onp.asarray(ref.exit_pass(lam, threshold))
    if threshold >= 1.0:
        assert (want_pass == 2).all()
    else:
        # the gates of random weights spread the exits over the passes
        assert len(set(want_pass.tolist())) == 3
    want = _ref_logits(ref, w, cfg, seq)
    fns = _model_fns(system._net, cfg)
    got, cache = _served_logits(fns, seq, 11, 16, rs)
    assert onp.abs(got - want[10:]).max() < 2e-4
    counts = dict(zip(system._net.step_counts, onp.asarray(cache["counts"])[0]))
    assert counts["loop.exit_pass"] == want_pass[-1]
    from incubator_mxnet_tpu.models.looped_decoder import exit_pass
    assert (onp.asarray(exit_pass(lam, threshold)) == want_pass).all()


def test_the_gates_alone_decide_below_a_threshold_of_one():
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.looped_decoder import exit_pass
    lam = jnp.asarray([[0.6, 0.2, 0.1, 0.0, 0.5],
                       [0.9, 0.5, 0.1, 0.0, 0.0],
                       [0.3, 0.9, 0.1, 0.0, 0.0],
                       [0.0, 0.0, 0.0, 0.0, 1.0]], jnp.float32)
    # p sums: 0.6 | 0.2, 0.6 | 0.1, 0.19, 0.271 -> the rest | none | 0.5
    assert onp.asarray(exit_pass(lam, 0.5)).tolist() == [0, 1, 3, 3, 0]
    assert onp.asarray(exit_pass(lam, 1.0)).tolist() == [3] * 5
    assert onp.asarray(exit_pass(lam[:1], 0.5)).tolist() == [0] * 5


def test_stacked_leaves_through_the_kernel_in_interpret_mode(monkeypatch):
    """`decode_attention` with a layer's index over stacked leaves, the
    ragged kernel itself (interpreted) against the einsums on that layer's
    slice, bfloat16 leaves in row blocks of 48 as the served size has."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention as att
    S, N, G, T, W = 5, 3, 2, 96, 128
    rs = onp.random.RandomState(2)
    mk = lambda *shape: jnp.asarray(rs.randn(*shape).astype(onp.float32))
    q = mk(S, G, W)
    k, v = mk(S, N, G, T, W).astype(jnp.bfloat16), \
        mk(S, N, G, T, W).astype(jnp.bfloat16)
    lens = jnp.asarray([0, 1, 48, 49, 96], jnp.int32)
    assert att.ragged_row_block(T, k.dtype) == 48
    assert list(onp.asarray(att.decode_rows_read(lens, k))) == \
        [0, 48, 48, 96, 96]
    want = [onp.asarray(att.dense_decode_attention(
        q, k[:, n].astype(jnp.float32), v[:, n].astype(jnp.float32), lens,
        scale=0.09)) for n in range(N)]
    assert onp.abs(want[0] - want[1])[1:].max() > 0.1
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    for n in range(N):
        got = onp.asarray(att.decode_attention(q, k, v, lens, scale=0.09,
                                               layer=jnp.int32(n)))
        assert onp.abs(got - want[n])[1:].max() < 2e-5
        # and the einsums' own path over the stacked leaves
        plain = onp.asarray(att.dense_decode_attention(
            q, k.astype(jnp.float32), v.astype(jnp.float32), lens,
            jnp.asarray([n], jnp.int32), scale=0.09))
        assert onp.abs(plain - want[n])[1:].max() < 1e-6


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_row_write_kernel_is_the_indexed_update(dtype, monkeypatch):
    """`decode_rows_write`, the kernel itself (interpreted) against the
    scatter: one row a slot and head at the slot's own position (a tile's
    first and last rows, the leaf's last), in the named layer only."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention as att
    S, N, G, T, W = 5, 3, 2, 96, 128
    rs = onp.random.RandomState(5)
    mk = lambda *shape: jnp.asarray(rs.randn(*shape), dtype)
    k, v, kn, vn = mk(S, N, G, T, W), mk(S, N, G, T, W), mk(S, G, W), \
        mk(S, G, W)
    pos = jnp.asarray([0, 15, 16, 95, 47], jnp.int32)
    want = att._rows_scatter(k, v, kn, vn, jnp.asarray([1]), pos)
    assert onp.asarray(att.decode_rows_write(
        k, v, kn, vn, jnp.int32(1), pos)[0] == want[0]).all()   # the CPU's
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    got = jax.jit(att.decode_rows_write)(k, v, kn, vn, jnp.int32(1), pos)
    f32 = lambda a: onp.asarray(a.astype(jnp.float32))
    for new, old, ref, rows in zip(got, (k, v), want, (kn, vn)):
        assert (f32(new) == f32(ref)).all()
        assert (f32(new)[:, [0, 2]] == f32(old)[:, [0, 2]]).all()
        assert (f32(new)[onp.arange(S), 1, :, onp.asarray(pos)]
                == f32(rows)).all()
    # a leaf of half-lane rows takes the indexed update everywhere
    narrow = mk(S, N, G, T, 64)
    assert not att._rows_fit(narrow)
    out = att.decode_rows_write(narrow, narrow, kn[..., :64], vn[..., :64],
                                jnp.int32(0), pos)
    assert (f32(out[0])[onp.arange(S), 0, :, onp.asarray(pos)]
            == f32(kn[..., :64])).all()


def test_its_step_keeps_decode_rows_write_and_not_the_live_rows_kernel(
        monkeypatch):
    """The looped model keeps its own row write: the engine's decode
    executable of a `LoopedDecoder` whose stacked leaves the kernels tile
    (two heads of 128 lanes), lowered for a TPU, calls `decode_rows_write`
    and never `live_rows_write`, and no trace of it, for the chip or in
    interpret mode, counts `cache.rows_kernel_traces`."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.looped_decoder import LoopedDecoder
    from incubator_mxnet_tpu.serving import GenerationEngine
    S, L, bucket = 3, 32, 16
    net = LoopedDecoder(97, 256, 2, 2, 128, 96, loops=2)
    net.collect_params().setattr("grad_req", "null")
    net.initialize(ctx=mx.cpu(0))
    eng = GenerationEngine(net, bos=1, eos=2, ctx=mx.cpu(0), slots=S,
                           max_len=L, prompt_buckets=(bucket,), queue_cap=4)
    try:
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        params = {n: sds(v) for n, v in eng._params.items()}
        row = eng._prefill._jit.trace(params, ints(1, bucket),
                                      ints(1)).out_info
        cache = {"m": {k: jax.ShapeDtypeStruct((S,) + v.shape[1:], v.dtype)
                       for k, v in row["m"].items()},
                 "tok": ints(S), "pos": ints(S), "left": ints(S),
                 "out": ints(S, L)}
        step = eng._decode._jit.__wrapped__
        # a function of its own for each trace: JAX keeps a function's
        # trace whatever MXNET_PALLAS_INTERPRET says
        trace = lambda: jax.jit(lambda *a: step(*a)).trace(params, cache)
        traced = events.get("cache.rows_kernel_traces") or 0
        text = trace().lower(lowering_platforms=("tpu",)).as_text()
        calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
        assert any("decode_rows_write" in l for l in calls), len(calls)
        assert "live_rows_write" not in text
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        assert "tpu_custom_call" not in trace().lower().as_text()
        assert (events.get("cache.rows_kernel_traces") or 0) == traced
    finally:
        eng.close()


def test_the_engine_serves_it_and_updates_its_cache_in_place():
    """Through `GenerationEngine.submit`: three streams over three slots
    give the tokens the model's own contract gives, the step's cache update
    makes no copy, and the loop's counters read what was run."""
    cfg, ref, w, system = _system()
    system.warmup()
    before = {n: events.get(n) or 0 for n in
              ("gen.donation_copy", "loop.passes", "loop.tokens",
               "loop.exit_pass", "gen.tokens")}
    rs = onp.random.RandomState(23)
    prompts = [rs.randint(3, 97, size=n).astype(onp.int32)
               for n in (7, 16, 25, 12)]
    streams = [system.submit(p, 9) for p in prompts]
    served = [s.result(timeout=120) for s in streams]
    fns = _model_fns(system._net, cfg)
    for p, toks in zip(prompts, served):
        toks = list(toks)
        assert len(toks) == 9 or toks[-1] == cfg["eos_token_id"]
        seq = onp.concatenate([p, toks[:-1]]).astype(onp.int64)
        want = _ref_logits(ref, w, cfg, seq)[len(p) - 1:]
        best = want.max(-1)
        got = want[onp.arange(len(toks)), toks]
        assert (best - got).max() < 1e-3
    after = {n: events.get(n) or 0 for n in before}
    assert after["gen.donation_copy"] == before["gen.donation_copy"]
    tokens = after["loop.tokens"] - before["loop.tokens"]
    assert tokens >= sum(len(t) for t in served)
    assert after["loop.passes"] - before["loop.passes"] == 3 * tokens
    assert after["loop.exit_pass"] - before["loop.exit_pass"] == 2 * tokens
    assert (events.get("loop.traces") or 0) >= 1
