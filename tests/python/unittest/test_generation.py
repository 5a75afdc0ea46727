"""Generation serving tests (serving.generation — ISSUE 14 tentpole):
the greedy-parity oracle against contrib.text.decode on both model
families, variable-length RNN exactness, slot join/retire correctness
under churn, the KV donation no-copy proof, zero-recompile across
varying prompt lengths, mid-decode deadline shedding, KV-aware
registry admission naming the KV term, drain/close exactly-once
stream resolution, and the default TTFT SLO rules.  CPU-only, fast."""
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu import config as cfg
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.models import Seq2Seq
from incubator_mxnet_tpu.models.transformer import transformer_nmt_small
from incubator_mxnet_tpu.serving import (AdmissionDenied,
                                         DeadlineExceeded, EngineClosed,
                                         GenerationEngine,
                                         ModelRegistry, Shed)
from incubator_mxnet_tpu.contrib.text.decode import greedy_translate

pytestmark = pytest.mark.gen

V, BOS, EOS = 23, 1, 2


def _seq2seq(seed=0):
    mx.random.seed(seed)
    net = Seq2Seq(V, V, embed_dim=16, hidden=24, num_layers=2)
    net.initialize(force_reinit=True)
    net(nd.array(onp.ones((1, 4), onp.int32)),
        nd.array(onp.ones((1, 1), onp.int32)))      # concrete shapes
    return net


def _transformer(seed=0):
    mx.random.seed(seed)
    net = transformer_nmt_small(V, V, dropout=0.0)
    net.initialize(force_reinit=True)
    return net


def _engine(net, slots=3, max_len=16, buckets=(4, 8), **kw):
    return GenerationEngine(net, bos=BOS, eos=EOS, slots=slots,
                            max_len=max_len, prompt_buckets=buckets,
                            **kw)


def _ref_tokens(net, prompt, max_new):
    """greedy_translate oracle, trimmed at (and including) EOS."""
    out = greedy_translate(net, nd.array(prompt[None], dtype="int32"),
                           BOS, EOS, max_len=max_new)[0]
    toks = list(out)
    if EOS in toks:
        toks = toks[:toks.index(EOS) + 1]
    return [int(t) for t in toks]


def _unending(net, prompt, n):
    """`n` greedy tokens with an end-of-sequence id the model cannot emit."""
    out = greedy_translate(net, nd.array(prompt[None], dtype="int32"),
                           BOS, V + 7, max_len=n)[0]
    return [int(t) for t in out]


# -- variable-length RNN substrate -------------------------------------

def test_rnn_varlen_matches_truncated_run():
    """The prefill exactness contract: RNN_varlen over a right-padded
    batch must equal running each row at its exact length — outputs,
    final h AND c, both directions."""
    from incubator_mxnet_tpu.gluon import rnn as grnn
    onp.random.seed(3)
    x = nd.array(onp.random.randn(6, 2, 4).astype(onp.float32))
    vl = nd.array(onp.array([4, 6], onp.int32))
    for bi in (False, True):
        lstm = grnn.LSTM(8, num_layers=1 if bi else 2,
                         bidirectional=bi, layout="TNC")
        lstm.initialize()
        s0 = lstm.begin_state(batch_size=2)
        y_full, _ = lstm(x, s0)
        y, h, c = nd.RNN_varlen(
            x, lstm.parameters.data(), s0[0], s0[1], vl, state_size=8,
            num_layers=1 if bi else 2, bidirectional=bi, mode="lstm")
        y4, (h4, c4) = lstm(x[:4, 0:1], lstm.begin_state(batch_size=1))
        assert onp.allclose(y[:4, 0].asnumpy(), y4[:, 0].asnumpy(),
                            atol=1e-6)
        assert onp.allclose(h[:, 0].asnumpy(), h4[:, 0].asnumpy(),
                            atol=1e-6)
        assert onp.allclose(c[:, 0].asnumpy(), c4[:, 0].asnumpy(),
                            atol=1e-6)
        # full-length row is untouched; padded tail outputs are zeroed
        assert onp.allclose(y[:, 1].asnumpy(), y_full[:, 1].asnumpy(),
                            atol=1e-6)
        assert float(abs(y[4:, 0].asnumpy()).max()) == 0.0


# -- greedy-parity oracle ----------------------------------------------

@pytest.mark.parametrize("family", ["seq2seq", "transformer"])
def test_greedy_parity_oracle(family):
    """GenerationEngine greedy output is token-identical to the
    host-looped contrib.text.decode.greedy_translate — for prompts AT
    a bucket size and prompts padded up to one (the KV-cached path
    may differ by masked-padding noise only; tokens must match)."""
    net = _seq2seq() if family == "seq2seq" else _transformer()
    eng = _engine(net)
    try:
        eng.warmup()
        rs = onp.random.RandomState(7)
        for n in (3, 8):                # off-bucket and on-bucket
            prompt = rs.randint(3, V, (n,))
            ref = _ref_tokens(net, prompt, 10)
            got = [int(t) for t in
                   eng.submit(prompt, max_new_tokens=10)
                      .result(timeout=60)]
            assert got == ref[:len(got)], (n, got, ref)
            # a short result is legal only because EOS ended it
            if len(got) < 10:
                assert got[-1] == EOS
    finally:
        eng.close()


def test_slot_churn_isolation():
    """Join/retire masked updates under churn: more requests than
    slots, staggered lengths — every sequence must decode exactly as
    it would alone (slot reuse may not leak state across requests)."""
    net = _seq2seq(seed=1)
    eng = _engine(net, slots=2)
    try:
        eng.warmup()
        rs = onp.random.RandomState(11)
        # lengths repeat across requests on purpose: the greedy oracle
        # reuses its per-(src,prefix)-length executables, so 6 refs
        # cost ~2 requests' worth of compiles
        prompts = [rs.randint(3, V, (int(n),))
                   for n in (3, 8, 3, 8, 3, 8)]
        budgets = [4, 9, 6, 11, 3, 7]
        streams = [eng.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, budgets)]
        for p, m, s in zip(prompts, budgets, streams):
            got = [int(t) for t in s.result(timeout=60)]
            ref = _ref_tokens(net, p, m)
            assert got == ref[:len(got)], (list(p), got, ref)
        assert events.get("gen.retires") >= len(prompts)
    finally:
        eng.close()


def test_continuous_join_mid_generation():
    """A request submitted while generation is RUNNING joins at a
    step boundary without evicting the running sequence — both finish
    correctly, and the join happened while the first was live (the
    continuous-batching contract)."""
    net = _seq2seq(seed=2)
    eng = _engine(net, slots=2, max_len=16)
    try:
        eng.warmup()
        rs = onp.random.RandomState(5)
        p1, p2 = rs.randint(3, V, (5,)), rs.randint(3, V, (4,))
        s1 = eng.submit(p1, max_new_tokens=14)
        # wait until the first sequence has visibly started emitting
        first = next(iter(s1))
        s2 = eng.submit(p2, max_new_tokens=4)
        got2 = [int(t) for t in s2.result(timeout=60)]
        got1 = [first] + [int(t) for t in s1]
        assert got1 == _ref_tokens(net, p1, 14)[:len(got1)]
        assert got2 == _ref_tokens(net, p2, 4)[:len(got2)]
        # the overlap really happened: s2 joined before s1 retired
        st = eng.stats()
        assert st["counters"].get("gen.joins", 0) >= 2
    finally:
        eng.close()


# -- zero-recompile + donation -----------------------------------------

def test_zero_recompile_across_prompt_lengths():
    """After warmup, no mix of prompt lengths / batch membership may
    trace a new executable (serve.traces stays flat)."""
    net = _seq2seq(seed=3)
    eng = _engine(net, slots=2, buckets=(4, 8))
    try:
        w = eng.warmup()
        assert w["traces"] >= 4         # 2 prefill + join + decode
        t0 = events.get("serve.traces")
        rs = onp.random.RandomState(13)
        streams = [eng.submit(rs.randint(3, V, (int(n),)),
                              max_new_tokens=5)
                   for n in (1, 2, 3, 4, 5, 6, 7, 8, 3, 5)]
        for s in streams:
            s.result(timeout=60)
        assert events.get("serve.traces") - t0 == 0
    finally:
        eng.close()


def test_kv_donation_no_copy():
    """The no-copy proof: after a decode step, the PREVIOUS cache
    buffers are deleted (donated into the executable), not silently
    copied — and the runtime audit counter stayed at zero."""
    import jax
    net = _seq2seq(seed=4)
    eng = _engine(net, slots=2)
    try:
        eng.warmup()
        before = events.get("gen.donation_copy") or 0
        old_leaf = jax.tree_util.tree_leaves(eng._cache["m"])[0]
        s = eng.submit(onp.random.RandomState(0).randint(3, V, (4,)),
                       max_new_tokens=3)
        s.result(timeout=60)
        assert old_leaf.is_deleted(), \
            "decode step copied the KV cache instead of donating it"
        assert (events.get("gen.donation_copy") or 0) == before
    finally:
        eng.close()


# -- the NMT decode step: one row a slot, written where it lies ----------

def _one_hot_step(net, tok, pos, cache):
    """`TransformerNMT.decode_step` with the arithmetic it had before the
    indexed write, on the cache's own rows: every K/V leaf goes whole
    through `cache * (1 - oh) + new * oh`, `oh` the one-hot of each slot's
    position.  Eager NumPy-style code, independent of the step's write."""
    import math
    import jax
    import jax.numpy as jnp
    H, U = net._num_heads, net._units
    d = U // H
    B, G, L, W = cache["k0"].shape
    P = W // d
    M = cache["mem_k0"].shape[2]
    x = net.dec_ln(net.tgt_embed(tok.reshape((-1, 1))) * math.sqrt(U)
                   + net.pos_embed(pos.reshape((-1, 1))))
    at_pos = jnp.arange(L)[None, :] == pos._data[:, None]
    oh = at_pos.astype(jnp.float32)[:, None, :, None]       # (B, 1, L, 1)
    self_mask = jnp.where(jnp.arange(L)[None, :] > pos._data[:, None],
                          -1e9, 0.0)[:, None, None, :].astype(jnp.float32)
    mem_mask = jnp.where(
        jnp.arange(M)[None, :] >= cache["src_len"]._data[:, None],
        -1e9, 0.0)[:, None, None, :].astype(jnp.float32)
    own = jnp.eye(P)[:, :, None]

    def attend(q, k, v, mask):
        q = q._data.reshape(B, G, P, 1, d)
        qh = (q * own.astype(q.dtype)).reshape(B, G, P, W)
        sc = jnp.einsum("bgjw,bgtw->bgjt", qh, k) / math.sqrt(d) + mask
        ctx = jnp.einsum("bgjt,bgtw->bgjw", jax.nn.softmax(sc, axis=-1), v)
        ctx = jnp.einsum("bgjjd->bgjd", ctx.reshape(B, G, P, P, d))
        return nd.NDArray(ctx.reshape(B, 1, U))

    rows = lambda t: t._data.reshape(B, 1, G, W).transpose(0, 2, 1, 3)
    out = dict(cache)
    for i, layer in enumerate(net.decoder.layers._children.values()):
        sa, ca = layer.self_attn, layer.cross_attn
        kc = cache["k%d" % i]._data * (1.0 - oh) + rows(sa.key(x)) * oh
        vc = cache["v%d" % i]._data * (1.0 - oh) + rows(sa.value(x)) * oh
        out["k%d" % i], out["v%d" % i] = nd.NDArray(kc), nd.NDArray(vc)
        x = layer.ln1(x + sa.proj(attend(sa.query(x), kc, vc, self_mask)))
        x = layer.ln2(x + ca.proj(attend(
            ca.query(x), cache["mem_k%d" % i]._data,
            cache["mem_v%d" % i]._data, mem_mask)))
        x = layer.ln3(x + layer.ffn(x))
    return net.out_proj(x).reshape((0, -1)), out


_NMT_L = 12
_NMT_WRITES = {
    # positions of the four slots, step after step
    "spread": [[0, 5, _NMT_L - 1, 8], [1, 6, _NMT_L - 1, 9]],
    "first_row": [[0, 0, 0, 0]],
    "last_row": [[_NMT_L - 1] * 4],     # dead slots, held at the clamp
    # slot 2 leaves and a new request joins it between two steps
    "rejoined": [[4, 7, 9, 2], [5, 8, 0, 3], [6, 9, 1, 4]],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_NMT_WRITES))
def test_nmt_step_writes_one_row_like_the_one_hot_rewrite(case, dtype):
    """The indexed write against the one-hot rewrite it replaced, bit for
    bit in every cache leaf and every logit: slots at different
    positions, position 0, position L - 1, and a slot that is handed to
    a new request between two steps.  With bfloat16 weights the K/V
    leaves stay float32 and the memory leaves are bfloat16, as in the
    benchmark's cell."""
    net = _transformer(seed=21)
    if dtype != "float32":
        net(nd.array(onp.ones((1, 2), onp.int32)),
            nd.array(onp.ones((1, 2), onp.int32)))
        net.cast(dtype)
    S, L, M = 4, _NMT_L, 8
    rs = onp.random.RandomState(len(case))

    def prefilled(n):
        src = nd.array(rs.randint(3, V, (n, M)), dtype="int32")
        return net.init_cache(src, nd.array(rs.randint(2, M + 1, (n,)),
                                            dtype="int32"), L, M)

    cache = prefilled(S)
    assert cache["k0"].shape[0] == S and cache["k0"].shape[2] == L
    assert str(cache["k0"].dtype) == "float32"
    assert str(cache["mem_k0"].dtype) == dtype
    for name in [k for k in cache if k[0] in "kv"]:  # a running generation
        cache[name] = nd.array(
            rs.standard_normal(cache[name].shape).astype(onp.float32))
    ref = dict(cache)
    for n, pos in enumerate(_NMT_WRITES[case]):
        if case == "rejoined" and n == 1:
            row = prefilled(1)
            for name in cache:
                for c in (cache, ref):
                    a = onp.array(c[name].asnumpy())
                    a[2] = row[name].asnumpy()[0]
                    c[name] = nd.array(a, dtype=a.dtype)
        tok = nd.array(rs.randint(3, V, (S,)), dtype="int32")
        pos = nd.array(onp.array(pos), dtype="int32")
        logits, cache = net.decode_step(
            tok, pos, cache, nd.array(onp.ones(S, bool), dtype="bool"))
        want, ref = _one_hot_step(net, tok, pos, ref)
        assert set(cache) == set(ref)
        ref["counts"] = cache["counts"]     # the step's own report
        for name in sorted(ref):
            have, need = cache[name].asnumpy(), ref[name].asnumpy()
            assert have.dtype == need.dtype and have.shape == need.shape
            assert have.tobytes() == need.tobytes(), (name, n)
        assert logits.asnumpy().tobytes() == want.asnumpy().tobytes()


# -- liveness on the device, and the kernel that reads it ----------------

def _wide_transformer(seed):
    """One head to a 128-lane row (d 128, two groups): leaves the ragged
    kernel tiles, at a size the interpreter runs in seconds."""
    from incubator_mxnet_tpu.models.transformer import TransformerNMT
    mx.random.seed(seed)
    net = TransformerNMT(V, V, units=256, hidden_size=64, num_layers=2,
                         num_heads=2, max_length=32, dropout=0.0)
    net.initialize(force_reinit=True)
    return net


def _greedy_margin(net, prompt, n, L, M):
    """Smallest gap between a step's two best logits over `n` greedy
    steps of `prompt`, by the model's own (dense) step, one slot."""
    cache = net.init_cache(nd.array(prompt[None], dtype="int32"),
                           nd.array([len(prompt)], dtype="int32"), L, M)
    tok, gap = BOS, float("inf")
    for pos in range(n):
        logits, cache = net.decode_step(
            nd.array([tok], dtype="int32"), nd.array([pos], dtype="int32"),
            cache, nd.array([True], dtype="bool"))
        top = onp.sort(logits.asnumpy()[0].astype(onp.float64))
        gap = min(gap, top[-1] - top[-2])
        tok = int(logits.asnumpy()[0].argmax())
    return gap


_CHURN_L, _CHURN_M, _CHURN_S = 16, 8, 3


def _churn(net):
    """A run the test steps by hand (no decode thread): six requests over
    three slots, one cancelled in the queue, one that comes late and is
    shed by its deadline after two tokens, the others ending by eos,
    budget or max_len, and the freed slots taken again.  Returns each stream's tokens and how it
    ended, and for every step the slots whose token the host pushed
    beside the device's `left` as the step found it (a step dispatched
    early runs before the host has read the one before it: the last entry
    is the state after the run, nothing pushed)."""
    rs = onp.random.RandomState(5)
    prompts = [rs.randint(3, V, (n,)).astype(onp.int32)
               for n in (3, 8, 5, 4, 6, 7)]
    budgets = [_CHURN_L, 3, _CHURN_L, 5, 4, 6]
    eng = _engine(net, slots=_CHURN_S, max_len=_CHURN_L, buckets=(4, 8))
    eng._ensure_loop = lambda: None         # the test is the loop
    found, pushed, decode, emit = [], [], eng._decode, eng._emit

    def watched(params, cache):
        found.append(onp.asarray(cache["left"]))
        return decode(params, cache)

    def emitting(seats, *rest):
        pushed.append([i for i, sl in seats if eng._slots[i] is sl])
        return emit(seats, *rest)

    try:
        eng.warmup()
        eng._decode, eng._emit = watched, emitting
        streams = {i: eng.submit(prompts[i], max_new_tokens=budgets[i])
                   for i in (0, 1, 3, 4, 5)}
        assert streams[4].future.cancel()           # still queued
        shed = False
        while len(streams) < 6 or not all(st.done()
                                          for st in streams.values()):
            assert eng._tick() == "ran"
            if len(found) == 4:     # the queue is empty by now: the slot
                # this one leaves stays idle, and live on the device
                streams[2] = eng.submit(prompts[2], deadline=60.0,
                                        max_new_tokens=budgets[2])
            late = [sl for sl in eng._slots if sl is not None
                    and sl.req.stream is streams.get(2)]
            if late and late[0].emitted == 2 and not shed:
                late[0].req.deadline = time.monotonic() - 1.0
                shed = True
            assert len(found) < 80
        assert len(found) == len(pushed)    # every step was read
        ticks = list(zip(pushed, found)) \
            + [(eng._live(), onp.asarray(eng._cache["left"]))]
        ends = []
        streams = [streams[i] for i in range(6)]
        for st in streams:
            exc = None if st.future.cancelled() else st.future.exception()
            ends.append("cancelled" if st.future.cancelled()
                        else type(exc).__name__ if exc else "ok")
        return prompts, budgets, [st.tokens() for st in streams], ends, ticks
    finally:
        eng.close(timeout=5.0)


def test_kernel_serves_the_same_tokens_and_the_device_knows_who_is_live(
        monkeypatch):
    """(a) The ragged kernel (interpret mode) serves, token for token,
    what the masked einsums serve, over a run with eos, budget, max_len,
    deadline and cancel retirements and re-admissions into the same
    slots; no step's two best logits lie within 1e-4.  (b) At every step
    the device's `left` covers the host's live slots, and a slot whose
    stream ended by eos or budget reads 0.  (c) `gen.attn_rows_read` is
    the live slots' lengths, self + source, rounded up to the kernel's
    row block, and stays under the rows held."""
    from incubator_mxnet_tpu.ops import attention as att
    monkeypatch.setattr(att, "_ROW_BLOCK", 8)       # two row blocks of 8
    L, M, S = _CHURN_L, _CHURN_M, _CHURN_S
    net = _wide_transformer(seed=34)
    assert att._ragged_fits(onp.zeros((S, 2, L, 128), onp.float32))
    rows0, steps0 = (events.get(n) or 0
                     for n in ("gen.attn_rows_read", "gen.steps"))
    prompts, budgets, want, ends, ticks = _churn(net)
    rows = (events.get("gen.attn_rows_read") or 0) - rows0
    steps = (events.get("gen.steps") or 0) - steps0
    # the run holds every way a stream ends
    assert ends == ["ok", "ok", "DeadlineExceeded", "ok", "cancelled", "ok"]
    assert len(want[2]) == 3 and want[4] == []
    assert len(want[1]) == budgets[1] and EOS not in want[1]    # budget
    assert any(t[-1] == EOS and len(t) < b                      # eos
               for t, b in zip(want, budgets) if t)
    assert len(want[0]) == L and EOS not in want[0]     # the last row
    for p, t in zip(prompts, want):
        if t:
            assert _greedy_margin(net, p, len(t), L, M) > 1e-4
    # (b) every slot whose token the host took was live on the device
    # in that step; a stream shed by its deadline stays live on the
    # device, eos and budget leave 0 behind
    assert len({tuple(live) for live, _ in ticks}) > 3          # churn
    idle_left = set()
    for live, left in ticks:
        assert (left[live] > 0).all(), (live, left)
        idle_left.update(int(left[i]) for i in range(S) if i not in live)
    assert 0 in idle_left and max(idle_left) > 0
    assert ticks[-1][0] == []
    # (c) a stream of n tokens read rows 1..n of itself and its source
    up = lambda n, b: -(-n // b) * b
    expect = sum(up(t, 8) + up(len(p), 8)
                 for p, toks in zip(prompts, want)
                 for t in range(1, len(toks) + 1))
    assert rows == expect
    assert 0 < rows <= steps * S * (L + M)
    # (a) the kernel itself
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    _, _, got, ends_k, ticks_k = _churn(net)
    assert got == want and ends_k == ends
    assert [live for live, _ in ticks_k] == [live for live, _ in ticks]
    assert (events.get("gen.attn_rows_read") or 0) - rows0 == 2 * expect


# the slots live at each step, and the slot a new request joins before it
_ROWS_LIVE = [([0, 1, 2], None), ([0, 1, 2], None), ([0, 1, 2], None),
              ([0, 2], None), ([0, 2, 3], 3), ([0, 2, 3], None),
              ([2, 3], None), ([1, 2, 3], 1), ([1, 2, 3], None)]


def test_nmt_step_through_the_row_kernel_is_the_indexed_update(monkeypatch):
    """`decode_step` of a `TransformerNMT` whose K/V leaves the kernel
    `live_rows_write` takes (float32, 128-lane rows), with the kernel
    (interpret mode) and with the indexed update, step after step while
    slots retire (left dead at their last position, or past the end) and
    new requests join them (a fresh prefill written over the slot): the
    same greedy tokens, and logits and every row of the live slots' caches
    equal bit for bit; a dead slot's rows are left as they were by the
    kernel.  Attention takes the einsums in both runs, so the row writes
    are all that differ.  Two kernel traces a step: one a layer."""
    from incubator_mxnet_tpu.ops import attention as att
    monkeypatch.setattr(att, "ragged_decode_attention",
                        att.dense_decode_attention)
    net = _wide_transformer(seed=42)
    S, L, M = 4, 16, 8
    rs = onp.random.RandomState(9)

    def prefilled(n):
        src = nd.array(rs.randint(3, V, (n, M)), dtype="int32")
        return net.init_cache(src, nd.array(rs.randint(2, M + 1, (n,)),
                                            dtype="int32"), L, M)

    ref = prefilled(S)
    assert att._live_fits(ref["k0"]._data)
    kern = dict(ref)
    pos = onp.array([0, 0, 0, 5])
    tok = onp.full(S, BOS)
    traced = events.get("cache.rows_kernel_traces") or 0
    for n, (slots, joins) in enumerate(_ROWS_LIVE):
        if joins is not None:
            row = prefilled(1)
            for c in (ref, kern):
                for name in c:
                    a = onp.array(c[name].asnumpy())
                    a[joins] = row[name].asnumpy()[0]
                    c[name] = nd.array(a, dtype=a.dtype)
            pos[joins], tok[joins] = 0, BOS
        if n == 6:
            pos[0] = L                      # retired past the last row
        live = onp.isin(onp.arange(S), slots)
        args = (nd.array(tok, dtype="int32"), nd.array(pos, dtype="int32"))
        flag = nd.array(live, dtype="bool")
        monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
        want, ref = net.decode_step(*args, ref, flag)
        monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
        before = {k: kern[k].asnumpy() for k in kern}
        got, kern = net.decode_step(*args, kern, flag)
        assert got.asnumpy()[live].tobytes() == want.asnumpy()[live].tobytes()
        for name in ref:
            have, need = kern[name].asnumpy(), ref[name].asnumpy()
            assert have[live].tobytes() == need[live].tobytes(), (name, n)
            if name[0] in "kv":
                assert have[~live].tobytes() == before[name][~live].tobytes()
        tok = onp.where(live, want.asnumpy().argmax(-1), tok)
        pos = onp.where(live, pos + 1, pos)
    assert (events.get("cache.rows_kernel_traces") or 0) - traced \
        == 2 * len(_ROWS_LIVE)


# -- join: indexed in-place write of one slot ---------------------------

def _join_operands(eng, seed, cast_leaf=None):
    """A random non-zero cache of the engine's decode signature, a
    random one-slot row of its prefill signature and their NumPy
    copies.  `cast_leaf` names a model leaf whose CACHE side is made
    float16 while the row stays float32 (the join casts the row)."""
    import jax
    rs = onp.random.RandomState(seed)

    def rand(a):
        if onp.issubdtype(a.dtype, onp.integer):
            return rs.randint(1, 99, a.shape).astype(a.dtype)
        return rs.standard_normal(a.shape).astype(a.dtype)

    eng._init_cache_arrays()
    cache = jax.tree_util.tree_map(lambda a: rand(onp.asarray(a)),
                                   eng._cache)
    eng._cache = None
    # a prefilled row: the model's leaves and where its stream starts
    row = {"m": {k: rand(v[:1]) for k, v in cache["m"].items()},
           "tok": rand(cache["tok"][:1]), "pos": rand(cache["pos"][:1])}
    if cast_leaf is not None:
        assert cache["m"][cast_leaf].dtype == onp.float32
        cache["m"][cast_leaf] = cache["m"][cast_leaf].astype(onp.float16)
    dev = eng._ctx.jax_device
    return cache, row, jax.device_put(cache, dev), jax.device_put(row, dev)


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("family,cast_leaf", [("seq2seq", "h"),
                                              ("transformer", "k0")])
def test_join_writes_one_slot_bit_exact(family, cast_leaf, where):
    """The join against a plain NumPy reference, bit for bit on every
    leaf: the slot's row holds the prefilled row (cast to the cache
    leaf's dtype), the row's start token and position, the request's
    budget of tokens, a row of eos, and every other slot keeps its
    bytes."""
    import jax
    net = _seq2seq(seed=11) if family == "seq2seq" else _transformer(11)
    S = 5
    eng = _engine(net, slots=S)
    slot = {"first": 0, "middle": 2, "last": S - 1}[where]
    try:
        ref, row, cache_d, row_d = _join_operands(eng, 17 + slot,
                                                  cast_leaf)
        got = eng._join(cache_d, row_d, jax.device_put(
            onp.array([slot, 7], onp.int32), eng._ctx.jax_device))
        for k, r in row["m"].items():
            ref["m"][k][slot] = r[0].astype(ref["m"][k].dtype)
        ref["tok"][slot] = row["tok"][0]
        ref["pos"][slot] = row["pos"][0]
        ref["left"][slot] = 7
        ref["out"][slot] = EOS
        tree = jax.tree_util
        assert tree.tree_structure(got) == tree.tree_structure(ref)
        for (path, want), have in zip(tree.tree_leaves_with_path(ref),
                                      tree.tree_leaves(got)):
            have = onp.asarray(have)
            assert have.dtype == want.dtype and have.shape == want.shape
            assert have.tobytes() == want.tobytes(), (path, slot)
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["seq2seq", "transformer"])
def test_join_lowers_to_indexed_update(family):
    """The property that makes an admission cost one slot's bytes,
    guarded where no chip is: the lowered join holds one
    dynamic_update_slice per cache leaf, and no select (the old
    masked update) over a leaf of the cache's (S, ...) shape."""
    import re
    import jax
    net = _seq2seq(seed=12) if family == "seq2seq" else _transformer(12)
    S = 5                           # no other dimension of the cache is 5
    eng = _engine(net, slots=S)
    try:
        _, _, cache_d, row_d = _join_operands(eng, 3)
        text = eng._join.lower(
            cache_d, row_d, jax.device_put(
                onp.array([1, 7], onp.int32),
                eng._ctx.jax_device)).as_text()
        n_leaves = len(jax.tree_util.tree_leaves(cache_d))
        assert n_leaves == len(cache_d["m"]) + 4
        assert text.count("stablehlo.dynamic_update_slice") == n_leaves
        # a select line ends ": <predicate type>, <result type>"
        whole = [ln for ln in text.splitlines()
                 if "stablehlo.select" in ln
                 and re.search(r"tensor<%dx" % S, ln.rsplit(":", 1)[-1])]
        assert not whole, whole
    finally:
        eng.close()


def test_warmup_join_donates_the_cache():
    """The warm-up's join takes the donated cache: the leaf held from
    before it is deleted after, and the probe's counter did not move."""
    import jax
    net = _transformer(seed=13)
    eng = _engine(net, slots=2)
    try:
        eng._init_cache_arrays()
        old_leaf = jax.tree_util.tree_leaves(eng._cache["m"])[0]
        before = events.get("gen.donation_copy") or 0
        eng.warmup()
        assert old_leaf.is_deleted(), \
            "join copied the KV cache instead of donating it"
        assert (events.get("gen.donation_copy") or 0) == before
    finally:
        eng.close()


def test_warmup_join_copy_is_counted_and_named():
    """A join that leaves the old cache alive (a backend that ignores
    the donation) counts gen.donation_copy and warns with the join's
    label."""
    import jax
    net = _seq2seq(seed=14)
    eng = _engine(net, slots=2, cost_label="serve.gen:probe")
    try:
        donating = eng._join

        def copying(cache, row, slot):
            return donating(jax.tree_util.tree_map(lambda a: a + 0,
                                                   cache), row, slot)

        eng._join = copying
        before = events.get("gen.donation_copy") or 0
        with pytest.warns(UserWarning, match="serve.gen:probe:join"):
            eng.warmup()
        assert (events.get("gen.donation_copy") or 0) == before + 1
    finally:
        eng.close()


def test_prefill_bucket_warmup_counts():
    """warmup() compiles exactly the closed executable set: one
    prefill per prompt bucket + join + decode."""
    net = _seq2seq(seed=5)
    t0 = events.get("serve.traces")
    eng = _engine(net, slots=2, buckets=(4, 8))
    try:
        eng.warmup()
        assert events.get("serve.traces") - t0 == 4
    finally:
        eng.close()


# -- deadlines / shedding ----------------------------------------------

def test_mid_decode_deadline_frees_slot():
    """A deadline expiring MID-generation resolves the stream with
    DeadlineExceeded and frees the slot — the engine keeps serving
    (the next request completes on the freed slot)."""
    from incubator_mxnet_tpu import fault
    net = _seq2seq(seed=6)
    eng = _engine(net, slots=1, max_len=16)
    try:
        eng.warmup()
        rs = onp.random.RandomState(17)
        shed0 = events.get("gen.shed") or 0
        # stall every decode step 20ms (serve.decode_slow site): 14
        # tokens need >=280ms, the 80ms deadline expires mid-decode
        # deterministically — but AFTER the first token lands
        fault.install("serve.decode_slow", steps=list(range(5000)),
                      seconds=0.02)
        s = eng.submit(rs.randint(3, V, (8,)), max_new_tokens=14,
                       deadline=0.080)
        with pytest.raises(DeadlineExceeded):
            s.result(timeout=60)
        fault.clear()
        assert len(s.tokens()) >= 1     # it WAS mid-decode
        assert (events.get("gen.shed") or 0) > shed0
        # the slot is free again: a fresh request completes
        s2 = eng.submit(rs.randint(3, V, (4,)), max_new_tokens=3)
        assert len(s2.result(timeout=60)) >= 1
        assert eng.stats()["slots_live"] == 0
    finally:
        eng.close()


def test_born_expired_and_infeasible_shed():
    net = _seq2seq(seed=7)
    eng = _engine(net, slots=1)
    try:
        eng.warmup()
        with pytest.raises(DeadlineExceeded):
            eng.submit(onp.array([3, 4, 5]), deadline=-1.0)
        # lane-quota shed: with the decode loop parked, the low lane's
        # cap (0.25 x 8 = 2) sheds the 3rd submit deterministically.
        # Parked means never started: a loop that finds the stop flag
        # flushes the queue as it leaves, and a submit that then found
        # room again raced it (the test failed under the six-worker
        # run for that reason, the engine's shed path was sound)
        small = GenerationEngine(
            net, bos=BOS, eos=EOS, slots=1, max_len=16,
            prompt_buckets=(4,), queue_cap=8,
            lanes=("hi", "lo"), lane_quotas=(1.0, 0.25))
        try:
            small._ensure_loop = lambda: None
            with pytest.raises(Shed):
                for _ in range(4):
                    small.submit(onp.array([3, 4]), lane="lo",
                                 max_new_tokens=2)
        finally:
            small.close()
    finally:
        eng.close()


# -- lifecycle ----------------------------------------------------------

def test_drain_close_resolve_every_stream_exactly_once():
    """Queued + running + mid-flight streams are ALL resolved exactly
    once across drain()/close(); no future is left pending and no
    queue accounting leaks."""
    net = _seq2seq(seed=8)
    eng = _engine(net, slots=2, max_len=16)
    try:
        eng.warmup()
        rs = onp.random.RandomState(23)
        streams = [eng.submit(rs.randint(3, V, (4,)),
                              max_new_tokens=12)
                   for _ in range(8)]
        # close with work still queued/running: every stream resolves
        eng.close(timeout=60)
        done = 0
        for s in streams:
            assert s.future.done()
            try:
                s.result(timeout=0)
                done += 1
            except (EngineClosed, DeadlineExceeded):
                pass
        assert done >= 1                # the ones that finished
        assert eng._q.unfinished_tasks == 0
        assert eng.stats()["slots_live"] == 0
        with pytest.raises(EngineClosed):
            eng.submit(onp.array([3, 4]))
    finally:
        eng.close()


def test_stream_iterates_incrementally():
    net = _seq2seq(seed=9)
    eng = _engine(net, slots=1)
    try:
        eng.warmup()
        s = eng.submit(onp.random.RandomState(1).randint(3, V, (5,)),
                       max_new_tokens=6)
        got = [int(t) for t in s]
        assert got == [int(t) for t in s.result(timeout=1)]
        assert len(got) >= 1
        assert (events.get("gen.ttft_us.n") or 0) >= 1
    finally:
        eng.close()


def test_drain_mode_admits_only_at_batch_boundary():
    """continuous=False (the A/B baseline): while ANY slot is live no
    new request joins; after the batch drains the queued one runs."""
    net = _seq2seq(seed=10)
    eng = _engine(net, slots=2, continuous=False)
    try:
        eng.warmup()
        rs = onp.random.RandomState(29)
        s1 = eng.submit(rs.randint(3, V, (5,)), max_new_tokens=12)
        first = next(iter(s1))          # batch 1 is running
        assert isinstance(first, int)
        joins_before = events.get("gen.joins")
        s2 = eng.submit(rs.randint(3, V, (4,)), max_new_tokens=2)
        # while s1 is live, s2 must NOT have joined
        time.sleep(0.05)
        if not s1.done():
            assert events.get("gen.joins") == joins_before
        s1.result(timeout=60)
        assert len(s2.result(timeout=60)) >= 1
    finally:
        eng.close()


# -- registry / admission ----------------------------------------------

def test_registry_kv_admission_names_kv_term():
    """Generation admission accounts slots × kv_bytes; the refusal
    names the KV term (message + flight-recorder event)."""
    from incubator_mxnet_tpu.telemetry import flightrec as bb
    net = _seq2seq(seed=11)
    reg = ModelRegistry(devices=[mx.cpu()], hbm_budget=150 * 1024)
    try:
        with pytest.raises(AdmissionDenied) as ei:
            reg.register_generator("g_big", net, BOS, EOS,
                                   slots=4096, max_len=32,
                                   prompt_buckets=(8,))
        msg = str(ei.value)
        assert "KV cache" in msg and "slots x" in msg
        rec = reg.register_generator("g", net, BOS, EOS, slots=2,
                                     max_len=16, prompt_buckets=(4, 8))
        assert rec["detail"]["kv_bytes"] > 0
        assert rec["detail"]["kv_bytes"] == \
            2 * rec["detail"]["kv_bytes_per_slot"]
        reg.warmup("g")
        s = reg.generate("g", onp.array([3, 4, 5]), max_new_tokens=4)
        assert len(s.result(timeout=60)) >= 1
        ledger = reg.stats()["ledger"][0]
        assert ledger["committed"] >= rec["footprint_bytes"]
        reg.unregister("g")
        assert reg.stats()["ledger"][0]["committed"] == 0
    finally:
        reg.close()


def test_engine_projection_matches_live_cache():
    """project_generation_footprint's per-slot KV bytes equal the
    live engine's model-cache share (the projection admission trusts
    is the thing actually allocated)."""
    from incubator_mxnet_tpu.serving import project_generation_footprint
    net = _seq2seq(seed=12)
    total, detail = project_generation_footprint(
        net, slots=2, max_len=16, buckets=(4, 8))
    eng = _engine(net, slots=2, max_len=16, buckets=(4, 8))
    try:
        kv = eng.kv_cache_bytes()
        # engine cache adds the tok/pos/left/out bookkeeping leaves on
        # top of the model KV rows the projection counts
        assert kv["per_slot"] >= detail["kv_bytes_per_slot"]
        assert kv["per_slot"] - detail["kv_bytes_per_slot"] <= \
            4 * (3 + 16)                # tok+pos+left+out int32 rows
    finally:
        eng.close()


# -- SLO ----------------------------------------------------------------

def test_default_generation_slo_rules():
    from incubator_mxnet_tpu.telemetry import slo
    net = _seq2seq(seed=13)
    eng = _engine(net, slots=1, lanes=("high", "low"),
                  lane_quotas=(1.0, 0.5))
    try:
        eng.warmup()
        s = eng.submit(onp.array([3, 4, 5]), max_new_tokens=2,
                       deadline=5.0, lane="high")
        s.result(timeout=60)
        names = eng.install_slo_rules()
        try:
            assert "gen-shed-high" in names
            assert "gen-ttft-p99-high" in names   # observed deadline
            assert "gen-ttft-p99-low" not in names  # never deadlined
            rules = slo.rules()
            r = rules["gen-ttft-p99-high"]
            assert r.bound == pytest.approx(5.0 * 1e6)
        finally:
            for n in names:
                slo.unregister_rule(n)
    finally:
        eng.close()


# -- telemetry / occupancy ---------------------------------------------

def test_slot_occupancy_gauge_and_spans():
    from incubator_mxnet_tpu.telemetry import flightrec as bb
    net = _seq2seq(seed=14)
    eng = _engine(net, slots=2)
    try:
        eng.warmup()
        s = eng.submit(onp.array([3, 4, 5, 6]), max_new_tokens=3)
        s.result(timeout=60)
        time.sleep(0.02)
        # the occupancy gauge sampled live slots; join/retire landed
        # in the flight-recorder ring
        assert (events.get("gen.slots_live.n") or 0) >= 1
        kinds = [(e.get("kind"), e.get("name"))
                 for e in bb.ring_snapshot()]
        assert ("gen", "join") in kinds
        assert ("gen", "retire") in kinds
    finally:
        eng.close()


@pytest.mark.slow
def test_check_decode_gate_runs():
    """The CI gate executes end to end (SKIP rc 0 on this host is a
    legal verdict; nonzero = the contract broke)."""
    import subprocess
    import sys as _sys
    import os as _os
    root = _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))))
    res = subprocess.run(
        [_sys.executable,
         _os.path.join(root, "tools", "check_decode.py"),
         "--trials", "1", "--duration", "1.5"],
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr


def test_close_gives_the_device_memory_back_without_the_collector():
    """A closed engine holds no cache and no parameters, and dropping its
    last name frees it by reference count: no closure of its executables
    holds the engine (the benchmark's driver closes the system while the
    collector is frozen, and the reference that runs next needs the
    room)."""
    import gc
    import weakref
    net = _seq2seq(seed=21)
    eng = _engine(net, slots=2)
    eng.warmup()
    eng.submit(onp.array([3, 4, 5]), max_new_tokens=3).result(timeout=60)
    cache_leaf = weakref.ref(eng._probe_leaf())
    gc.collect()
    gc.disable()
    try:
        assert eng.close()
        assert eng._cache is None and eng._params is None
        assert cache_leaf() is None
        ref = weakref.ref(eng)
        del eng
        assert ref() is None
    finally:
        gc.enable()


# -- the step dispatched early ------------------------------------------

@pytest.mark.parametrize("ends", ["budget", "eos"])
def test_a_full_engine_dispatches_the_next_step_early_and_streams_the_same(
        ends):
    """With every slot taken and no stream on its last token the engine
    dispatches step n + 1 before it reads step n.  The streams are what one
    step at a time gives: by budget no boundary is ever late; by eos the
    stream that ends is found a step late and its one token more is read by
    nobody, not by the request that takes its slot either."""
    net = _transformer(4)
    rs = onp.random.RandomState(12)
    prompts = [rs.randint(3, V, n).astype(onp.int32) for n in (3, 7, 5, 4)]
    budgets = [19, 26, 11, 23]
    want = [_unending(net, p, n) for p, n in zip(prompts, budgets)]
    eos = V + 7
    if ends == "eos":
        eos = want[0][14]       # only the first stream has it: it ends
        assert eos not in want[0][:14] + want[1] + want[2] + want[3]
        want = [w[:w.index(eos) + 1] if eos in w else w for w in want]
    eng = GenerationEngine(net, bos=BOS, eos=eos, slots=2, max_len=32,
                           prompt_buckets=(4, 8))
    early, late = [], []
    settled, emit = eng._settled, eng._emit

    def counted(flight):
        early.append(settled(flight))
        return early[-1]

    def watched(seats, *a):
        late.extend(i for i, slot in seats if eng._slots[i] is not slot)
        return emit(seats, *a)

    eng._settled, eng._emit = counted, watched
    try:
        eng.warmup()
        streams = [eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, budgets)]
        got = [[int(t) for t in s.result(120)] for s in streams]
    finally:
        eng.close()
    assert got == want
    assert sum(early) >= (8 if ends == "budget" else 2), early  # it did
    assert not all(early)               # and not across a budget's end
    if ends == "budget":
        assert not late                 # no seat was ever read too late
    else:
        assert late                     # the ended stream's one step more
