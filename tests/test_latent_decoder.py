"""models.latent_decoder behind serving.GenerationEngine, at a small size on
the CPU that keeps every distinction of the published model: 4 heads with
rope 8 < nope 16 and values of 12, q rank 24 != kv rank 16, a leading dense
layer before two sparse ones, 4 groups of 4 experts with 2 groups kept and
top-3, 8 experts held and two shared.  The oracle is the benchmark's plain
reference (benchmark/reference/deepseek_v2.py: float32 jax.numpy, the
EXPANDED form only, no cache) on the same seeded weights, so the absorbed
decode step is checked against other algebra.  Also: the YaRN frequencies
and the softmax scale of the published block against hand-computed numbers,
`group_limited_route` against a brute-force routing with ties, the grouped
expert kernel with its hidden width in three blocks, and the expert layer's
shares with the shared experts counted once."""
import json
import math
import os
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.monitor import events

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(REPO, "tests", "benchmark", "data", "deepseek_v2_root",
                    "benchmark", "configs", "deepseek_v2_tiny.json")

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
    deepseek_v2_tiny."""
    import jax
    import jax.numpy as jnp
    with open(TINY) as f:
        cfg = json.load(f)
    ref = _bench("reference", "deepseek_v2")
    builder = _bench("configs", "deepseek_v2")
    w = _bench(None, "weights").make(ref.spec(cfg), 11, jnp.float32,
                                     jax.devices("cpu")[0])
    system = builder.build(cfg, w, mx.cpu(0))
    system.warmup()
    yield cfg, ref, w, system
    system.close()


_FNS = {}


def _ref_logits(ref, w, cfg, seq):
    """Reference logits of `seq`, padded to the tiny max_len (one shape,
    one compile; the model is causal)."""
    import jax
    import jax.numpy as jnp
    if "ref" not in _FNS:
        _FNS["ref"] = jax.jit(lambda w_, t: ref.forward(w_, cfg, t))
    full = onp.full(cfg["serving"]["max_len"], cfg["eos_token_id"], onp.int32)
    full[:len(seq)] = seq
    return onp.asarray(_FNS["ref"](w, jnp.asarray(full)))[:len(seq)]


def _model_fns(net, cfg):
    """The model's `init_cache` and `decode_step` as the engine traces them
    (pure functions of the parameters), jitted."""
    import jax
    from incubator_mxnet_tpu.parallel.functional import extract_params
    from incubator_mxnet_tpu.serving.generation import _pure_method
    if "model" not in _FNS:
        L = cfg["serving"]["max_len"]
        pure = _pure_method(net, "init_cache")
        params = extract_params(net)
        init = jax.jit(lambda pv, tok, n: pure(pv, tok, n, L, None))
        step = jax.jit(_pure_method(net, "decode_step"))
        _FNS["model"] = (lambda tok, n: init(params, tok, n),
                         lambda *a: step(params, *a))
    return _FNS["model"]


def _interpret_kernels(monkeypatch):
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    assert attention._interpret()


def _kernels_off(monkeypatch):
    """A plain CPU trace, whatever `tests/benchmark`'s rehearsals left in
    `os.environ` (`benchmark/run.py` sets MXNET_PALLAS_INTERPRET for good,
    ROADMAP S0 (e))."""
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(config, "_OVERRIDES", {
        k: v for k, v in config._OVERRIDES.items()
        if k != "MXNET_PALLAS_INTERPRET"})
    assert not attention._interpret()


# ---- rotary positions and the softmax scale of the published block ---------

PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"}


def test_yarn_frequencies_and_scale_of_the_published_block():
    """By hand, for rope 64, theta 10000, factor 40 over 4096 positions:
    d(n) = 64 ln(4096 / (2 pi n)) / (2 ln 10000) is 10.47 at 32 turns and
    22.51 at one, so frequencies 0-10 stay, 23-31 are divided by 40, and
    11-22 are (i - 10) / 13 of the way from the one to the other.
    m(0.707) = 0.0707 ln 40 + 1 = 1.26080; cos and sin carry m / m = 1 and
    the softmax scale 192^-1/2 m^2 = 0.114721."""
    from incubator_mxnet_tpu.models import latent_decoder as ld
    f = ld.yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    plain = 10000.0 ** (-onp.arange(32) / 32.0)
    assert f.shape == (32,)
    assert onp.allclose(f[:11], plain[:11], rtol=1e-6)
    assert onp.allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    for i in (11, 16, 22):
        t = (i - 10) / 13.0
        assert abs(f[i] / (plain[i] * (1 - t) + plain[i] / 40 * t) - 1) < 1e-6
    assert abs(f[16] - 10000.0 ** -0.5 * (7 / 13 + 6 / 13 / 40)) < 1e-9
    assert abs(ld.yarn_mscale(40, 0.707) - 1.2608038) < 1e-6
    attn = ld.LatentAttention(1, 64, 2, 24, 16, 128, 64, 128,
                              rope_theta=10000, rope_scaling=PUBLISHED_YARN)
    assert abs(attn.scale - 0.114721) < 1e-6
    assert attn._rot_scale == 1.0
    # the reference computes its own, by the same published rule
    ref = _bench("reference", "deepseek_v2")
    fr, rot, sigma = ref.yarn({"dr": 64, "dn": 128, "theta": 10000.0,
                               "yarn": PUBLISHED_YARN})
    assert onp.allclose(onp.asarray(fr), f, rtol=1e-6)
    assert rot == 1.0 and abs(sigma - attn.scale) < 1e-9
    # no scaling: plain rotary positions and the plain scale
    bare = ld.LatentAttention(1, 64, 2, 24, 16, 128, 64, 128)
    assert onp.allclose(bare.inv_freq, plain, rtol=1e-6)
    assert abs(bare.scale - 192 ** -0.5) < 1e-12


def test_rotary_pairs_turn_together():
    """Pair (2i, 2i + 1) of a key turns by pos * f_i: the program lays the
    result evens first, the reference in place; products of a query and a
    key agree, and depend on the positions' difference only."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models import latent_decoder as ld
    ref = _bench("reference", "deepseek_v2")
    rs = onp.random.RandomState(0)
    q = jnp.asarray(rs.randn(5, 3, 8).astype(onp.float32))
    k = jnp.asarray(rs.randn(5, 1, 8).astype(onp.float32))
    f = ld.yarn_inv_freq(8, 10000.0, 40, 32, 32, 1)
    pos = jnp.asarray([0, 3, 7, 20, 41])
    ours = lambda x, p: ld._rotary_pairs(x, p, f, 1.0)
    theirs = lambda x, p: ref.rotary(x, p, jnp.asarray(f), 1.0)
    got = jnp.einsum("qhd,khd->hqk", ours(q, pos), ours(k, pos))
    want = jnp.einsum("qhd,khd->hqk", theirs(q, pos), theirs(k, pos))
    assert onp.abs(onp.asarray(got - want)).max() < 1e-5
    moved = jnp.einsum("qhd,khd->hqk", ours(q, pos + 9), ours(k, pos + 9))
    assert onp.abs(onp.asarray(onp.diagonal(moved - got, axis1=1, axis2=2))
                   ).max() < 1e-4
    a = onp.asarray(theirs(k, pos))[2, 0]
    c, s = math.cos(7 * f[1]), math.sin(7 * f[1])
    assert abs(a[2] - (k[2, 0, 2] * c - k[2, 0, 3] * s)) < 1e-6
    assert abs(a[3] - (k[2, 0, 2] * s + k[2, 0, 3] * c)) < 1e-6


# ---- routing ---------------------------------------------------------------

def _route_by_hand(logits, k, n_group, topk_group, scale):
    """Brute force, one token at a time: sort the groups by their best
    expert (ties to the lower group), then the kept experts by their value
    (ties to the lower expert)."""
    logits = onp.asarray(logits, onp.float64)
    gates, experts = [], []
    for row in logits:
        r = onp.exp(row - row.max())
        r = (r / r.sum()).astype(onp.float32)
        size = len(r) // n_group
        best = [max(r[g * size:(g + 1) * size]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))
        kept = [e for g in sorted(groups[:topk_group])
                for e in range(g * size, (g + 1) * size)]
        top = sorted(kept, key=lambda e: (-r[e], e))[:k]
        experts.append(top)
        gates.append([scale * r[e] for e in top])
    return onp.asarray(gates, onp.float32), onp.asarray(experts)


@pytest.mark.parametrize("case", ["random", "ties", "published_shape"])
def test_group_limited_route_is_the_brute_force_routing(case):
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    rs = onp.random.RandomState(5)
    if case == "published_shape":
        T, E, k, G, KG, scale = 64, 160, 6, 8, 3, 16.0
        logits = rs.randn(T, E).astype(onp.float32)
    else:
        T, E, k, G, KG, scale = 40, 16, 3, 4, 2, 16.0
        logits = rs.randn(T, E).astype(onp.float32)
        if case == "ties":
            # values from a few levels only: groups tie for a place, and
            # experts tie inside a group and across the groups kept
            logits = rs.randint(0, 3, (T, E)).astype(onp.float32)
            logits[0] = 1.0                             # everything ties
    gate, expert = moe.group_limited_route(jnp.asarray(logits), k, G, KG,
                                           scale)
    want_gate, want_expert = _route_by_hand(logits, k, G, KG, scale)
    assert (onp.asarray(expert) == want_expert).all()
    assert onp.abs(onp.asarray(gate) - want_gate).max() < 1e-6
    if case == "ties":
        assert list(onp.asarray(expert)[0]) == [0, 1, 2]
    # not renormalised: the gates are the softmax's own values times 16
    assert (onp.asarray(gate).sum(-1) < scale).all()
    assert expert.dtype == jnp.int32 and gate.dtype == jnp.float32
    # the reference routes alike, by its own algebra
    ref = _bench("reference", "deepseek_v2")
    import jax
    rg, re_ = ref.route(jax.nn.softmax(jnp.asarray(logits), -1),
                        {"NG": G, "TOPG": KG, "TOPE": k, "gate": scale})
    assert (onp.asarray(re_) == want_expert).all()
    assert onp.abs(onp.asarray(rg) - want_gate).max() < 1e-6


def test_grouped_kernel_with_the_hidden_width_in_three_blocks(monkeypatch):
    """The regime of an expert of 1536 x 5120 (`_grouped_hidden_block` gives
    512 there, three blocks a tile) at a small size: F = 384 in blocks of
    128, the down projection summed over them, in interpret mode against the
    loop over tiles; routed by groups, gates not renormalised."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    assert moe._grouped_hidden_block(1536, 5120, 2) == 512
    T, D, F, E, k, held, tile = 40, 128, 384, 16, 3, 8, 8
    rs = onp.random.RandomState(9)
    rand = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32))
    x = rand(T, D)
    wg, wu = rand(2, held, F, D) / 8, rand(2, held, F, D) / 8
    wd = rand(2, held, D, F) / 12
    gate, expert = moe.group_limited_route(rand(T, E), k, 4, 2, 16.0)
    run = lambda: onp.asarray(moe.held_experts(
        x, gate, expert, wg, wu, wd, 0, tile=tile, layer=jnp.int32(1)))
    want = run()                                        # the loop over tiles
    assert onp.abs(want).max() > 0.05
    monkeypatch.setattr(moe, "_GROUPED_WEIGHT_BYTES", 2 * 3 * 128 * D * 4)
    assert moe._grouped_hidden_block(F, D, 4) == 128
    traced = events.get("moe.grouped_traces") or 0
    _interpret_kernels(monkeypatch)
    moe._held_many.clear_cache()        # the counter counts traces
    got = run()
    assert (events.get("moe.grouped_traces") or 0) == traced + 1
    assert onp.isfinite(got).all()
    assert onp.abs(got - want).max() < 2e-6 * max(1.0, onp.abs(want).max())


# ---- the two forms of the attention ----------------------------------------

def test_absorbed_is_expanded_on_the_same_rows():
    """One layer, the same rows: `prompt` (expanded: every head's keys and
    values formed from the latent rows) and `step` (absorbed: the keys' map
    on the query's side, the context summed in latent space) position by
    position over the rows the prompt cached."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.latent_decoder import LatentAttention
    T, D, H = 24, 64, 4
    attn = LatentAttention(2, D, H, 24, 16, 16, 8, 12, rope_scaling=dict(
        PUBLISHED_YARN, original_max_position_embeddings=32))
    attn.initialize(ctx=mx.cpu(0))
    rs = onp.random.RandomState(1)
    p = {n: jnp.asarray(rs.randn(*a.shape).astype(onp.float32))
         / math.sqrt(a.shape[-1]) for n, a in attn.stacked().items()}
    p.update({n: 1.0 + 0.1 * p[n] for n in ("ln", "gq", "gkv")})
    p1 = {n: a[1] for n, a in p.items()}
    h = jnp.asarray(rs.randn(T, D).astype(onp.float32))
    want, c, kr = attn.prompt(p1, h, 8, 8)
    assert c.shape == (T, 16) and kr.shape == (T, 8)
    # three slots at ragged positions of the same sequence; layer 1 of 2
    L, pos = 32, jnp.asarray([23, 5, 0])
    pad = lambda a: jnp.pad(a, [(0, L - T), (0, 0)])
    noise = jnp.asarray(rs.randn(3, 2, L, 16).astype(onp.float32))
    cache = {"ckv": noise.at[:, 1].set(pad(c)[None]),
             "kr": jnp.asarray(rs.randn(3, 2, L, 8).astype(onp.float32))
             .at[:, 1].set(pad(kr)[None])}
    got, new = attn.step(p1, h[pos], pos, 1, cache)
    assert onp.abs(onp.asarray(got - want[pos])).max() < 1e-5
    # the step rewrote row pos of layer 1 with what it held, nothing else
    assert onp.abs(onp.asarray(new["ckv"] - cache["ckv"])).max() < 1e-5
    assert (onp.asarray(new["ckv"][:, 0]) == onp.asarray(noise[:, 0])).all()


def _attend_by_hand(qa, qr, ckv, kr, layer, lengths, scale):
    """A loop over slots in NumPy float64 over rows [0, length) of `layer`."""
    f64 = lambda a: onp.asarray(a, onp.float64)
    qa, qr, ckv, kr = f64(qa), f64(qr), f64(ckv), f64(kr)
    out = []
    for s, n in enumerate(lengths):
        c = ckv[s, layer, :n]
        sc = (qa[s] @ c.T + qr[s] @ kr[s, layer, :n].T) * scale
        p = onp.exp(sc - sc.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ c)
    return onp.stack(out)


@pytest.mark.parametrize("interpret", [False, True])
def test_latent_decode_attention_is_attention_over_the_live_rows(
        monkeypatch, interpret):
    """The einsums (leaves the kernel does not tile: rank 8, 16 rows), in
    interpret mode too, against a loop over slots in NumPy float64: rows
    past a slot's length and the other layer's rows, whatever they hold,
    do not enter; every row of the slot is read."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    S, H, T, R, dr = 4, 3, 16, 8, 4
    rs = onp.random.RandomState(3)
    f32 = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32))
    qa, qr, ckv, kr = f32(S, H, R), f32(S, H, dr), f32(S, 2, T, R), \
        f32(S, 2, T, dr)
    lengths = onp.array([16, 1, 7, 12])
    if interpret:
        _interpret_kernels(monkeypatch)
    traced = events.get("mla.kernel_traces") or 0
    got = onp.asarray(A.latent_decode_attention(
        qa, qr, ckv, kr, jnp.int32(1), jnp.asarray(lengths), 0.3))
    want = _attend_by_hand(qa, qr, ckv, kr, 1, lengths, 0.3)
    assert onp.abs(got - want).max() < 1e-5
    assert list(onp.asarray(A.latent_rows_read(jnp.asarray(lengths), ckv))) \
        == [T] * S
    assert (events.get("mla.kernel_traces") or 0) == traced


@pytest.mark.parametrize("dtype,lengths", [
    ("bfloat16", [1, 1536, 512, 513, 1400, 37]),
    ("bfloat16", [1300, 40, 1024, 1025, 1536, 1536]),
    ("float32", [1, 1536, 256, 257, 1400, 37]),
    ("float32", [1300, 40, 768, 769, 1536, 1536]),
])
def test_latent_kernel_reads_each_slots_rows_up_to_its_length(
        monkeypatch, dtype, lengths):
    """The kernel in interpret mode over whole leaves (S, 3 layers, 1536,
    128 | 64) at layer 1, against the loop in float64: lengths 1, T, a
    block border, a border + 1, a slot far shorter than its neighbours (its
    steps past its rows hold the next slot's first block), the last slot
    short and full.  Rows past a slot's length hold huge values, blocks
    past its last needed one and both other layers NaN: none may enter.
    `latent_rows_read` is the length rounded up to the row block, and
    `mla.kernel_traces` counts the one layer body traced."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    S, H, T, R, dr, layers = len(lengths), 8, 1536, 128, 64, 3
    dt = jnp.dtype(dtype)
    tb = A.latent_row_block(T, dt)
    assert tb == (512 if dtype == "bfloat16" else 256)
    rs = onp.random.RandomState(len(dtype) + lengths[0])
    rnd = lambda *s: onp.asarray(jnp.asarray(
        rs.randn(*s).astype(onp.float32), dt).astype(jnp.float32))
    qa, qr = rnd(S, H, R), rnd(S, H, dr)
    ckv = onp.full((S, layers, T, R), onp.nan, onp.float32)
    kr = onp.full((S, layers, T, dr), onp.nan, onp.float32)
    for s, n in enumerate(lengths):
        end = -(-n // tb) * tb
        ckv[s, 1, :end], kr[s, 1, :end] = 1e30, -1e30
        ckv[s, 1, :n], kr[s, 1, :n] = rnd(n, R), rnd(n, dr)
    want = _attend_by_hand(qa, qr, ckv, kr, 1, lengths, 0.11)
    _interpret_kernels(monkeypatch)
    traced = events.get("mla.kernel_traces") or 0
    run = jax.jit(lambda *a: A.latent_decode_attention(*a, 0.11))
    got = onp.asarray(run(*[jnp.asarray(a, dt) for a in (qa, qr, ckv, kr)],
                          jnp.int32(1), jnp.asarray(lengths, jnp.int32)))
    assert (events.get("mla.kernel_traces") or 0) == traced + 1
    assert got.shape == (S, H, R) and got.dtype == onp.float32
    assert onp.isfinite(got).all()
    # bfloat16: the probabilities are rounded to 8 bits for the context
    tol = 1e-2 if dtype == "bfloat16" else 2e-5
    assert onp.abs(got - want).max() < tol * onp.abs(want).max()
    assert list(onp.asarray(A.latent_rows_read(
        jnp.asarray(lengths), jnp.zeros((S, layers, T, R), dt)))) \
        == [-(-n // tb) * tb for n in lengths]


def test_blocked_causal_attention_takes_values_narrower_than_keys():
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    T, H = 24, 3
    rs = onp.random.RandomState(4)
    q, k, v = rs.randn(T, H, 12), rs.randn(T, H, 12), rs.randn(T, H, 5)
    got = onp.asarray(A.blocked_causal_attention(
        *[jnp.asarray(a.astype(onp.float32)) for a in (q, k, v)], 0.4,
        block=8, chunk=4))
    assert got.shape == (T, H, 5)
    for h in range(H):
        sc = onp.where(onp.tril(onp.ones((T, T), bool)),
                       q[:, h] @ k[:, h].T * 0.4, -onp.inf)
        p = onp.exp(sc - sc.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ v[:, h]
        assert onp.abs(got[:, h] - want).max() < 1e-5
    with pytest.raises(ValueError):
        A.blocked_causal_attention(jnp.zeros((20, H, 12)),
                                   jnp.zeros((20, H, 12)),
                                   jnp.zeros((20, H, 5)), 1.0, block=8)


def _prefill_operands(T, H, dtype, seed):
    """(qn, qr, kn, kr, v) of `latent_prefill_attention` at keys of 128 + 64
    and values of 128, values of `dtype` held as float32."""
    import jax.numpy as jnp
    rs = onp.random.RandomState(seed)
    rnd = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32),
                                 jnp.dtype(dtype))
    return (rnd(T, H * 128), rnd(H, T, 64), rnd(T, H * 128), rnd(T, 64),
            rnd(T, H * 128))


def _causal_by_hand(qn, qr, kn, kr, v, scale):
    """A loop over heads in NumPy float64: (T, H * dv)."""
    import jax.numpy as jnp
    f64 = lambda a: onp.asarray(a.astype(jnp.float32), onp.float64)
    qn, qr, kn, kr, v = (f64(a) for a in (qn, qr, kn, kr, v))
    H, T, _ = qr.shape
    qn, kn, v = (a.reshape(T, H, -1) for a in (qn, kn, v))
    out = []
    for h in range(H):
        sc = onp.where(onp.tril(onp.ones((T, T), bool)),
                       (qn[:, h] @ kn[:, h].T + qr[h] @ kr.T) * scale,
                       -onp.inf)
        p = onp.exp(sc - sc.max(-1, keepdims=True))
        out.append((p / p.sum(-1, keepdims=True)) @ v[:, h])
    return onp.stack(out, 1).reshape(T, -1)


@pytest.mark.parametrize("T", [1024, 1536])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_latent_prefill_kernel_is_causal_attention(monkeypatch, dtype, T):
    """The kernel in interpret mode at keys of 192 (128 + the shared 64)
    and values of 128, eight heads (two groups of four), a prompt of two and
    of three blocks of 512: equal to `blocked_causal_attention` over the
    joined keys (the plain CPU trace of the same call) and to a loop over
    heads in NumPy float64.  `mla.prefill_kernel_traces` counts the one
    body traced with the kernel, and none without it."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    assert A.latent_prefill_block(T) == 512
    args = _prefill_operands(T, 8, dtype, T + len(dtype))
    run = lambda: jax.jit(
        lambda *a: A.latent_prefill_attention(*a, 0.11))(*args)
    _kernels_off(monkeypatch)
    traced = events.get("mla.prefill_kernel_traces") or 0
    blocked = run()
    assert (events.get("mla.prefill_kernel_traces") or 0) == traced
    _interpret_kernels(monkeypatch)
    got = run()
    assert (events.get("mla.prefill_kernel_traces") or 0) == traced + 1
    assert got.shape == (T, 8 * 128) and got.dtype == jnp.dtype(dtype)
    assert blocked.shape == got.shape and blocked.dtype == got.dtype
    got, blocked = (onp.asarray(a.astype(jnp.float32))
                    for a in (got, blocked))
    want = _causal_by_hand(*args, 0.11)
    assert onp.isfinite(got).all()
    # bfloat16: the probabilities and the result are rounded to 8 bits
    tol = 1e-2 if dtype == "bfloat16" else 2e-5
    assert onp.abs(got - want).max() < tol * onp.abs(want).max()
    assert onp.abs(got - blocked).max() < tol * onp.abs(want).max()


@pytest.mark.parametrize("T,block", [(600, 200), (1000, 500)])
def test_a_prompt_that_does_not_tile_takes_the_xla_form(monkeypatch, T,
                                                        block):
    """No divisor of T up to 512 is whole lane tiles: the call is
    `blocked_causal_attention`, in interpret mode too, and counts no
    kernel."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import attention as A
    assert A.latent_prefill_block(T) == 0
    args = _prefill_operands(T, 2, "float32", T)
    _interpret_kernels(monkeypatch)
    monkeypatch.setattr(A, "_prefill_pallas", lambda *a, **k: 1 / 0)
    traced = events.get("mla.prefill_kernel_traces") or 0
    got = onp.asarray(A.latent_prefill_attention(*args, 0.2, block=block,
                                                 chunk=100))
    assert (events.get("mla.prefill_kernel_traces") or 0) == traced
    want = _causal_by_hand(*args, 0.2)
    assert onp.abs(got - want).max() < 2e-5 * onp.abs(want).max()


# ---- the model against the plain reference --------------------------------

# the whole forward pass, in query blocks of 8
@pytest.mark.parametrize("T", [32, 16, 8])
def test_forward_matches_the_reference(tiny, T):
    cfg, ref, w, system = tiny
    tok = onp.random.RandomState(T).randint(3, 128, (2, T)).astype(onp.int32)
    out = system._net(nd.array(tok, dtype="int32")).asnumpy()
    for r in range(2):
        assert onp.abs(out[r] - _ref_logits(ref, w, cfg, tok[r])).max() < 2e-4


def test_prefill_then_decode_logits_match_the_reference(tiny):
    """The model's own contract, logits compared: init_cache over padded
    buckets, then decode_step (absorbed) fed the reference's sequences, three
    slots at ragged lengths of which one is dead.  The cache leaves have
    kv_rank and rope values a row and no head axis."""
    import jax.numpy as jnp
    cfg, ref, w, system = tiny
    L = cfg["serving"]["max_len"]
    rs = onp.random.RandomState(100)
    lens = [5, 16, 11]
    seqs = [rs.randint(3, cfg["vocab_size"], n + 9).astype(onp.int32)
            for n in lens]
    want = [_ref_logits(ref, w, cfg, s) for s in seqs]
    init, step = _model_fns(system._net, cfg)
    prompts = rs.randint(3, 128, (3, 16)).astype(onp.int32)     # the padding
    for r, n in enumerate(lens):
        prompts[r, :n] = seqs[r][:n]
    cache = dict(init(jnp.asarray(prompts), jnp.asarray(lens, jnp.int32)))
    tok, pos = cache.pop("start_tok"), cache.pop("start_pos")
    assert cache["ckv"].shape == (3, 3, L, 16)          # 3 layers, no heads
    assert cache["kr"].shape == (3, 3, L, 8)
    assert sorted(cache) == ["ckv", "counts", "kr"]
    assert [int(t) for t in tok] == [s[n - 1] for s, n in zip(seqs, lens)]
    assert [int(p) for p in pos] == [n - 1 for n in lens]
    live = jnp.asarray([True, True, False])
    for j in range(10):
        at = [n - 1 + j for n in lens]
        logits, cache = step(
            jnp.asarray([s[a] for s, a in zip(seqs, at)], jnp.int32),
            jnp.asarray(at, jnp.int32), cache, live)
        for r in range(3):                              # the dead slot too
            assert onp.abs(onp.asarray(logits)[r] - want[r][at[r]]).max() \
                < 2e-4, (j, r)
    counts = onp.asarray(cache["counts"])
    # what the last step did: 3 layers over rows 0..pos, all 48 read
    assert list(counts[:, 0]) == [3 * (a + 1) for a in at]
    assert list(counts[:, 1]) == [3 * L] * 3
    row = 3 * (16 + 8) * 4
    assert list(counts[:, 2]) == [row * (a + 2) // 1024 for a in at]
    share = system._net.step_weight_bytes() // 1024 // 2
    assert list(counts[:, 3] - counts[:, 2]) == [share, share, 0]
    assert list(counts[:, 4]) == [2 * 3] * 3


@pytest.mark.parametrize("kernel", [True, False])
@pytest.mark.parametrize("path", ["forward", "prefill_then_decode"])
def test_the_prefill_kernel_in_the_model(monkeypatch, tiny, path, kernel):
    """A new trace of `forward` and of `init_cache` with the prefill's
    attention as its kernel (interpret mode: the tiny widths are no whole
    lane tiles, which only the interpreter takes) and as the XLA form (a
    plain CPU trace): the logits are the reference's either way, and an
    executable counts two layer bodies traced with the kernel (the dense
    layer's and the scan's), or none."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel.functional import extract_params
    from incubator_mxnet_tpu.serving.generation import _pure_method
    cfg, ref, w, system = tiny
    net, L = system._net, cfg["serving"]["max_len"]
    (_interpret_kernels if kernel else _kernels_off)(monkeypatch)
    params = extract_params(net)
    rs = onp.random.RandomState(40 + kernel)
    traced = events.get("mla.prefill_kernel_traces") or 0
    if path == "forward":
        tok = rs.randint(3, 128, (1, 24)).astype(onp.int32)
        out = jax.jit(_pure_method(net, "forward"))(params, jnp.asarray(tok))
        assert onp.abs(onp.asarray(out)[0]
                       - _ref_logits(ref, w, cfg, tok[0])).max() < 2e-4
    else:
        n = 13
        seq = rs.randint(3, cfg["vocab_size"], n + 4).astype(onp.int32)
        want = _ref_logits(ref, w, cfg, seq)
        prompt = rs.randint(3, 128, (1, 16)).astype(onp.int32)
        prompt[0, :n] = seq[:n]
        pure = _pure_method(net, "init_cache")
        cache = dict(jax.jit(lambda pv, t, m: pure(pv, t, m, L, None))(
            params, jnp.asarray(prompt), jnp.asarray([n], jnp.int32)))
        assert int(cache.pop("start_tok")[0]) == seq[n - 1]
        assert int(cache.pop("start_pos")[0]) == n - 1
        _, step = _model_fns(net, cfg)
        for j in range(4):
            logits, cache = step(jnp.asarray(seq[n - 1 + j:n + j]),
                                 jnp.asarray([n - 1 + j], jnp.int32), cache,
                                 jnp.asarray([True]))
            assert onp.abs(onp.asarray(logits)[0] - want[n - 1 + j]).max() \
                < 2e-4, j
    assert (events.get("mla.prefill_kernel_traces") or 0) - traced \
        == (2 if kernel else 0)


@pytest.mark.parametrize("n_prompt,n_new", [(3, 14), (8, 9), (16, 16),
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


def test_an_engine_run_with_refills(tiny):
    """Seven requests through three slots: every slot is taken again, and
    every stream is what the reference puts first, whatever rows the slot's
    earlier stream left past its position."""
    cfg, ref, w, system = tiny
    rs = onp.random.RandomState(77)
    prompts = [rs.randint(3, 128, n).astype(onp.int32)
               for n in (30, 12, 19, 7, 25, 16, 3)]
    joins = events.get("gen.joins") or 0
    streams = [system.engine.submit(p, max_new_tokens=8 + i)
               for i, p in enumerate(prompts)]
    for p, s in zip(prompts, streams):
        toks = onp.asarray(s.result(120))
        logits = _ref_logits(ref, w, cfg, onp.concatenate([p, toks[:-1]]))
        at = len(p) - 1 + onp.arange(len(toks))
        assert (logits[at].max(-1) - logits[at, toks]).max() <= 1e-4
    assert events.get("gen.joins") - joins == 7


def test_counters_and_prefill_rows(tiny):
    """The step's counts reach the counters once a step, summed over live
    slots; a gen.prefill row carries the prompt's tokens; a decode executable
    is traced in the absorbed form, a prefill is not."""
    from incubator_mxnet_tpu.telemetry import spans
    cfg, ref, w, system = tiny
    names = system._net.step_counts
    assert names == ("gen.attn_context", "gen.attn_rows_read",
                     "gen.cache_kib", "gen.step_kib", "moe.picks",
                     "moe.picks_held", "moe.expert_max")
    # one decode executable was traced at warm-up (and whatever the tests
    # above jitted): a prefill adds none
    traced = events.get("mla.absorbed_traces")
    assert traced >= 1
    before = {n: events.get(n) or 0 for n in names}
    t0 = spans._now()
    prompt = onp.arange(3, 3 + 11, dtype=onp.int32)
    toks = system.engine.submit(prompt, max_new_tokens=5).result(120)
    assert events.get("mla.absorbed_traces") == traced
    d = {n: events.get(n) - before[n] for n in names}
    n, L = len(toks), cfg["serving"]["max_len"]
    ctx = [11 + j for j in range(n)]
    row = 3 * (16 + 8) * 4                  # float32 rows of 3 layers
    weights = system._net.step_weight_bytes() // 1024
    assert d["gen.attn_context"] == 3 * sum(ctx)
    assert d["gen.attn_rows_read"] == 3 * L * n
    assert d["gen.cache_kib"] == sum(row * (c + 1) // 1024 for c in ctx)
    assert d["gen.step_kib"] == d["gen.cache_kib"] + n * weights
    assert d["moe.picks"] == 2 * 3 * n
    assert 0 <= d["moe.expert_max"] <= d["moe.picks_held"] <= d["moe.picks"]
    rows = [r for r in spans.phase_log(since=t0, prefix="gen.prefill")]
    assert [r[5] for r in rows] == [11]


# ---- the expert layer: shares and the shared experts -----------------------

@pytest.mark.parametrize("tile,kernel", [(256, False), (8, False), (8, True)])
def test_the_shares_add_up_with_the_shared_experts_counted_once(
        tiny, tile, kernel, monkeypatch):
    """Two shares of 8 (experts 0-7 and 8-15), each with the un-gated shared
    experts that every chip computes alike: their sum less one shared term
    is the reference's uncut layer, through the forms of `held_experts`:
    every expert over every token, the loop over tiles, and the grouped
    kernel in interpret mode.  The reference's own shares add up alike."""
    import functools
    import jax.numpy as jnp
    from incubator_mxnet_tpu.models.sparse_decoder import HeldExperts
    from incubator_mxnet_tpu.parallel import moe
    cfg, ref, w, _ = tiny
    if kernel:
        _interpret_kernels(monkeypatch)
    z = dict(ref.sizes(cfg), EH=16, E0=0)
    rs = onp.random.RandomState(2)
    D, F, FS, E = 64, 32, 64, 16
    rand = lambda *s: jnp.asarray(rs.randn(*s).astype(onp.float32))
    p = {"moe.ln": 1.0 + 0.1 * rand(1, D), "moe.router": rand(1, E, D),
         "moe.wg": rand(1, E, F, D) / 8, "moe.wu": rand(1, E, F, D) / 8,
         "moe.wd": rand(1, E, D, F) / 6,
         "moe.shared_wg": rand(1, FS, D) / 8,
         "moe.shared_wu": rand(1, FS, D) / 8,
         "moe.shared_wd": rand(1, D, FS) / 6}
    h = rand(40, D)
    whole = onp.asarray(ref.experts(h, p, 0, z, None) - h)
    x = ref.norm(h, p["moe.ln"][0], z["eps"])
    shared = onp.asarray(ref.swiglu(
        x, p["moe.shared_wg"][0], p["moe.shared_wu"][0],
        p["moe.shared_wd"][0], None))
    assert onp.abs(shared).max() > 0.05
    route = functools.partial(moe.group_limited_route, n_group=4,
                              topk_group=2, scale=16.0)
    total, ref_total = 0.0, 0.0
    for lo in (0, 8):
        blk = HeldExperts(1, D, F, E, 3, first_held=lo, held=8, tile=tile,
                          shared_hidden=FS, route=route, shared_gate=False)
        assert "sgate" not in blk._names
        share = {"ln": p["moe.ln"][0], "router": p["moe.router"][0],
                 "sg": p["moe.shared_wg"][0], "su": p["moe.shared_wu"][0],
                 "sd": p["moe.shared_wd"][0]}
        share.update({"w" + c: p["moe.w" + c][0, lo:lo + 8] for c in "gud"})
        out = onp.asarray(blk.apply(share, h)[0] - h)
        assert onp.abs(out - shared).max() > 0.05       # its experts' terms
        total = total + out
        cut = dict(p, **{"moe.w" + c: p["moe.w" + c][:, lo:lo + 8]
                         for c in "gud"})
        ref_total = ref_total + onp.asarray(
            ref.experts(h, cut, 0, dict(z, EH=8, E0=lo), None) - h)
    assert onp.abs(total - shared - whole).max() < 2e-4
    assert onp.abs(ref_total - shared - whole).max() < 2e-4
