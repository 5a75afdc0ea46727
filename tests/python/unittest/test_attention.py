"""Flash attention: Pallas kernel numerics vs naive XLA path.

Ref test model: tests/python/unittest/test_contrib_operator.py's
interleaved_matmul attention checks (fused vs decomposed numerics).
MXNET_PALLAS_INTERPRET=1 runs the *actual* Pallas kernel in interpreter
mode so the CPU corpus exercises the kernel, not just the fallback.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd as ag
from incubator_mxnet_tpu.ops import attention as att


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_K", "128")


def _rand_qkv(BH=4, T=256, d=64, dtype=np.float32):
    rs = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rs.randn(BH, T, d).astype(dtype) * 0.5)
    return mk(), mk(), mk()


def _tol():
    return 2e-5


def test_flash_fwd_matches_naive(pallas_interpret):
    q, k, v = _rand_qkv()
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = att._flash_attention(q, k, v, float(scale), False)
    ref = att.naive_attention(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=_tol(), atol=_tol())


def test_flash_fwd_causal(pallas_interpret):
    q, k, v = _rand_qkv(BH=2, T=256, d=32)
    scale = 0.125
    out = att._flash_attention(q, k, v, scale, True)
    ref = att.naive_attention(q, k, v, scale, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=_tol(), atol=_tol())


def test_flash_grad_matches_naive(pallas_interpret):
    q, k, v = _rand_qkv(BH=2, T=128, d=32)
    scale = 1.0 / np.sqrt(32)

    def f_flash(q, k, v):
        return jnp.sum(att._flash_attention(q, k, v, float(scale), False)
                       * jnp.cos(jnp.arange(32.0)))

    def f_ref(q, k, v):
        return jnp.sum(att.naive_attention(q, k, v, scale)
                       * jnp.cos(jnp.arange(32.0)))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=max(1e-4, _tol()),
                                   atol=max(1e-4, _tol()))


def test_flash_bwd_chunked_matches_direct(pallas_interpret, monkeypatch):
    """Force the lax.scan k-block backward and compare to the one-shot
    (both on the XLA fallback path)."""
    monkeypatch.setenv("MXNET_FLASH_BWD_PALLAS", "0")
    q, k, v = _rand_qkv(BH=2, T=128, d=32)
    scale = 1.0 / np.sqrt(32)

    def loss(q, k, v):
        return jnp.sum(att._flash_attention(q, k, v, float(scale), True)
                       * jnp.sin(jnp.arange(32.0)))

    g_direct = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("MXNET_FLASH_BWD_BYTES", "100000")   # forces nk > 1
    g_chunked = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_direct, g_chunked):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=max(1e-5, _tol()),
                                   atol=max(1e-5, _tol()))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_pallas_matches_naive(pallas_interpret, monkeypatch,
                                        causal):
    """The Pallas dq/dkv kernel pair (multi-block grid: T=256 with
    128-blocks) vs autodiff through the naive path."""
    monkeypatch.setenv("MXNET_FLASH_BWD_PALLAS", "2")
    q, k, v = _rand_qkv(BH=2, T=256, d=32)
    scale = 1.0 / np.sqrt(32)
    w = jnp.cos(jnp.arange(32.0))

    def f_flash(q, k, v):
        return jnp.sum(att._flash_attention(
            q, k, v, float(scale), causal) * w)

    def f_ref(q, k, v):
        return jnp.sum(att.naive_attention(
            q, k, v, scale, causal=causal) * w)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=max(1e-4, _tol()),
                                   atol=max(1e-4, _tol()))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_pallas_matches_xla_path(pallas_interpret, monkeypatch,
                                           causal):
    """Pallas backward vs the fused-XLA from-lse backward — same
    residuals, same math, different schedule."""
    monkeypatch.setenv("MXNET_FLASH_BWD_PALLAS", "2")
    q, k, v = _rand_qkv(BH=2, T=256, d=32)
    scale = 1.0 / np.sqrt(32)

    def loss(q, k, v):
        return jnp.sum(att._flash_attention(
            q, k, v, float(scale), causal) ** 2)

    g_pallas = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("MXNET_FLASH_BWD_PALLAS", "0")
    g_xla = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=max(1e-5, _tol()),
                                   atol=max(1e-5, _tol()))


def test_contrib_op_ndarray_surface():
    """Registered op through nd + autograd (fallback path on CPU)."""
    B, T, C, H = 2, 16, 32, 4
    rs = np.random.RandomState(3)
    q = nd.array(rs.randn(B, T, C).astype(np.float32))
    k = nd.array(rs.randn(B, T, C).astype(np.float32))
    v = nd.array(rs.randn(B, T, C).astype(np.float32))
    for a in (q, k, v):
        a.attach_grad()
    with ag.record():
        out = nd._contrib_flash_attention(q, k, v, num_heads=H)
        loss = (out * out).sum()
    loss.backward()
    assert out.shape == (B, T, C)
    # reference computation in numpy
    d = C // H
    qn = q.asnumpy().reshape(B, T, H, d).transpose(0, 2, 1, 3)
    kn = k.asnumpy().reshape(B, T, H, d).transpose(0, 2, 1, 3)
    vn = v.asnumpy().reshape(B, T, H, d).transpose(0, 2, 1, 3)
    s = np.einsum("bhqd,bhkd->bhqk", qn, kn) / np.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, vn).transpose(0, 2, 1, 3) \
        .reshape(B, T, C)
    np.testing.assert_allclose(out.asnumpy(), ref,
                               rtol=max(1e-4, _tol()),
                               atol=max(1e-4, _tol()))
    assert np.abs(q.grad.asnumpy()).sum() > 0


def test_mha_block_uses_fused_path(monkeypatch):
    from incubator_mxnet_tpu.models import transformer
    from incubator_mxnet_tpu.ops import registry

    calls = []
    od = registry.get("_contrib_flash_attention")
    orig = od.fn

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(od, "fn", counting)
    blk = transformer.MultiHeadAttention(32, 4, dropout=0.0)
    blk.initialize()
    x = nd.array(np.random.RandomState(0).randn(2, 16, 32)
                 .astype(np.float32))
    out = blk(x)
    assert out.shape == (2, 16, 32)
    assert calls, "MultiHeadAttention did not dispatch the fused op"


def test_mha_mask_branch_matches_fused():
    """The masked (unfused) attention branch — refactored onto the
    shape-free head helpers (r4) — equals the fused path when the mask
    is all-zeros, and actually masks when it is -inf-like."""
    from incubator_mxnet_tpu.models import transformer
    rs = np.random.RandomState(5)
    blk = transformer.MultiHeadAttention(32, 4, dropout=0.0)
    blk.initialize()
    x = nd.array(rs.randn(2, 8, 32).astype(np.float32))
    fused = blk(x).asnumpy()
    zero_mask = nd.array(np.zeros((1, 1, 8, 8), np.float32))
    masked = blk(x, zero_mask).asnumpy()
    np.testing.assert_allclose(masked, fused, rtol=1e-4, atol=1e-5)
    # causal -inf mask: position 0 must only attend to itself →
    # different from the unmasked result at later positions
    causal = np.triu(np.full((8, 8), -1e9, np.float32), k=1)
    out_c = blk(x, nd.array(causal[None, None])).asnumpy()
    assert np.abs(out_c - fused).max() > 1e-3


# -- ragged decode attention: one query row a slot, rows below a length ----

_RAGGED_T = 128         # two row blocks of 64


def _ragged_lengths(case, S):
    tb = att.ragged_row_block(_RAGGED_T)
    if case == "all_dead":
        return np.zeros(S, np.int32)
    if case == "one_live":                  # in the second slot block
        lens = np.zeros(S, np.int32)
        lens[S - 2] = tb + 1
        return lens
    edges = [0, 1, tb - 1, tb, tb + 1, _RAGGED_T]
    return np.array((edges * S)[:S], np.int32)


@pytest.mark.parametrize("case", ["edges", "all_dead", "one_live"])
@pytest.mark.parametrize("heads", [2, 1])            # d 64, d 128
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ragged_decode_attention_matches_masked_einsums(
        pallas_interpret, dtype, heads, case):
    """The kernel itself (interpret mode) against the masked einsums:
    lengths 0, 1, a block edge and its neighbours, T; every slot dead; a
    lone live slot behind dead blocks; float32 and bfloat16 leaves; two
    heads a row and one; 20 slots in blocks of 16.  Rows of a slot of
    length 0 are finite and compared with nothing."""
    S, G, T, W = 20, 2, _RAGGED_T, 128
    assert S % att._SLOT_BLOCK and T // att.ragged_row_block(T, dtype) == 2
    rs = np.random.RandomState(11 * heads + len(case))
    mk = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    q, k, v = mk(S, G, W), mk(S, G, T, W).astype(dtype), \
        mk(S, G, T, W).astype(dtype)
    lens = _ragged_lengths(case, S)
    out = np.asarray(att.ragged_decode_attention(
        q, k, v, jnp.asarray(lens), heads=heads, scale=0.11))
    assert out.shape == (S, G, W) and out.dtype == np.float32
    assert np.isfinite(out).all()
    live = lens > 0
    f32 = jnp.float32
    exact = np.asarray(att.dense_decode_attention(
        q, k.astype(f32), v.astype(f32), jnp.asarray(lens), heads=heads,
        scale=0.11))
    np.testing.assert_allclose(out[live], exact[live], rtol=_tol(),
                               atol=_tol())
    # and the reference on the leaves as they lie (bfloat16 leaves give
    # it scores rounded to bfloat16)
    as_is = np.asarray(att.dense_decode_attention(
        q.astype(dtype), k, v, jnp.asarray(lens), heads=heads, scale=0.11))
    tol = _tol() if dtype == "float32" else 0.05
    np.testing.assert_allclose(out[live], as_is[live], rtol=tol, atol=tol)


def test_decode_attention_lowers_the_kernel_for_a_tpu_only():
    """`decode_attention` chooses where it is lowered: the CPU's text holds
    the einsums and no kernel, leaves the kernel cannot tile take the
    einsums everywhere, and the count of rows read follows the choice."""
    S, G, T, W = 4, 2, 128, 128
    q, k = jnp.zeros((S, G, W)), jnp.zeros((S, G, T, W))
    lens = jnp.array([0, 1, 64, 65], jnp.int32)
    text = jax.jit(att.decode_attention).lower(q, k, k, lens).as_text()
    assert "dot_general" in text and "custom_call" not in text
    out = att.decode_attention(q, k, k, lens)
    ref = att.dense_decode_attention(q, k, k, lens)
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()
    assert list(np.asarray(att.decode_rows_read(lens, k))) == \
        [0, 64, 64, 128]
    narrow = jnp.zeros((S, G, T, 64))           # half a lane row
    assert not att._ragged_fits(narrow)
    assert list(np.asarray(att.decode_rows_read(lens, narrow))) == \
        [0, T, T, T]


# -- a decode step's new rows, written at the live slots alone -------------

def _live_mask(case, S):
    return {"none": np.zeros(S, bool),
            "one": np.arange(S) == 3,
            "every_other": np.arange(S) % 2 == 0,
            "all": np.ones(S, bool)}[case]


@pytest.mark.parametrize("case", ["none", "one", "every_other", "all"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_live_rows_write_is_the_indexed_update_at_live_slots(
        monkeypatch, dtype, case):
    """`live_rows_write` at `nmt_base`'s leaf geometry (G 4, T 256, W 128)
    over 8 slots at positions 0, T - 1, T and past it (nothing written),
    and others: at the live slots, bit for bit, what the indexed update
    writes.  float32 leaves take the kernel itself (interpret mode), which
    leaves every other byte as it was, dead slots included, and counts one
    trace; bfloat16 leaves (a row is half of a packed pair) take the
    indexed update and count none.  Lowered for a TPU, the kernel's
    results are its leaves (aliased operands)."""
    from incubator_mxnet_tpu.monitor import events
    S, G, T, W = 8, 4, 256, 128
    rs = np.random.RandomState(len(case))
    mk = lambda *shape: jnp.asarray(rs.randn(*shape), dtype)
    k, v, kn, vn = mk(S, G, T, W), mk(S, G, T, W), mk(S, G, W), mk(S, G, W)
    pos_np = np.array([0, T - 1, T, T + 9, 17, 8, 7, 200], np.int32)
    live_np = _live_mask(case, S)
    pos, live = jnp.asarray(pos_np), jnp.asarray(live_np)
    plan = att.live_rows_plan(pos, live, T)
    ok = live_np & (pos_np < T)
    assert int(plan[1][0]) == ok.sum()
    assert list(np.asarray(plan[0])[:ok.sum()]) == list(np.nonzero(ok)[0])
    f32 = lambda a: np.asarray(jnp.asarray(a, jnp.float32))
    want = att._live_update(k, v, kn, vn, *plan)
    fits = dtype == "float32"
    assert att._live_fits(k) == fits
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    traced = events.get("cache.rows_kernel_traces") or 0
    # a function of its own: a trace of this file's last case is not reused
    got = jax.jit(lambda *a: att.live_rows_write(*a))(k, v, kn, vn, plan)
    assert (events.get("cache.rows_kernel_traces") or 0) - traced == fits
    for new, ref, old in zip(got, want, (k, v)):
        assert new.dtype == old.dtype and new.shape == old.shape
        assert f32(new)[ok].tobytes() == f32(ref)[ok].tobytes()
        if fits:        # nothing else is written
            expect = f32(old).copy()
            expect[ok] = f32(ref)[ok]
            assert f32(new).tobytes() == expect.tobytes()
    if fits:
        monkeypatch.delenv("MXNET_PALLAS_INTERPRET")
        text = jax.jit(att.live_rows_write).trace(k, v, kn, vn, plan).lower(
            lowering_platforms=("tpu",)).as_text()
        call = [l for l in text.splitlines() if "tpu_custom_call" in l]
        assert len(call) == 1 and "live_rows_write" in text
        assert call[0].count("stablehlo.output_operand_alias<") == 2


# -- grouped decode attention: several query heads a key/value head ---------

def _grouped_by_hand(q, k, v, layer, lengths, scale):
    """A loop over slots and query heads in NumPy float64 over rows
    [0, length) of `layer`; query head i reads key/value head i // (H/G)."""
    f64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)
    q, k, v = f64(q), f64(k), f64(v)
    S, H, d = q.shape
    h = H // k.shape[2]
    out = np.zeros((S, H, d))
    for s, n in enumerate(lengths):
        for i in range(H if n else 0):
            sc = k[s, layer, i // h, :n] @ q[s, i] * scale
            p = np.exp(sc - sc.max())
            out[s, i] = (p / p.sum()) @ v[s, layer, i // h, :n]
    return out


@pytest.mark.parametrize("T,lengths", [
    # a full layer: 0, 1, a block border and the row after it, T, a short
    # slot between long ones (its steps past its rows hold the next slot's
    # first block), the last slot short
    (768, [0, 1, 256, 257, 768, 700, 3, 600, 40]),
    # a full ring of 512 in every slot, and rings still filling
    (512, [512, 512, 512, 1, 300, 512]),
])
@pytest.mark.parametrize("group", [6, 8])
def test_grouped_kernel_reads_each_slots_rows_up_to_its_length(
        pallas_interpret, T, lengths, group):
    """The kernel (interpret mode) over whole bfloat16 leaves (S, 3 layers,
    2, T, 128) at layer 1, groups of 6 and 8 query heads a key/value head,
    against the loop in float64 and against `masked_decode_attention` over
    the layer's rows under the lengths' mask.  Rows past a slot's length
    hold huge values, blocks past its last needed one and both other layers
    NaN: none may enter.  A slot of length 0 reads nothing and gives finite
    rows.  `grouped_rows_read` is the length rounded up to the row block,
    and `attn.grouped_kernel_traces` counts the one body traced."""
    from incubator_mxnet_tpu.monitor import events
    S, G, d, layers, dt = len(lengths), 2, 128, 3, jnp.bfloat16
    H = G * group
    tb = att.grouped_row_block(T)
    assert tb == 256 and T % tb == 0
    rs = np.random.RandomState(T + group)
    rnd = lambda *s: np.asarray(jnp.asarray(rs.randn(*s), dt), np.float32)
    q = rnd(S, H, d)
    k = np.full((S, layers, G, T, d), np.nan, np.float32)
    v = k.copy()
    for s, n in enumerate(lengths):
        end = -(-n // tb) * tb
        k[s, 1, :, :end], v[s, 1, :, :end] = 1e30, -1e30
        k[s, 1, :, :n], v[s, 1, :, :n] = rnd(G, n, d), rnd(G, n, d)
    want = _grouped_by_hand(q, k, v, 1, lengths, 0.088)
    q, k, v = (jnp.asarray(a, dt) for a in (q, k, v))
    lens = jnp.asarray(lengths, jnp.int32)
    traced = events.get("attn.grouped_kernel_traces") or 0
    got = np.asarray(jax.jit(lambda *a: att.grouped_decode_attention(
        *a, 0.088))(q, k, v, jnp.int32(1), lens))
    assert (events.get("attn.grouped_kernel_traces") or 0) == traced + 1
    assert got.shape == (S, H, d) and got.dtype == np.float32
    assert np.isfinite(got).all()
    live = np.asarray(lengths) > 0
    # bfloat16: the probabilities are rounded to 8 bits for the context
    tol = 1e-2 * np.abs(want).max()
    assert np.abs(got - want)[live].max() < tol
    # the masked einsums over the same rows, with the leaves' garbage made
    # finite (their probability-0 rows would carry a NaN into the context)
    clean = lambda a: jnp.where(
        jnp.arange(T)[None, None, :, None] < lens[:, None, None, None],
        a[:, 1], 0).astype(dt)
    ref = np.asarray(att.masked_decode_attention(
        q, clean(k), clean(v), jnp.arange(T)[None, :] < lens[:, None],
        0.088))
    assert np.abs(got - ref)[live].max() < tol
    assert list(np.asarray(att.grouped_rows_read(lens, k))) == \
        [-(-n // tb) * tb for n in lengths]


def test_grouped_einsums_are_the_masked_form_off_the_chip():
    """On the CPU (no interpreter), and for leaves the kernel does not tile
    (d 16, 48 rows), `grouped_decode_attention` is `masked_decode_attention`
    over the layer's rows under rows < length, bit for bit, and its text
    holds no kernel; the count of rows read is all T there; nothing is
    counted as traced with the kernel."""
    from incubator_mxnet_tpu.monitor import events
    rs = np.random.RandomState(2)
    traced = events.get("attn.grouped_kernel_traces") or 0
    for (S, H, G, T, d) in ((4, 12, 2, 512, 128), (3, 6, 2, 48, 16)):
        mk = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32))
        q, k, v = mk(S, H, d), mk(S, 2, G, T, d), mk(S, 2, G, T, d)
        lens = jnp.asarray(rs.randint(0, T + 1, S), jnp.int32)
        got = att.grouped_decode_attention(q, k, v, 1, lens, 0.3,
                                           part="window")
        want = att.masked_decode_attention(
            q, k[:, 1], v[:, 1], jnp.arange(T)[None, :] < lens[:, None], 0.3)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
        text = jax.jit(lambda *a: att.grouped_decode_attention(
            *a, 0.3)).lower(q, k, v, 1, lens).as_text()
        assert "dot_general" in text and "custom_call" not in text
        fits = d == 128
        assert att._grouped_fits(k) == fits
        tb = 256 if fits else T
        assert list(np.asarray(att.grouped_rows_read(lens, k))) == \
            [-(-int(n) // tb) * tb for n in lens]
    assert (events.get("attn.grouped_kernel_traces") or 0) == traced
