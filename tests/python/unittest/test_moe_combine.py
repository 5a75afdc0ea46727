"""`moe.held_experts_combine`, the Pallas kernel that hands the held
experts' rows back to their tokens, in interpret mode against the XLA
gathers it replaces and against NumPy float64; and the many-token form's
one trace for all its call sites of one shape (`moe._held_many`)."""
import numpy as onp
import pytest

from incubator_mxnet_tpu.monitor import events


def _interpret(monkeypatch):
    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.parallel import moe
    monkeypatch.setattr(config, "_OVERRIDES",
                        dict(config._OVERRIDES, MXNET_PALLAS_INTERPRET=True))
    assert moe._interpret()


def _picks(T, k, N, held, seed):
    """where, gate, held (T, k) over N rows: a pick's row, its gate in
    (0, 1), and the held mask `held` names ("mixed": the first three tokens
    hold 0, 1 and k picks, the rest one in three; "all"; "none")."""
    rs = onp.random.RandomState(seed)
    where = rs.randint(0, N, (T, k)).astype(onp.int32)
    gate = rs.rand(T, k).astype(onp.float32)
    if held == "all":
        mask = onp.ones((T, k), bool)
    elif held == "none":
        mask = onp.zeros((T, k), bool)
    else:
        mask = rs.rand(T, k) < 1.0 / 3
        mask[0], mask[1], mask[2] = False, onp.arange(k) == k // 2, True
    return where, gate, mask


def _gathers(rows, where, gate, held):
    """The XLA form, as `moe._gathers` sums: a gather of T rows a pick, a
    pick that is not held reading row 0 times 0, in pick order from 0."""
    import jax.numpy as jnp
    rows = jnp.asarray(rows)[:, 0]
    where = jnp.where(held, where, 0)
    gate = jnp.where(held, gate, 0.0)
    out = jnp.zeros((where.shape[0], rows.shape[1]), jnp.float32)
    for i in range(where.shape[1]):
        out = out + rows[where[:, i]] * gate[:, i:i + 1]
    return onp.asarray(out)


@pytest.mark.parametrize("k,held", [(8, "mixed"), (10, "mixed"),
                                    (8, "all"), (8, "none")])
def test_the_combine_is_the_gathers(monkeypatch, k, held):
    """The same sum of the same terms in the same order: equal to the
    gathers' but for the rounding of a fused multiply-add where a backend
    contracts one, at most one float32 ulp of the running sum a term."""
    from incubator_mxnet_tpu.parallel import moe
    T, D, N = 40, 128, 96
    where, gate, mask = _picks(T, k, N, held, k)
    rows = onp.random.RandomState(1).randn(N, 1, D).astype(onp.float32)
    want = _gathers(rows, where, gate, mask)
    _interpret(monkeypatch)
    got = onp.asarray(moe.held_experts_combine(rows, where, gate, mask))
    assert got.shape == (T, D) and got.dtype == onp.float32
    terms = onp.where(mask[:, :, None], rows[where, 0] * gate[:, :, None], 0)
    ulp = onp.spacing(onp.abs(terms).sum(axis=1).astype(onp.float32))
    assert (onp.abs(got - want) <= k * ulp).all()
    # a token that holds no pick gets nothing
    assert not got[~mask.any(axis=1)].any()
    assert got.any() == (held != "none")
    if held == "mixed":
        assert not got[0].any() and got[1].any() and got[2].any()


def test_no_row_that_no_held_pick_names_is_read(monkeypatch):
    """NaN in every row that no held pick names, the stale buffer rows of
    picks that are not held among them: the kernel's result is finite and
    is the float64 sum.  (The gathers read row 0 for a pick that is not
    held, so this case is the kernel's alone.)"""
    from incubator_mxnet_tpu.parallel import moe
    T, k, D, N = 48, 8, 128, 200
    where, gate, mask = _picks(T, k, N, "mixed", 3)
    rs = onp.random.RandomState(2)
    rows = onp.full((N, 1, D), onp.nan, onp.float32)
    named = onp.unique(where[mask])
    rows[named] = rs.randn(len(named), 1, D)
    _interpret(monkeypatch)
    got = onp.asarray(moe.held_experts_combine(rows, where, gate, mask))
    r64 = rows[:, 0].astype(onp.float64)
    want = onp.zeros((T, D))
    for i in range(k):
        m = mask[:, i]
        want[m] += r64[where[m, i]] * gate[m, i, None]
    assert onp.isfinite(got).all()
    assert onp.abs(got - want).max() < 1e-5 * onp.abs(want).max()


def test_the_grouped_rows_are_the_loops_rows_widened(monkeypatch):
    """`held_experts_grouped` keeps its rows in float32 so that the combine
    moves one by one DMA; each is rounded to x's type first, so a held
    pick's row is the loop's bfloat16 row, widened."""
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    T, D, F, E, k, held, tile = 40, 128, 128, 8, 2, 4, 16
    rs = onp.random.RandomState(5)
    bf = lambda *s: jnp.asarray(rs.randn(*s) / 4, jnp.bfloat16)
    x, wg, wu, wd = bf(T, D), bf(2, held, F, D), bf(2, held, F, D), \
        bf(2, held, D, F)
    gate, expert = moe.topk_route(jnp.asarray(rs.randn(T, E), jnp.float32), k)
    key = jnp.where(expert < held, expert, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    count = jnp.sum(jnp.eye(held + 1, dtype=jnp.int32)[key], axis=0)[:held]
    start = jnp.cumsum(count) - count
    plan = moe._tile_plan(count, start, tile, T * k // tile + held)
    args = (x, order // k, plan[:4], wg, wu, wd, jnp.int32(1))
    loop = onp.asarray(moe._held_experts_loop(*args, tile=tile)
                       .astype(jnp.float32))
    _interpret(monkeypatch)
    rows = onp.asarray(moe.held_experts_grouped(*args, tile=tile))
    assert rows.shape == (loop.shape[0], 1, D) and rows.dtype == onp.float32
    used, at = int(plan[0][0]), onp.asarray(plan[4])
    valid = [r for e in range(held)
             for r in range(at[e], at[e] + int(count[e]))]
    assert used > 1 and len(valid) == int(count.sum()) > 0
    assert (rows[valid, 0] == loop[valid]).all()
    assert (rows[valid, 0] == rows[valid, 0].astype(jnp.bfloat16)
            .astype(onp.float32)).all()


def _four_layers(moe, jnp):
    """Four layers' expert halves at one shape, as a period calls them."""
    def f(x, scores, wg, wu, wd):
        for layer in range(4):
            gate, expert = moe.topk_route(scores + layer, 2)
            x = x + moe.held_experts(x, gate, expert, wg, wu, wd, 0, tile=8,
                                     layer=jnp.int32(layer))
        return x
    return f


def _operands():
    """x (64, 128), router scores over 8 experts, the stacks of 4 layers of
    4 held experts of 128 x 128."""
    import jax.numpy as jnp
    rs = onp.random.RandomState(6)
    rand = lambda *s: jnp.asarray(rs.randn(*s) / 4, jnp.float32)
    return (rand(64, 128), rand(64, 8), rand(4, 4, 128, 128),
            rand(4, 4, 128, 128), rand(4, 4, 128, 128))


def test_four_call_sites_lower_one_kernel_of_each():
    """A function that calls `held_experts` four times at one shape, lowered
    for a TPU: ONE grouped and ONE combine custom call in the module, the
    four sites' shared function; a CPU process counts no kernel trace."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    moe._held_many.clear_cache()
    before = [events.get(c) or 0 for c in ("moe.grouped_traces",
                                           "moe.combine_traces")]
    text = jax.jit(_four_layers(moe, jnp)).trace(*_operands()).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert len(calls) == 2, len(calls)
    assert text.count('kernel_name = "held_experts_grouped"') == 1
    assert text.count('kernel_name = "held_experts_combine"') == 1
    assert text.count("call @_held_many(") == 4
    assert [events.get(c) or 0 for c in ("moe.grouped_traces",
                                         "moe.combine_traces")] == before


def test_interpret_toggled_between_calls_of_one_shape(monkeypatch):
    """The trace of one kernel choice is never handed to another: four call
    sites, run with the kernels interpreted, count ONE trace of each kernel
    and agree with the CPU's form; then, with the switch off again at the
    same shapes, the lowering for a TPU holds the Mosaic calls, not the
    interpreters, and the CPU's form counts nothing."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.parallel import moe
    ops = _operands()
    f = _four_layers(moe, jnp)
    # a jitted function of its own each time: the model's trace is not
    # reused, only `_held_many`'s may be
    run = lambda: onp.asarray(jax.jit(lambda *a: f(*a))(*ops))
    lower = lambda: jax.jit(lambda *a: f(*a)).trace(*ops).lower(
        lowering_platforms=("tpu",)).as_text()
    count = lambda: [events.get(c) or 0 for c in ("moe.grouped_traces",
                                                  "moe.combine_traces")]
    moe._held_many.clear_cache()
    want = run()
    before = count()
    _interpret(monkeypatch)
    got = run()
    assert count() == [before[0] + 1, before[1] + 1]
    assert "tpu_custom_call" not in lower()
    assert onp.abs(got - want).max() < 2e-6 * onp.abs(want).max()
    monkeypatch.undo()
    assert not moe._interpret()
    again = run()
    assert len([l for l in lower().splitlines()
                if "tpu_custom_call" in l]) == 2
    assert count() == [before[0] + 1, before[1] + 1]
    assert (again == want).all()
