"""Expert parallelism: switch-routed Mixture-of-Experts over a mesh
axis.

Beyond-reference axis (absent in MXNet 1.x — SURVEY §2.3 lists only
DP + ctx_group).  TPU-first shape, per the Switch-Transformer /
scaling-book recipe: tokens live data-sharded, experts live one (or
more) per device along the `expert` axis, and dispatch/return ride
TWO `all_to_all` collectives over ICI.  Routing is the capacity-
factored top-1 einsum dispatch — fixed shapes, no sorting, fully
XLA-compilable; overflowing tokens are dropped (residual passes them
through, the standard Switch behaviour).

`switch_route`/`moe_apply` are shard_map-body functions (like
ring_attention): call them inside `shard_map` with `axis_name` bound to
the expert axis.  Gradients flow through `all_to_all`/einsum natively.

**Dropless top-k over held experts** (`topk_route`, `held_experts`):
the serving form.  The router scores ALL experts, each token keeps its
top k with gates renormalised over those k, and a device computes the
terms of the experts it HOLDS (`first_held`, and as many as its weight
stacks carry) for the tokens routed to them.  Nothing is dropped and
nothing stands in for the experts held elsewhere: their devices add
their terms.  On one device the layer runs as it is, without an
exchange.  `group_limited_route` is the routing of models that keep a
token's experts inside a few groups and do not renormalise the gates; it
returns what `topk_route` returns, and `held_experts` takes either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..monitor import events
from ..ops.attention import _interpret
from ..telemetry import costs as _costs

__all__ = ["switch_route", "moe_apply", "moe_ffn", "topk_route",
           "group_limited_route", "swiglu", "held_experts",
           "held_experts_grouped", "held_experts_combine", "held_load"]


def switch_route(router_logits, capacity):
    """Top-1 capacity-factored routing (per-device local tokens).

    router_logits: (T, E).  Returns (dispatch (T, E, C) one-hot,
    combine (T, E, C) prob-weighted, aux_loss scalar — the Switch
    load-balancing loss)."""
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                  # (T,)
    mask = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T, E)
    # position of each token in its expert's queue
    pos = jnp.cumsum(mask, axis=0) * mask                # 1-based
    keep = (pos <= capacity) * mask                      # (T, E)
    pos_idx = (pos - 1.0) * keep                         # 0-based
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos_idx.astype(jnp.int32), capacity, dtype=jnp.float32)
    gate = jnp.sum(probs * keep, axis=-1, keepdims=True)  # (T, 1)
    combine = dispatch * gate[..., None]
    # load-balancing aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac = jnp.mean(mask, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_apply(x, router_w, expert_fn, expert_params, axis_name,
              capacity_factor=1.25):
    """Expert-parallel switch MoE layer (shard_map body).

    x: (T_local, d) this device's tokens.
    router_w: (d, E_total) router weights (replicated).
    expert_fn(params, tokens) -> tokens: one expert's computation;
        `expert_params` is THIS device's expert's params (tree sharded
        P('expert') outside; a leading axis of 1 is squeezed).
    Returns (T_local, d) combined outputs + aux loss.  Tokens routed
    past capacity are dropped (add x residually outside if desired).
    """
    n_dev = lax.psum(1, axis_name)
    T, d = x.shape
    E = router_w.shape[-1]
    if E % n_dev:
        raise ValueError("experts %d not divisible by axis size %d"
                         % (E, n_dev))
    e_local = E // n_dev
    capacity = int(max(1, (T * capacity_factor) // E))

    from .mesh import squeeze_stage_axis
    eparams = squeeze_stage_axis(expert_params)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    dispatch, combine, aux = switch_route(logits, capacity)

    # gather this device's dispatched tokens: (E, C, d)
    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           x.astype(jnp.float32))
    # all_to_all: split the expert axis across devices, concat the
    # sender shards — device e receives (e_local, n_dev*C, d): ALL
    # devices' tokens for ITS experts
    expert_in = expert_in.reshape(n_dev, e_local * capacity, d)
    recv = lax.all_to_all(expert_in, axis_name, split_axis=0,
                          concat_axis=0, tiled=False)
    # recv: (n_dev, e_local*C, d) where axis 0 = source device
    recv = recv.reshape(n_dev, e_local, capacity, d) \
        .transpose(1, 0, 2, 3) \
        .reshape(e_local, n_dev * capacity, d)
    # run the local expert(s)
    if e_local == 1:
        out = expert_fn(eparams, recv[0].astype(x.dtype))[None]
    else:
        out = jax.vmap(lambda p, t: expert_fn(p, t.astype(x.dtype)),
                       in_axes=(0, 0))(eparams, recv)
    out = out.astype(jnp.float32)
    # reverse the shuffle
    back = out.reshape(e_local, n_dev, capacity, d) \
        .transpose(1, 0, 2, 3) \
        .reshape(n_dev, e_local * capacity, d)
    sent = lax.all_to_all(back, axis_name, split_axis=0,
                          concat_axis=0, tiled=False)
    sent = sent.reshape(E, capacity, d)
    # combine back to token order, weighted by the router gate
    y = jnp.einsum("tec,ecd->td", combine, sent)
    # aux is averaged across the axis so it is replicated (a scalar
    # loss term addable outside shard_map)
    return y.astype(x.dtype), lax.pmean(aux, axis_name)


def moe_ffn(d_model, d_hidden, n_experts, key=None):
    """Convenience: per-expert FFN params (stacked on the expert axis —
    shard with P('expert')) + the matching expert_fn."""
    import numpy as np
    rs = np.random.RandomState(0 if key is None else key)
    params = {
        "w1": jnp.asarray(rs.randn(n_experts, d_model, d_hidden)
                          * (1.0 / np.sqrt(d_model)), jnp.float32),
        "w2": jnp.asarray(rs.randn(n_experts, d_hidden, d_model)
                          * (1.0 / np.sqrt(d_hidden)), jnp.float32),
    }

    def expert_fn(p, t):
        h = jax.nn.relu(t @ p["w1"])
        return h @ p["w2"]

    return params, expert_fn


# -- dropless top-k over the experts a device holds ----------------------

def topk_route(router_logits, k, scale=1.0):
    """Softmax over all experts, the k largest, renormalised over those
    k, times `scale`.  router_logits (T, E) -> (gate (T, k) float32,
    expert (T, k) int32), best first, ties to the lower expert."""
    with _costs.part("experts"):
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        top, expert = lax.top_k(probs, k)
        gate = top / jnp.sum(top, axis=-1, keepdims=True)
        # a scale of 1 adds no product to the lowered program
        return (gate if scale == 1.0 else gate * scale), expert


def group_limited_route(router_logits, k, n_group, topk_group, scale=1.0):
    """Softmax over all experts; the experts lie in `n_group` equal groups,
    a group scores what its best expert scores, the `topk_group` best groups
    are kept and the k largest experts inside them; the gates are the
    softmax's own values times `scale`, NOT renormalised.  router_logits
    (T, E) -> (gate (T, k) float32, expert (T, k) int32) in `topk_route`'s
    form, best first, ties to the lower group and to the lower expert."""
    with _costs.part("experts"):
        T, E = router_logits.shape
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        best = jnp.max(probs.reshape(T, n_group, E // n_group), axis=-1)
        _, groups = lax.top_k(best, topk_group)
        kept = jnp.any(
            groups[:, :, None] == jnp.arange(n_group)[None, None, :],
            axis=1)                                             # (T, n_group)
        # a softmax's values are positive, so -1 is below every kept expert
        inside = jnp.where(jnp.repeat(kept, E // n_group, axis=1), probs, -1.0)
        top, expert = lax.top_k(inside, k)
        return top * scale, expert


def held_load(expert, first_held, n_held):
    """What routing asks of the held experts: (picks (T,) the number of a
    token's k experts that are held, at_fullest (T,) how many of them are
    the held expert with the most tokens).  Their sums over tokens are
    the held picks and the fullest held expert's tokens."""
    with _costs.part("experts"):
        local = expert - first_held
        held = (local >= 0) & (local < n_held)
        load = jnp.sum(jax.nn.one_hot(jnp.where(held, local, n_held), n_held,
                                      dtype=jnp.int32), axis=(0, 1))
        fullest = jnp.argmax(load)
        return (jnp.sum(held, axis=-1, dtype=jnp.int32),
                jnp.sum(held & (local == fullest), axis=-1, dtype=jnp.int32))


def swiglu(x, wg, wu, wd):
    """One expert: wd (silu(wg x) * wu x); weights are (out, in)."""
    f32 = jnp.float32
    a = jax.nn.silu(jnp.einsum("td,fd->tf", x, wg,
                               preferred_element_type=f32)) \
        * jnp.einsum("td,fd->tf", x, wu, preferred_element_type=f32)
    return jnp.einsum("tf,df->td", a.astype(x.dtype), wd,
                      preferred_element_type=f32)


# the weight blocks of one grid step, both pipeline buffers of all three,
# that `held_experts_grouped` may keep in VMEM, beside its row tiles, its
# result tile and the products' float32 temporaries (`_grouped_vmem`)
_GROUPED_WEIGHT_BYTES = 40 << 20


def _grouped_hidden_block(F, D, itemsize):
    """Columns of an expert's hidden width F that one grid step of
    `held_experts_grouped` multiplies: all of F where the three (F, D)
    blocks fit in VMEM twice over, else F's largest divisor that is a
    multiple of 128 lanes and does."""
    fits = lambda fb: 2 * 3 * fb * D * itemsize <= _GROUPED_WEIGHT_BYTES
    if fits(F):
        return F
    for n in range(2, F // 128 + 1):
        if F % (128 * n) == 0 and fits(F // n):
            return F // n
    return 128 if F % 128 == 0 else F


def _grouped_vmem(tile, fb, D, itemsize):
    """Bytes of VMEM `held_experts_grouped` asks for: its weight blocks and
    float32 result tile twice over, the two float32 row buffers, one tile's
    operands and products, and an eighth more; no less than the 16 MB a
    fusion gets anyway.  What a kernel reserves, XLA cannot use to keep the
    operands of the OTHER ops of the executable near: under a flat 64 MB
    `keye_vl2_30b_a3b`'s prefill lost 0.7 ms a layer in its attention
    loops."""
    need = 2 * 3 * fb * D * itemsize + 2 * tile * D * 8 \
        + tile * (D * (4 + itemsize) + fb * (8 + itemsize))
    return max(16 << 20, need + need // 8)


def _tile_plan(count, start, tile, steps):
    """The tiles of the held experts' sorted runs, run after run, each run
    from a tile's first row on: (used, the tiles in all; expert, first,
    valid (steps,): whose tile j is, its first row among the sorted picks
    and how many of its rows belong to the run, 0 past the last tile; at
    (n_held,): the first row of e's run among the tiles' rows)."""
    count, start = count.astype(jnp.int32), start.astype(jnp.int32)
    tiles = -(-count // tile)
    tile_end = jnp.cumsum(tiles)
    at = (tile_end - tiles) * tile
    j = jnp.arange(steps, dtype=jnp.int32)
    e = jnp.minimum(jnp.sum(tile_end[None, :] <= j[:, None], axis=1),
                    count.shape[0] - 1).astype(jnp.int32)
    off = j * tile - at[e]
    return (tile_end[-1:], e, start[e] + off,
            jnp.clip(count[e] - off, 0, tile), at)


def _grouped_kernel(layer_ref, used_ref, exp_ref, first_ref, valid_ref,
                    tok_ref, x_hbm, wg_ref, wu_ref, wd_ref, o_ref, xbuf, sem,
                    *acc, nf, dtype):
    del layer_ref, exp_ref              # read by the weights' index maps
    j, f = pl.program_id(0), pl.program_id(1)
    used = used_ref[0]
    f32 = jnp.float32
    rows_shape = (o_ref.shape[0], o_ref.shape[2])

    def rows_of(t, wait):
        """Tile t's rows of x, one DMA a valid row, into buffer t % 2."""
        slot, base = t % 2, first_ref[t]

        def one(r, carry):
            cp = pltpu.make_async_copy(
                x_hbm.at[pl.ds(tok_ref[base + r], 1)],
                xbuf.at[slot, pl.ds(r, 1)], sem.at[slot])
            cp.wait() if wait else cp.start()
            return carry

        lax.fori_loop(0, valid_ref[t], one, 0)

    @pl.when((f == 0) & (j < used))
    def _rows():
        @pl.when(j == 0)
        def _first():
            rows_of(0, False)

        @pl.when(j + 1 < used)          # the next tile's, under this one's
        def _ahead():                   # products
            rows_of(j + 1, False)

        rows_of(j, True)

    def store(y):
        row = lax.broadcasted_iota(jnp.int32, y.shape, 0)
        y = jnp.where(row < valid_ref[j], y, 0.0).astype(dtype)
        o_ref[...] = y.astype(f32).reshape(o_ref.shape)

    @pl.when(j < used)
    def _tile():
        xt = xbuf[j % 2].reshape(rows_shape).astype(dtype)
        nt = (((1,), (1,)), ((), ()))   # both operands contract their last
        g = lax.dot_general(xt, wg_ref[...], nt, preferred_element_type=f32)
        u = lax.dot_general(xt, wu_ref[...], nt, preferred_element_type=f32)
        y = lax.dot_general((jax.nn.silu(g) * u).astype(xt.dtype),
                            wd_ref[...], nt, preferred_element_type=f32)
        if nf == 1:
            store(y)
            return
        total, = acc

        @pl.when(f == 0)
        def _start():
            total[...] = y

        @pl.when(f > 0)
        def _more():
            total[...] += y

        @pl.when(f == nf - 1)
        def _done():
            store(total[...])

    @pl.when((used == 0) & (j == 0) & (f == 0))
    def _none():                        # no held pick: row 0 is read, as 0
        o_ref[...] = jnp.zeros_like(o_ref)


def held_experts_grouped(x, token, plan, wg, wu, wd, layer, *, tile):
    """The held experts' sorted runs as ONE grouped matmul (Pallas, TPU).
    x (T, D); pick i of the sorted picks is token `token[i]`'s (T * k,);
    `plan` the first four of `_tile_plan` over len(plan[1]) tiles of `tile`
    rows; wg, wu (layers, n_held, F, D), wd (layers, n_held, D, F) the
    stacks of all layers, read at [layer, expert] where they lie.  Returns
    the tiles' rows (tiles * tile, 1, D), each rounded to x's type and held
    in float32, the layout in which `held_experts_combine` moves one row by
    one DMA: rows of a tile past its run's end are 0, tiles past the last
    used one are not written (nothing reads them).

    Grid (tiles, blocks of F).  Whose tile a step multiplies, where its
    rows start and how many are valid are prefetched scalars; the weights'
    index maps read [layer, expert], so consecutive tiles of one expert
    keep its weights in VMEM and the next expert's come in under this
    one's products (the pipeline's two buffers).  The rows of x are
    gathered by the kernel itself, a DMA a valid row from x in HBM, the
    next tile's under this tile's products; Mosaic moves a single row only
    of a 32-bit array whose rows are its leading axis, so x goes in as
    float32 (T, 1, D), which holds its values exactly.  A step past the
    last used tile repeats the last block indices and does nothing, so the
    cost follows the held picks.  Rounding as `swiglu`'s: operands in x's type,
    float32 accumulation, silu(g) * u in float32, cast to x's type before
    the down projection, float32 until the result is rounded to x's type."""
    D, F = x.shape[1], wg.shape[-2]
    steps = plan[1].shape[0]
    fb = _grouped_hidden_block(F, D, jnp.dtype(wg.dtype).itemsize)
    nf = F // fb
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1)] \
        + [a.astype(jnp.int32) for a in plan + (token,)]

    def tile_of(j, used):               # a step past the last repeats it
        return jnp.minimum(j, jnp.maximum(used[0] - 1, 0))

    up = pl.BlockSpec(
        (None, None, fb, D),
        lambda j, f, l, used, exp, *_: (l[0], exp[tile_of(j, used)], f, 0))
    down = pl.BlockSpec(
        (None, None, D, fb),
        lambda j, f, l, used, exp, *_: (l[0], exp[tile_of(j, used)], 0, f))
    scratch = [pltpu.VMEM((2, tile, 1, D), jnp.float32),
               pltpu.SemaphoreType.DMA((2,))]
    if nf > 1:                          # the down projection's running sum
        scratch.append(pltpu.VMEM((tile, D), jnp.float32))
    return pl.pallas_call(
        functools.partial(_grouped_kernel, nf=nf, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(steps, nf),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), up, up, down],
            out_specs=pl.BlockSpec(
                (tile, 1, D),
                lambda j, f, l, used, *_: (tile_of(j, used), 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((steps * tile, 1, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_grouped_vmem(
                tile, fb, D, jnp.dtype(wg.dtype).itemsize)),
        interpret=_interpret(),
        name="held_experts_grouped",
    )(*scalars, x.astype(jnp.float32)[:, None, :], wg, wu, wd)


def _held_experts_loop(x, token, plan, wg, wu, wd, layer, *, tile):
    """`held_experts_grouped`'s rows, (tiles * tile, D) in x's type, by a
    loop over the used tiles, each a slice of the sorted picks, a gather of
    its rows of x, `swiglu` against one expert's weights read at
    [layer, expert], and an update of the result: the CPU's form and the
    tests' oracle.  (Rows of a tile past its
    run's end are the next picks' under this expert: nothing reads them.)"""
    used, expert, first, _ = plan
    token = jnp.concatenate([token, jnp.zeros((tile,), jnp.int32)])

    def one_tile(j, rows):
        e = expert[j]
        picks = lax.dynamic_slice(token, (first[j],), (tile,))
        y = swiglu(x[picks], wg[layer, e], wu[layer, e], wd[layer, e])
        return lax.dynamic_update_slice(rows, y.astype(rows.dtype),
                                        (j * tile, 0))

    return lax.fori_loop(
        0, used[0], one_tile,
        jnp.zeros((expert.shape[0] * tile, x.shape[1]), x.dtype))


def _grouped_fits(x, wg, tile):
    """Whether the kernel takes these shapes: lanes and sublanes whole."""
    D, F = x.shape[1], wg.shape[-2]
    sub = 32 // jnp.dtype(x.dtype).itemsize
    return D % 128 == 0 and F % 128 == 0 and tile % sub == 0 \
        and x.dtype == wg.dtype


# the rows of a block of tokens' held picks, both buffers, that
# `held_experts_combine` may keep in VMEM
_COMBINE_ROW_BYTES = 8 << 20


def _combine_block(T, k, D):
    """Tokens a grid step of `held_experts_combine` sums: the most, of 256
    down to 8 in powers of two, that divide T and whose picks' rows fit
    both buffers in `_COMBINE_ROW_BYTES`; else all T where they are at most
    256 and fit (a block of the whole dimension); else None.  (A token's
    place in its block is packed in 8 bits beside its row.)"""
    fits = lambda bt: 2 * k * bt * D * 4 <= _COMBINE_ROW_BYTES
    for bt in (256, 128, 64, 32, 16, 8):
        if T % bt == 0 and fits(bt):
            return bt
    return T if T <= 256 and fits(T) else None


def _combine_vmem(bt, k, D):
    """Bytes of VMEM `held_experts_combine` asks for: both buffers of a
    block's held rows, the running sums, the float32 result block twice
    over, and an eighth more; no less than the 16 MB a fusion gets
    anyway."""
    need = 2 * k * bt * D * 4 + 3 * bt * D * 4
    return max(16 << 20, need + need // 8)


def _combine_kernel(start_ref, pick_ref, gate_ref, rows_hbm, o_ref, buf, acc,
                    sem):
    b = pl.program_id(0)

    def rows_of(blk, wait):
        """Block blk's held rows, one DMA each, into buffer blk % 2."""
        slot, first = blk % 2, start_ref[blk]

        def one(n, carry):
            cp = pltpu.make_async_copy(
                rows_hbm.at[pl.ds(pick_ref[first + n] >> 8, 1)],
                buf.at[slot, pl.ds(n, 1)], sem.at[slot])
            cp.wait() if wait else cp.start()
            return carry

        lax.fori_loop(0, start_ref[blk + 1] - first, one, 0)

    @pl.when(b == 0)
    def _first():
        rows_of(0, False)

    @pl.when(b + 1 < pl.num_programs(0))   # the next block's, under this
    def _ahead():                          # block's sums
        rows_of(b + 1, False)

    rows_of(b, True)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    slot, first = b % 2, start_ref[b]

    def add(n, carry):
        """A held pick's term into its token's running sum: a token's held
        picks come in pick order."""
        t = pick_ref[first + n] & 255
        acc[pl.ds(t, 1)] += buf[slot, pl.ds(n, 1)] * gate_ref[first + n]
        return carry

    lax.fori_loop(0, start_ref[b + 1] - first, add, 0)
    o_ref[...] = acc[...].reshape(o_ref.shape)


def held_experts_combine(rows, where, gate, held):
    """The held experts' rows handed back to their tokens (Pallas, TPU):
    for each token, the sum over its held picks of gate * rows[where].
    rows (N, 1, D) float32 from `held_experts_grouped`; where, gate, held
    (T, k): a pick's row among them, its gate, whether it is held.  Returns
    (T, D) float32.

    Grid (blocks of tokens, `_combine_block`).  The held picks alone, in
    token and pick order, are prefetched scalars: where each block's
    begin, each one's row and token in its block (packed, row << 8 |
    token) and its gate.  A loop over a block's held picks moves each one's
    row by one DMA from rows in HBM into a VMEM buffer, the next block's
    under this block's sums; a pick that is not held costs nothing.  A
    token's sum runs in float32 from zeros over its held picks in pick
    order, out + row_i * gate_i: the gathers' sum (`_gathers`), whose
    picks that are not held add 0, term for term; a row that no held pick
    names is never read."""
    T, k = where.shape
    D = rows.shape[-1]
    bt = _combine_block(T, k, D)
    flat = held.reshape(-1)
    at = jnp.cumsum(flat.astype(jnp.int32))        # held picks up to each
    into = jnp.where(flat, at - 1, T * k)           # its place, or dropped
    token = jnp.arange(T * k, dtype=jnp.int32) // k % bt
    pick = jnp.zeros((T * k,), jnp.int32).at[into].set(
        (where.reshape(-1).astype(jnp.int32) << 8) | token, mode="drop")
    gates = jnp.zeros((T * k,), jnp.float32).at[into].set(
        gate.reshape(-1).astype(jnp.float32), mode="drop")
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             at.reshape(T // bt, bt * k)[:, -1]])
    return pl.pallas_call(
        _combine_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T // bt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((bt, D), lambda b, *_: (b, 0)),
            scratch_shapes=[pltpu.VMEM((2, bt * k, 1, D), jnp.float32),
                            pltpu.VMEM((bt, 1, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_combine_vmem(bt, k, D)),
        interpret=_interpret(),
        name="held_experts_combine",
    )(start, pick, gates, rows)


def held_experts(x, gate, expert, wg, wu, wd, first_held, tile=256,
                 run_tile=None, layer=None):
    """sum over a token's picks e that this device holds of
    gate_e * expert_e(x): the device's share of a dropless top-k layer.

    x (T, D); gate, expert (T, k) from `topk_route`; wg, wu
    (n_held, F, D) and wd (n_held, D, F) the held experts' weights, expert
    `first_held + i` at index i.  Returns (T, D) float32.

    With `layer` (a traced scalar), wg, wu, wd are the stacks of ALL layers,
    (layers, n_held, ...), and an expert's weights are read at
    [layer, expert] where they lie: a layer's slice taken first and an
    expert's slice of that inside the loop over tiles is a copy of the
    layer's experts (0.4 GB a layer at 64 experts of 3 x 512 x 2048).
    `run_tile` is the rows of a tile of the many-token form where they
    should differ from `tile`, the bound of the few-token form.

    Few tokens (T <= tile, a decode step): every held expert multiplies
    every token under its gate, zero where it was not picked; the weights
    stream once either way and there is nothing to sort.  Many tokens (a
    prefill): the held picks are sorted by expert and each expert's run
    is multiplied in tiles of `tile` rows, as many tiles as its tokens
    need and no more, so the work follows the picks, however uneven, and
    the rows come back to their tokens: `held_experts_grouped` and
    `held_experts_combine` where the layer is lowered for a TPU (and
    wherever `MXNET_PALLAS_INTERPRET` runs the kernels themselves),
    `_held_experts_loop` and a gather of T rows a pick elsewhere and for
    shapes the kernels do not tile.  That form is ONE jitted function
    (`_held_many`) that the call sites of one shape share."""
    with _costs.part("experts"):
        return _held_experts(x, gate, expert, wg, wu, wd, first_held, tile,
                             run_tile, layer)


def _held_experts(x, gate, expert, wg, wu, wd, first_held, tile, run_tile,
                  layer):
    if x.shape[0] <= tile:
        n_held = wg.shape[0 if layer is None else 1]
        f32 = jnp.float32
        local = expert - first_held
        held = (local >= 0) & (local < n_held)
        if layer is not None:
            wg, wu, wd = wg[layer], wu[layer], wd[layer]
        g = jnp.sum(jax.nn.one_hot(jnp.where(held, local, n_held), n_held,
                                   dtype=f32) * gate[..., None], axis=1)
        a = jax.nn.silu(jnp.einsum("td,efd->tef", x, wg,
                                   preferred_element_type=f32)) \
            * jnp.einsum("td,efd->tef", x, wu, preferred_element_type=f32)
        return jnp.einsum("tef,edf->td", (a * g[..., None]).astype(x.dtype),
                          wd, preferred_element_type=f32)

    if run_tile is not None:
        tile = run_tile
    if layer is None:                   # one layer's weights: a stack of one
        wg, wu, wd, layer = wg[None], wu[None], wd[None], 0
    return _held_many(x, gate, expert, wg, wu, wd,
                      jnp.asarray(layer, jnp.int32), first_held=first_held,
                      tile=tile, form=_many_form(x, gate.shape[1], wg, tile))


def _many_form(x, k, wg, tile):
    """What `_held_many` runs for these shapes, read where the layer is
    traced: "interpret" where `MXNET_PALLAS_INTERPRET` runs the kernels
    themselves, "kernel" where the kernels tile the shapes (they run where
    the layer is lowered for a TPU, the loop and the gathers elsewhere),
    "loop" where they do not."""
    T, D = x.shape
    bt = _combine_block(T, k, D)
    if bt is None:
        return "loop"
    if _interpret():
        return "interpret"
    return "kernel" if _grouped_fits(x, wg, tile) and bt % 8 == 0 \
        else "loop"


@functools.partial(jax.jit, static_argnames=("first_held", "tile", "form"))
def _held_many(x, gate, expert, wg, wu, wd, layer, *, first_held, tile,
               form):
    """`held_experts`' many-token form over stacked weights, traced and
    lowered ONCE for all its call sites of one shape: a period's four
    expert halves in a model's scan share one jaxpr, and a module lowers it,
    kernels and all, once (XLA inlines the call: one kernel call a layer
    after that).  `form` (`_many_form`) is read by the caller, so a trace
    made under another choice is never handed back.  The counters are
    trace-time side effects, as `serve.traces` is: one a trace that holds
    the kernels, however many call sites share it."""
    T, k = gate.shape
    n_held = wg.shape[1]
    local = expert - first_held
    held = (local >= 0) & (local < n_held)
    # picks in token order, flattened; held ones first after the sort,
    # grouped by expert
    key = jnp.where(held, local, n_held).reshape(-1)             # (T*k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)         # where a pick went
    count = jnp.sum(jax.nn.one_hot(key, n_held, dtype=jnp.int32), axis=0)
    start = jnp.cumsum(count) - count                   # first row of e
    plan = _tile_plan(count, start, tile, T * k // tile + n_held)
    # a held pick's row: its place in its expert's run, from the run's
    # first row among the tiles' rows
    where = (plan[4] - start)[jnp.where(held, local, 0)] + rank.reshape(T, k)
    args = (x, order // k, plan[:4], wg, wu, wd, layer, where, gate, held)
    kernels = functools.partial(_kernels, tile=tile)
    gathers = functools.partial(_gathers, tile=tile)
    if form == "interpret":
        out = kernels(*args)
    elif form == "loop":
        out = gathers(*args)
    else:
        out = lax.platform_dependent(*args, tpu=kernels, default=gathers)
    if form == "interpret" or (form == "kernel"
                               and jax.default_backend() == "tpu"):
        events.incr("moe.grouped_traces")
        events.incr("moe.combine_traces")
    return out


def _kernels(x, token, plan, wg, wu, wd, layer, where, gate, held, *, tile):
    """The TPU's many-token form: `held_experts_grouped`, then
    `held_experts_combine`."""
    rows = held_experts_grouped(x, token, plan, wg, wu, wd, layer, tile=tile)
    return held_experts_combine(rows, where, gate, held)


def _gathers(x, token, plan, wg, wu, wd, layer, where, gate, held, *, tile):
    """The CPU's many-token form and the kernels' oracle:
    `_held_experts_loop`, then the sum over a token's picks, one gather of T
    rows a pick fused with its multiply-add, in pick order from zeros; a
    pick that is not held reads row 0 times a gate of 0.  (Gathered whole,
    (T, k, D) pads k to a sublane tile and is copied, (k, T, D) is widened
    to float32 in a pass of its own: v5e, 8192 tokens x 8, 4.2 and 5.5 ms
    a layer against 3.8.)"""
    rows = _held_experts_loop(x, token, plan, wg, wu, wd, layer, tile=tile)
    where, gate = jnp.where(held, where, 0), jnp.where(held, gate, 0.0)
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(gate.shape[1]):
        out = out + rows[where[:, i]].astype(jnp.float32) * gate[:, i:i + 1]
    return out
