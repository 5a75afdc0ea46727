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
exchange.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["switch_route", "moe_apply", "moe_ffn", "topk_route",
           "swiglu", "held_experts", "held_load"]


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

def topk_route(router_logits, k):
    """Softmax over all experts, the k largest, renormalised over those
    k.  router_logits (T, E) -> (gate (T, k) float32, expert (T, k)
    int32), best first, ties to the lower expert."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top, expert = lax.top_k(probs, k)
    return top / jnp.sum(top, axis=-1, keepdims=True), expert


def held_load(expert, first_held, n_held):
    """What routing asks of the held experts: (picks (T,) the number of a
    token's k experts that are held, at_fullest (T,) how many of them are
    the held expert with the most tokens).  Their sums over tokens are
    the held picks and the fullest held expert's tokens."""
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
    need and no more, so the work follows the picks, however uneven."""
    T, D = x.shape
    k = gate.shape[1]
    if layer is None:
        of = lambda w, e: w[e]
    else:
        of = lambda w, e: w[layer, e]
    n_held = wg.shape[0 if layer is None else 1]
    f32 = jnp.float32
    local = expert - first_held
    held = (local >= 0) & (local < n_held)
    if T <= tile:
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
    # picks in token order, flattened; held ones first after the sort,
    # grouped by expert
    key = jnp.where(held, local, n_held).reshape(-1)             # (T*k,)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank = jnp.argsort(order).astype(jnp.int32)         # where a pick went
    count = jnp.sum(jax.nn.one_hot(key, n_held, dtype=jnp.int32), axis=0)
    start = jnp.cumsum(count) - count                   # first row of e
    tiles = (count + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)                        # tiles up to e
    order_pad = jnp.concatenate([order, jnp.zeros((tile,), jnp.int32)])
    # the results, one row a held pick in sorted order (a run's last tile
    # overhangs into the next run, whose own tile then overwrites it)
    rows = jnp.zeros((T * k + tile, D), x.dtype)

    def one_tile(j, rows):
        e = jnp.sum(tile_end <= j).astype(jnp.int32)    # whose tile j is
        base = start[e] + (j - (tile_end[e] - tiles[e])) * tile
        picks = lax.dynamic_slice(order_pad, (base,), (tile,))
        y = swiglu(x[picks // k], of(wg, e), of(wu, e), of(wd, e))
        return lax.dynamic_update_slice(rows, y.astype(rows.dtype),
                                        (base, 0))

    rows = lax.fori_loop(0, tile_end[-1], one_tile, rows)
    mine = rows[jnp.where(held, rank.reshape(T, k), 0)]          # (T, k, D)
    return jnp.einsum("tkd,tk->td", mine, jnp.where(held, gate, 0.0),
                      preferred_element_type=f32)
