"""Decoder-only transformer whose attention layers are of two kinds, each
with a head count of its own: FULL layers attend over every earlier
position, WINDOW layers over the last `window` positions alone.  Leading
dense layers, then sparse expert layers (`sparse_decoder.HeldExperts`: a
router over all experts, the top k renormalised and scaled, the experts
this device holds, an un-gated shared expert).

Pre-norm, no biases; N(x; w) = w x / sqrt(mean(x^2) + eps).  h is the
residual stream, G key/value heads of d, H_l query heads in layer l.

    x = N(h; ln_l)
    q = Wq x (H_l heads), k = Wk x, v = Wv x (G heads), g = sigmoid(Wgate x)
      (one gate a query head)
    q, k take rotary positions, rotate-half over the first dims of a head
      (`rope_frequencies`: a kind's own theta, YaRN frequencies and scale,
      partial width); the other dims pass
    o_i = softmax_j(q_i . k_j / sqrt(d)) v_j over j <= i (full) or
      i - window < j <= i (window); query head h reads head h // (H_l / G)
    h = h + Wo (g_h o_h, a head)
    m = N(h; ln2_l)
    dense layers: h = h + SwiGLU(m);  sparse: h = h + the held experts'
      gated terms + the shared expert's

Types as in `sparse_decoder`: the residual stream float32, every block
rounding its normed input to the weights' type for its matrix products,
which accumulate in float32; norms, rotary positions, gates, softmax and
the router float32; the cached rows in the weights' type.

`KindAttention` holds the parameters of ALL layers of one kind stacked on a
leading axis.  The leading dense layers run one by one, then the model
scans over PERIODS of the pattern (`layer_types` after the dense layers
must repeat); a layer slices each stacked leaf at its own index where it
is used (`_at`: a period's slice sliced again is a copy).

Served through `sparse_decoder._ServedDecoder` (docs/serving.md, "A
served decoder"), with two kinds of K/V leaves in one slot, slot-major and
head-major: ``kf``, ``vf`` (B, full layers, G, max_len, d), the full
layers' rows at their positions, and ``kw``, ``vw`` (B, window layers, G,
window, d), RINGS: the row of position t lies at t mod window.  A prefill
of a prompt padded to its bucket hands over the rows of positions
[max(0, valid_len - window), valid_len) at their ring index, never the
bucket's padding.  A step writes row ``pos`` of the full leaves and row
``pos mod window`` of the rings in place
(`ops.attention.decode_rows_write`), then reads the full layers' rows
<= ``pos`` and the ring's rows that the slot has filled (ring index
<= ``pos``: all of them once pos >= window - 1) of each live slot, up to
those lengths (`ops.attention.grouped_decode_attention`).
"""
from __future__ import annotations

import functools
import math

import numpy as np

from ..telemetry import costs as _costs
from .latent_decoder import DenseSwiGLU, yarn_inv_freq, yarn_mscale
from .sparse_decoder import (HeldExperts, RMSNorm, _ServedDecoder, _Stacked,
                             _at, _dense, _rms)

__all__ = ["KindAttention", "WindowDecoder", "rope_frequencies"]

FULL, WINDOW = "full_attention", "sliding_attention"


def rope_frequencies(params, head_dim):
    """One kind's rotary positions from its published `rope_parameters`:
    (inverse frequencies (rotated dims / 2,) float32, the scale of cos and
    sin).  The rotated dims are `partial_rotary_factor` of the head; a
    `yarn` kind takes YaRN's frequencies (`yarn_inv_freq` over the rotated
    dims) and its `attention_factor` (m(1) = 0.1 ln(factor) + 1 where the
    parameters give none)."""
    rot = int(round(head_dim * float(params.get("partial_rotary_factor",
                                                1.0))))
    theta = float(params["rope_theta"])
    kind = params.get("rope_type", "default")
    if kind == "default":
        return yarn_inv_freq(rot, theta, 1.0, 0, 32, 1), 1.0
    if kind != "yarn":
        raise ValueError("rope_type %r is neither default nor yarn" % kind)
    factor = float(params["factor"])
    inv = yarn_inv_freq(rot, theta, factor,
                        params["original_max_position_embeddings"],
                        params.get("beta_fast", 32), params.get("beta_slow", 1))
    return inv, float(params.get("attention_factor",
                                 yarn_mscale(factor, 1.0)))


def _rotate_half(x, pos, inv_freq, scale):
    """x (T, heads, d) float32 at positions pos (T,): pair (i, i + r/2) of
    the first r = 2 len(inv_freq) dims turns by pos * inv_freq[i], cos and
    sin times `scale`; dims r.. pass."""
    import jax.numpy as jnp
    r = 2 * len(inv_freq)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * scale
    a = x[..., :r]
    turned = a * cos + jnp.concatenate([-a[..., r // 2:], a[..., :r // 2]],
                                       -1) * sin
    return turned if r == x.shape[-1] else \
        jnp.concatenate([turned, x[..., r:]], -1)


class KindAttention(_Stacked):
    """The attention half of the layers of one kind: pre-norm, grouped-query
    projections, a sigmoid gate a query head, rotary positions, softmax
    attention causal (`window` None) or under a band of `window` keys, the
    gated heads' output projection.  A window kind's work is part
    `window`, a full kind's `attn`."""

    _names = ("ln", "wq", "wk", "wv", "wgate", "wo")

    def __init__(self, layers, units, num_heads, num_kv_heads, head_dim,
                 inv_freq, rotary_scale=1.0, window=None, eps=1e-6,
                 query_block=512, key_chunk=512, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads over %d key/value heads"
                             % (num_heads, num_kv_heads))
        self._layers = int(layers)
        self._H, self._G, self._d = int(num_heads), int(num_kv_heads), \
            int(head_dim)
        self._inv, self._rot_scale = np.asarray(inv_freq, np.float32), \
            float(rotary_scale)
        self.window = None if window is None else int(window)
        self.part = "attn" if window is None else "window"
        self._eps = float(eps)
        self._block, self._chunk = int(query_block), int(key_chunk)
        self.scale = 1.0 / math.sqrt(self._d)
        D, Hd = int(units), self._H * self._d
        self.ln = self._param("ln", (D,), "ones")
        self.wq = self._param("wq", (Hd, D))
        self.wk = self._param("wk", (self._G * self._d, D))
        self.wv = self._param("wv", (self._G * self._d, D))
        self.wgate = self._param("wgate", (self._H, D))
        self.wo = self._param("wo", (D, Hd))

    def project(self, p, h, pos):
        """One layer's projections of h (T, D) at positions pos (T,): q
        (T, H, d), k, v (T, G, d) in the weights' type and the gate (T, H)
        float32.  `p` is the layer's slice of `stacked()`."""
        import jax
        T, dt = h.shape[0], p["wq"].dtype
        x = _rms(h, p["ln"], self._eps).astype(dt)
        rot = functools.partial(_rotate_half, pos=pos, inv_freq=self._inv,
                                scale=self._rot_scale)
        q = rot(_dense(x, p["wq"]).reshape(T, self._H, self._d))
        k = rot(_dense(x, p["wk"]).reshape(T, self._G, self._d))
        v = _dense(x, p["wv"]).reshape(T, self._G, self._d)
        gate = jax.nn.sigmoid(_dense(x, p["wgate"]))
        return q.astype(dt), k.astype(dt), v.astype(dt), gate

    def _out(self, p, h, o, gate):
        with _costs.part("proj"):
            o = (o * gate[..., None]).reshape(h.shape[0], -1)
            return h + _dense(o.astype(p["wo"].dtype), p["wo"])

    def prompt(self, p, h):
        """One layer over a whole prompt h (T, D): (h + attention, the rows
        k, v (G, T, d))."""
        import jax.numpy as jnp
        from ..ops.attention import blocked_window_attention
        T = h.shape[0]
        with _costs.part("proj"):
            q, k, v, gate = self.project(p, h, jnp.arange(T))
        o = blocked_window_attention(q, k, v, self.scale, self.window,
                                     self._block, self._chunk, self.part)
        h = self._out(p, h, o, gate)
        with _costs.part("cache"):
            return h, k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def rows(self, pos, live):
        """(S,) the rows [0, n) of a slot's leaf that its step reads: pos + 1,
        at most `window` in a ring, 0 where the slot is not live."""
        import jax.numpy as jnp
        # ring index j holds position pos - ((pos - j) mod window): filled
        # by this slot where j <= pos
        n = pos + 1 if self.window is None else jnp.minimum(pos + 1,
                                                            self.window)
        return jnp.where(live, n, 0).astype(jnp.int32)

    def step(self, p, h, pos, live, layer, k_leaf, v_leaf):
        """One layer, one token a slot: h (S, D) at pos (S,).  Writes the
        row of pos (at pos mod window in a ring) into the stacked leaves
        k_leaf, v_leaf (S, layers, G, rows, d) at `layer`, then attends
        over the rows of them that a live slot has filled (`rows`).
        Returns (h + attention, the two leaves)."""
        from ..ops.attention import decode_rows_write, grouped_decode_attention
        with _costs.part("proj"):
            q, k, v, gate = self.project(p, h, pos)
        at = pos if self.window is None else pos % self.window
        k_leaf, v_leaf = decode_rows_write(k_leaf, v_leaf, k, v, layer, at)
        o = grouped_decode_attention(q, k_leaf, v_leaf, layer,
                                     self.rows(pos, live), self.scale,
                                     self.part)
        return self._out(p, h, o, gate), k_leaf, v_leaf


def _pattern(layer_types, mlp_layer_types, heads):
    """(leading dense layers, period) of a layer pattern, or ValueError:
    the dense layers lead, every later layer is sparse, and the kinds and
    head counts after the dense layers repeat, two periods or more; each
    kind has one head count."""
    L = len(layer_types)
    if len(mlp_layer_types) != L or len(heads) != L:
        raise ValueError("%d layer types, %d mlp layer types, %d head counts"
                         % (L, len(mlp_layer_types), len(heads)))
    if set(layer_types) - {FULL, WINDOW}:
        raise ValueError("layer types %s are not %s or %s"
                         % (sorted(set(layer_types) - {FULL, WINDOW}), FULL,
                            WINDOW))
    lead = 0
    while lead < L and mlp_layer_types[lead] == "dense":
        lead += 1
    if lead == 0 or set(mlp_layer_types[lead:]) != {"sparse"}:
        raise ValueError("mlp layer types %s are not dense layers before "
                         "sparse ones" % list(mlp_layer_types))
    for kind in (FULL, WINDOW):
        if len({n for t, n in zip(layer_types, heads) if t == kind}) != 1:
            raise ValueError("the %s layers have head counts %s: one count "
                             "a kind" % (kind, sorted(
                                 {n for t, n in zip(layer_types, heads)
                                  if t == kind})))
    rest = list(layer_types[lead:])
    period = next(p for p in range(1, len(rest) + 1)
                  if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p))
    if len(rest) // period < 2:
        raise ValueError("the %d layers after %d dense ones, %s, are not two "
                         "or more periods of one pattern"
                         % (len(rest), lead, rest))
    return lead, period


class WindowDecoder(_ServedDecoder):
    """Embedding, layers whose attention is `KindAttention` of their kind
    (`layer_types`: full or sliding, with `heads_per_layer` query heads)
    and whose feed-forward is `DenseSwiGLU` (the leading `dense` layers of
    `mlp_layer_types`) or `HeldExperts` (top `experts_per_token` of
    `num_experts`, renormalised, times `routed_scale`, with an un-gated
    shared expert), a final RMSNorm and the output projection over the
    vocabulary rows held.  `rope_parameters` gives each kind's rotary
    positions under its published key (`full_attention`,
    `sliding_attention`)."""

    # what a decode step did for each slot, in the columns of `counts`: rows
    # the full layers attended from, and the rows their attention covered
    # (`grouped_rows_read`); the window layers' rows in the band
    # (min(pos + 1, window) a layer) and the rows their attention read (the
    # ring's); KiB of cache the step needs moved, the same plus the slot's
    # share of the weights (read once a step); expert picks, picks of held
    # experts, picks at each layer's fullest held expert
    step_counts = ("gen.attn_context", "gen.attn_rows_read",
                   "window.rows_needed", "window.rows_read", "gen.cache_kib",
                   "gen.step_kib", "moe.picks", "moe.picks_held",
                   "moe.expert_max")
    cache_leaves = ("kf", "vf", "kw", "vw")

    def __init__(self, vocab_size, units, layer_types, mlp_layer_types,
                 heads_per_layer, num_kv_heads, head_dim, window,
                 dense_hidden, expert_hidden, num_experts, experts_per_token,
                 rope_parameters, shared_hidden=0, routed_scale=1.0,
                 first_held=0, experts_held=None, eps=1e-6, query_block=512,
                 key_chunk=512, expert_tile=256, **kwargs):
        from ..parallel import moe
        super().__init__(**kwargs)
        types = list(layer_types)
        self._lead, self._period = _pattern(types, list(mlp_layer_types),
                                            list(heads_per_layer))
        self._layers = len(types)
        self._periods = (self._layers - self._lead) // self._period
        self._per_token = int(experts_per_token)
        heads = dict(zip(types, heads_per_layer))
        # (kind, index in the kind's stack) of each leading layer, and of
        # each layer of a period as an offset from the period's first index
        seen = {FULL: 0, WINDOW: 0}
        self._lead_at = []
        for kind in types[:self._lead]:
            self._lead_at.append((kind, seen[kind]))
            seen[kind] += 1
        self._first = dict(seen)
        per = {FULL: 0, WINDOW: 0}
        self._pattern_at = []
        for kind in types[self._lead:self._lead + self._period]:
            self._pattern_at.append((kind, per[kind]))
            per[kind] += 1
        self._per = per
        count = {k: self._first[k] + self._periods * per[k] for k in per}
        self.window = int(window)
        blocks = {}
        for kind, band in ((FULL, None), (WINDOW, self.window)):
            inv, scale = rope_frequencies(rope_parameters[kind], head_dim)
            blocks[kind] = KindAttention(
                count[kind], units, heads[kind], num_kv_heads, head_dim, inv,
                scale, band, eps, query_block, key_chunk)
        self.full, self.sliding = blocks[FULL], blocks[WINDOW]
        self.embed = self.params.get("embed", shape=(vocab_size, units))
        self.ffn = DenseSwiGLU(self._lead, units, dense_hidden, eps)
        self.experts = HeldExperts(
            self._layers - self._lead, units, expert_hidden, num_experts,
            experts_per_token, first_held, experts_held, eps, expert_tile,
            shared_hidden, route=functools.partial(
                moe.topk_route, scale=float(routed_scale)),
            shared_gate=False)
        self.norm = RMSNorm(units, eps)
        self.head = self.params.get("head", shape=(vocab_size, units))

    def _attn(self, kind):
        return self.full if kind == FULL else self.sliding

    def _stacks(self):
        return {FULL: self.full.stacked(), WINDOW: self.sliding.stacked(),
                "ffn": self.ffn.stacked(), "experts": self.experts.stacked()}

    def _run_prompt(self, tokens, valid_len=None):
        """tokens (T,) -> (h (T, D), {kind: (k, v) (kind's layers, G, T,
        d)})."""
        import jax
        import jax.numpy as jnp
        p, h = self._stacks(), self._embed(tokens)
        rows = {FULL: [], WINDOW: []}
        for j, (kind, n) in enumerate(self._lead_at):
            h, k, v = self._attn(kind).prompt(_at(p[kind], n), h)
            h = self.ffn.apply(_at(p["ffn"], j), h)
            rows[kind].append((k[None], v[None]))

        def period(h, i):
            out = {FULL: [], WINDOW: []}
            for j, (kind, off) in enumerate(self._pattern_at):
                n = self._first[kind] + i * self._per[kind] + off
                h, k, v = self._attn(kind).prompt(_at(p[kind], n), h)
                h = self.experts.apply_stacked(
                    p["experts"], h, i * self._period + j)[0]
                out[kind].append((k, v))
            return h, {kind: tuple(jnp.stack(a) for a in zip(*kv))
                       for kind, kv in out.items() if kv}

        h, scanned = jax.lax.scan(period, h, jnp.arange(self._periods))
        # a kind's rows in the order of its stack: the leading layers',
        # then period i's at first + i * per + offset
        for kind, (ks, vs) in scanned.items():
            rows[kind].append((ks.reshape((-1,) + ks.shape[2:]),
                               vs.reshape((-1,) + vs.shape[2:])))
        return h, {kind: tuple(jnp.concatenate(parts) for parts in zip(*kv))
                   for kind, kv in rows.items()}

    def _ring(self, rows, n):
        """A window layer's rows (layers, G, T, d) of a prompt with n valid
        positions -> its ring (layers, G, window, d): ring index j holds
        position n - window + ((j - n) mod window), the latest valid one
        congruent to j; zeros where that is below 0."""
        import jax.numpy as jnp
        W, T = self.window, rows.shape[2]
        j = jnp.arange(W)
        at = n - W + jnp.mod(j - n, W)
        got = jnp.take(rows, jnp.clip(at, 0, T - 1), axis=2)
        return jnp.where((at >= 0)[None, None, :, None], got,
                         jnp.zeros((), rows.dtype))

    def _prompt_leaves(self, run, valid_len, pad):
        import jax
        _, rows = run                                   # (B, layers, G, T, d)
        ring = jax.vmap(self._ring)
        return {"kf": pad(rows[FULL][0]), "vf": pad(rows[FULL][1]),
                "kw": ring(rows[WINDOW][0], valid_len),
                "vw": ring(rows[WINDOW][1], valid_len)}

    def _step(self, tok, pos, leaves, live):
        """The rows of `pos` written.  `live` shares the weights' bytes out
        among `counts`; the attention reads no row of a slot that is not
        live."""
        import jax
        import jax.numpy as jnp
        from ..ops.attention import grouped_rows_read
        names = {FULL: ("kf", "vf"), WINDOW: ("kw", "vw")}
        S = tok.shape[0]
        zero = jnp.zeros((S,), jnp.int32)
        p, h = self._stacks(), self._embed(tok)

        def layer(h, leaves, kind, n):
            kn, vn = names[kind]
            h, k_leaf, v_leaf = self._attn(kind).step(
                _at(p[kind], n), h, pos, live, n, leaves[kn], leaves[vn])
            return h, dict(leaves, **{kn: k_leaf, vn: v_leaf})

        for j, (kind, n) in enumerate(self._lead_at):
            h, leaves = layer(h, leaves, kind, n)
            h = self.ffn.apply(_at(p["ffn"], j), h)

        def period(carry, i):
            h, leaves, held, full = carry
            for j, (kind, off) in enumerate(self._pattern_at):
                h, leaves = layer(h, leaves, kind, self._first[kind]
                                  + i * self._per[kind] + off)
                h, n_held, n_full = self.experts.apply_stacked(
                    p["experts"], h, i * self._period + j)
                held, full = held + n_held, full + n_full
            return (h, leaves, held, full), None

        (h, leaves, held, full), _ = jax.lax.scan(
            period, (h, leaves, zero, zero), jnp.arange(self._periods))
        NF, NW, W = leaves["kf"].shape[1], leaves["kw"].shape[1], self.window
        # the rows a window layer's attention was handed: the ring's, or
        # max_len were a leaf of full rows given it
        read = leaves["kw"].shape[3]
        k = leaves["kf"]
        # one position's K and V rows of one layer, bytes
        row = 2 * k.shape[2] * k.shape[4] * k.dtype.itemsize
        band = jnp.minimum(pos + 1, W)
        with _costs.part("cache"):
            # a step reads rows 0..pos of the full leaves and the band of
            # the rings, and writes one row of each, in every layer
            cache_kib = (NF * (pos + 2) + NW * (band + 1)) * row // 1024
            share = self._weights_share(live)
            counts = jnp.stack(
                [NF * (pos + 1),
                 NF * grouped_rows_read(self.full.rows(pos, live), k),
                 NW * band, jnp.full((S,), NW * read, jnp.int32),
                 cache_kib, cache_kib + jnp.where(live, share, 0),
                 jnp.full((S,), (self._layers - self._lead)
                          * self._per_token, jnp.int32),
                 held, full], axis=1).astype(jnp.int32)
        return self._logits(h), leaves, counts
