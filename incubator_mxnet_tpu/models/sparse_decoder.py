"""Decoder-only transformer whose attention reads a learned top-k of its
cache and whose feed-forward is a sparse expert layer.

The layer (pre-norm, no biases), h_t the residual stream at position t:

1. x = RMSNorm(h_t).  q, k, v = projections of x, grouped-query (H query
   heads over G key/value heads); q and k take a per-head RMSNorm and
   rotary positions (rotate-half over the whole head).
2. The indexer: queries qi (J small heads), one key ki per position
   (LayerNorm, rotary) and head weights w, all from x.
   I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]); position t keeps the
   `index_topk` causal positions with the largest I, exactly.
3. Softmax attention over the kept positions only; h += Wo o.
4. x2 = RMSNorm(h); the router scores every expert, the top
   `experts_per_token` are kept with renormalised gates, and the experts
   this device holds (`first_held`, `experts_held`) add their terms
   (`parallel.moe.held_experts`); h += that.

Types: the residual stream is float32 from the embedding to the logits;
each block rounds its normed input to the weights' type for its matrix
products, which accumulate in float32; norms, rotary positions, softmax
and index scores are float32, the router's scores true float32 from the
unrounded input; the cache holds K, V and the indexer's key in the
weights' type.

The blocks are `RMSNorm`, `SelectAttention` (projections, q/k norm, the
indexer) and `HeldExperts` (router and the held experts).  A block holds
its parameters for ALL layers, stacked on a leading axis, and the model
scans over that axis: the layers compile as one, whatever their number.

Served through `_ServedDecoder` (docs/serving.md, "A served decoder").
The leaves, slot-major: ``k``, ``v`` (B, layers, G, max_len, d),
head-major so that a step's attention streams each head's rows as they
lie, and the indexer's keys ``ki`` (B, layers, max_len, di).  A step
writes row ``pos[slot]`` of every leaf by an indexed update, scores the
slot's cached indexer keys, selects, and attends over the slot's rows
under the selection (`ops.attention` says why under a mask and not by a
gather).  Rows of a slot past its position hold what an earlier occupant
or the prompt's padding left: a step writes row `pos` before it reads rows
<= `pos`, so they are never read.
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray
from ..telemetry import costs as _costs

__all__ = ["RMSNorm", "SelectAttention", "HeldExperts", "SparseDecoder",
           "rotary"]


def _f32(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _rms(x, g, eps, offset=0.0):
    """RMSNorm over the last axis, computed in float32; the scale is
    `offset + g` (a zero-centred scale is stored as g with offset 1)."""
    import jax
    import jax.numpy as jnp
    xf = _f32(x)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * (offset + _f32(g) if offset else _f32(g))


def rotary(x, pos, theta, width=None):
    """Rotary positions, rotate-half over the first `width` dims of the
    last axis (all of it by default); the others pass.
    x (T, heads, d) float32 at positions pos (T,)."""
    import jax.numpy as jnp
    if width is not None and width < x.shape[-1]:
        return jnp.concatenate(
            [rotary(x[..., :width], pos, theta), x[..., width:]], -1)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _dense(x, w):
    """x (..., in) by w (out, in), accumulated in float32."""
    import jax.numpy as jnp
    return jnp.einsum("...i,oi->...o", x, w,
                      preferred_element_type=jnp.float32)


def _at(tree, layer, whole=()):
    """One layer's slice of a block's stacked parameters, the leaves named
    in `whole` left as they are.  Each leaf is sliced on its leading axis
    where it is used, at the layer's own index: the one form of slice that
    XLA reads in place (a period's slice of several layers, sliced again,
    is copied: 3.2 GB of expert weights a step)."""
    import jax
    return {k: a if k in whole else
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for k, a in tree.items()}


class _Stacked(HybridBlock):
    """A block whose parameters carry a leading axis of layers."""

    def _param(self, name, shape, init=None):
        return self.params.get(name, shape=(self._layers,) + tuple(shape),
                               init=init)

    def stacked(self):
        """{short name: (layers, ...) array}: what a scan over layers
        takes as its `xs`."""
        return {n: getattr(self, n).data()._data for n in self._names}


class RMSNorm(HybridBlock):
    """x * rsqrt(mean(x^2) + eps) * (offset + gamma) over the last axis."""

    def __init__(self, units, eps=1e-6, offset=0.0, **kwargs):
        super().__init__(**kwargs)
        self._eps, self._offset = float(eps), float(offset)
        self.gamma = self.params.get("gamma", shape=(int(units),),
                                     init="zeros" if offset else "ones")

    def forward(self, x):
        g = self.gamma.data()._data
        return NDArray(_rms(x._data, g, self._eps, self._offset)
                       .astype(x._data.dtype))


class SelectAttention(_Stacked):
    """The attention half of every layer: pre-norm, grouped-query
    projections with per-head q/k RMSNorm and rotary positions, and the
    indexer that chooses what each position attends to."""

    _names = ("ln", "wq", "wk", "wv", "wo", "gq", "gk",
              "iwq", "iwk", "iww", "ilg", "ilb")

    def __init__(self, layers, units, num_heads, num_kv_heads, head_dim,
                 index_heads, index_dim, index_topk, rope_theta=1e7,
                 eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads over %d key/value heads"
                             % (num_heads, num_kv_heads))
        self._layers = int(layers)
        self._H, self._G, self._d = num_heads, num_kv_heads, head_dim
        self._J, self._di = index_heads, index_dim
        self._topk = int(index_topk)
        self._theta, self._eps = float(rope_theta), float(eps)
        D = int(units)
        self.ln = self._param("ln", (D,), "ones")
        self.wq = self._param("wq", (num_heads * head_dim, D))
        self.wk = self._param("wk", (num_kv_heads * head_dim, D))
        self.wv = self._param("wv", (num_kv_heads * head_dim, D))
        self.wo = self._param("wo", (D, num_heads * head_dim))
        self.gq = self._param("gq", (head_dim,), "ones")
        self.gk = self._param("gk", (head_dim,), "ones")
        self.iwq = self._param("iwq", (index_heads * index_dim, D))
        self.iwk = self._param("iwk", (index_dim, D))
        self.iww = self._param("iww", (index_heads, D))
        self.ilg = self._param("ilg", (index_dim,), "ones")
        self.ilb = self._param("ilb", (index_dim,), "zeros")

    def project(self, p, h, pos):
        """One layer's projections of h (T, D) at positions pos (T,):
        q (T, H, d), k, v (T, G, d), qi (T, J, di), ki (T, di) in h's
        type, and w (T, J) float32.  `p` is the layer's slice of
        `stacked()`."""
        import jax
        import jax.numpy as jnp
        T, dt = h.shape[0], p["wq"].dtype
        x = _rms(h, p["ln"], self._eps).astype(dt)
        q = _dense(x, p["wq"]).reshape(T, self._H, self._d)
        k = _dense(x, p["wk"]).reshape(T, self._G, self._d)
        v = _dense(x, p["wv"]).reshape(T, self._G, self._d)
        q = rotary(_rms(q, p["gq"], self._eps), pos, self._theta)
        k = rotary(_rms(k, p["gk"], self._eps), pos, self._theta)
        qi = rotary(_dense(x, p["iwq"]).reshape(T, self._J, self._di), pos,
                    self._theta)
        ki = _dense(x, p["iwk"])
        mu = jnp.mean(ki, -1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(
            jnp.mean(jnp.square(ki - mu), -1, keepdims=True) + self._eps) \
            * _f32(p["ilg"]) + _f32(p["ilb"])
        ki = rotary(ki[:, None, :], pos, self._theta)[:, 0]
        return (q.astype(dt), k.astype(dt), v.astype(dt), qi.astype(dt),
                ki.astype(dt), _dense(x, p["iww"]))

    def prompt(self, p, h, block, chunk):
        """One layer over a whole prompt h (T, D), in blocks of `block`
        queries against chunks of `chunk` keys: (h + attention, the rows
        k, v (G, T, d) and ki (T, di) for the cache)."""
        import jax.numpy as jnp
        from ..ops.attention import blocked_select_attention
        T = h.shape[0]
        with _costs.part("proj"):
            q, k, v, qi, ki, w = self.project(p, h, jnp.arange(T))
        o = blocked_select_attention(q, k, v, qi, ki, w, self._topk,
                                     1.0 / math.sqrt(self._d), block, chunk)
        with _costs.part("proj"):
            h = h + _dense(o.reshape(T, -1).astype(k.dtype), p["wo"])
        with _costs.part("cache"):
            return h, k.transpose(1, 0, 2), v.transpose(1, 0, 2), ki

    def step(self, p, h, pos, layer, cache):
        """One layer, one token a slot: h (S, D) at pos (S,).  Writes row
        pos of the layer's cache rows, selects, attends under the
        selection.  Returns (h + attention, cache, selected (S,))."""
        import jax.numpy as jnp
        from ..ops import attention as A
        S = h.shape[0]
        with _costs.part("proj"):
            q, k, v, qi, ki, w = self.project(p, h, pos)
        slots = jnp.arange(S)
        with _costs.part("cache"):
            at = (slots[:, None], layer, jnp.arange(self._G)[None, :],
                  pos[:, None])
            cache = dict(cache, k=cache["k"].at[at].set(k),
                         v=cache["v"].at[at].set(v),
                         ki=cache["ki"].at[slots, layer, pos].set(ki))
        with _costs.part("index"):
            L = cache["ki"].shape[2]
            keys = jnp.take(cache["ki"], layer, axis=1)          # (S, L, di)
            s = jnp.einsum("sjd,sld->sjl", qi, keys,
                           preferred_element_type=jnp.float32)
            score = jnp.einsum("sjl,sj->sl", jnp.maximum(s, 0.0), w)
            causal = jnp.arange(L)[None, :] <= pos[:, None]
            mask = A.select_mask(score, causal, self._topk)
        with _costs.part("attn"):
            o = A.masked_decode_attention(
                q, jnp.take(cache["k"], layer, axis=1),
                jnp.take(cache["v"], layer, axis=1), mask,
                1.0 / math.sqrt(self._d))
        with _costs.part("proj"):
            h = h + _dense(o.reshape(S, -1).astype(k.dtype), p["wo"])
        with _costs.part("index"):
            return h, cache, jnp.sum(mask, -1, dtype=jnp.int32)


class HeldExperts(_Stacked):
    """The expert half of every layer: pre-norm, a router over ALL
    `num_experts`, the top `per_token` with renormalised gates, and the
    SwiGLU experts `first_held .. first_held + held - 1` that this
    device holds.  Terms of experts held elsewhere are left out.  With
    `shared_hidden`, a shared SwiGLU expert of that width is added for
    every token, under a sigmoid gate of its own unless `shared_gate` is
    false: every device holds it whole and computes it alike.  `route`
    takes the place of `moe.topk_route`: (scores (T, E), per_token) ->
    (gate, expert) in its form.  `norm_offset` as `_rms`'s."""

    _names = ("ln", "router", "wg", "wu", "wd")

    def __init__(self, layers, units, hidden, num_experts, per_token,
                 first_held=0, held=None, eps=1e-6, tile=256,
                 shared_hidden=0, norm_offset=0.0, route=None,
                 shared_gate=True, **kwargs):
        super().__init__(**kwargs)
        held = num_experts - first_held if held is None else int(held)
        if not 0 <= first_held <= first_held + held <= num_experts:
            raise ValueError("experts %d..%d are not among %d"
                             % (first_held, first_held + held - 1,
                                num_experts))
        self._layers = int(layers)
        self._k, self._first, self._held = int(per_token), int(first_held), held
        self._experts = int(num_experts)
        self._eps, self._tile = float(eps), int(tile)
        self._offset = float(norm_offset)
        self._route = route
        D, F = int(units), int(hidden)
        self.ln = self._param("ln", (D,), "zeros" if norm_offset else "ones")
        self.router = self._param("router", (num_experts, D))
        self.wg = self._param("wg", (held, F, D))
        self.wu = self._param("wu", (held, F, D))
        self.wd = self._param("wd", (held, D, F))
        if shared_hidden:
            Fs = int(shared_hidden)
            if shared_gate:
                self._names = self._names + ("sgate",)
                self.sgate = self._param("sgate", (1, D))
            self._names = self._names + ("sg", "su", "sd")
            self.sg = self._param("sg", (Fs, D))
            self.su = self._param("su", (Fs, D))
            self.sd = self._param("sd", (D, Fs))

    def _run_tile(self, tokens):
        """Rows of a tile of a held expert's sorted run: three times the
        expert's mean share of `tokens` tokens' picks, as a power of two
        from 16 up to `tile`.  (On the v5e, 1024 tokens x 10 picks over 512
        experts, 20 an expert, a layer by the kernel `held_experts_grouped`:
        tiles of 64 rows 0.56 ms, of 128 0.61, of 32 and of 256 more, where
        the loop over tiles took 1.28 at 64; 8192 x 8 over 128 stays at
        `tile`: 0.91 ms, the same at 128, more at 512.)"""
        run = 16
        while run < 3 * tokens * self._k / self._experts:
            run *= 2
        return min(run, self._tile)

    def apply(self, p, h, layer=None):
        """One layer over tokens h (T, D): (h + the held experts' terms,
        picks held (T,), picks at the fullest held expert (T,)).  `p` is the
        layer's slice of `stacked()`; with `layer`, its expert weights
        `wg`, `wu`, `wd` are the whole stacks and are read at that layer
        (`moe.held_experts`)."""
        with _costs.part("experts"):
            return self._apply(p, h, layer)

    def apply_stacked(self, p, h, layer):
        """`apply` of layer `layer`, `p` the whole `stacked()`: every leaf
        sliced at the layer but the expert weights, which go down whole
        with the layer's index (the many-token form reads one expert at a
        time, at [layer, expert])."""
        return self.apply(_at(p, layer, ("wg", "wu", "wd")), h, layer)

    def _apply(self, p, h, layer):
        from ..parallel import moe
        import jax
        import jax.numpy as jnp
        x = _rms(h, p["ln"], self._eps, self._offset)
        # the router reads the normed state unrounded, in true float32: two
        # experts nearly tied for the last place are common, and rounding
        # that swaps them puts another expert's whole term in the sum
        scores = jnp.einsum("td,ed->te", x, _f32(p["router"]),
                            precision=jax.lax.Precision.HIGHEST)
        gate, expert = (self._route or moe.topk_route)(scores, self._k)
        xw = x.astype(p["wg"].dtype)
        y = moe.held_experts(xw, gate, expert, p["wg"], p["wu"], p["wd"],
                             self._first, tile=self._tile,
                             run_tile=self._run_tile(h.shape[0]),
                             layer=layer)
        if "sgate" in p:
            y = y + jax.nn.sigmoid(_dense(xw, p["sgate"])) \
                * moe.swiglu(xw, p["sg"], p["su"], p["sd"])
        elif "sg" in p:
            y = y + moe.swiglu(xw, p["sg"], p["su"], p["sd"])
        return (h + y,) + moe.held_load(expert, self._first, self._held)


class _ServedDecoder(HybridBlock):
    """What every decoder-only model served by `serving.GenerationEngine`
    shares (docs/serving.md, "A served decoder"): the embedding, the final
    norm and the head (`embed`, `norm`, `head`), `forward`, and the
    engine's contract, `init_cache` and `decode_step`.  A subclass gives
    its mechanism:

    - `step_counts`, the names of the columns of the leaf ``counts``;
    - `_run_prompt(tokens (T,), valid_len ())` -> (h (T, D), ...): the
      prompt through every layer (a model with a recurrence stops it at
      `valid_len`, one of cached rows alone need not read it);
    - `_prompt_leaves(run, valid_len (B,), pad)` -> {name: leaf}: the
      cache's leaves of a batch of prompts from what the vmapped
      `_run_prompt` gave, `pad` padding a leaf's T axis (the one before
      last) out to `max_len`;
    - `_step(tok, pos, leaves, live)` -> (logits (S, V) float32, the
      leaves, counts (S, len(step_counts)) int32): one token a slot
      against the leaves named by `cache_leaves`."""

    def _embed(self, tokens):
        """The residual stream's start.  The stream is float32 from here to
        the logits: each block rounds its normed input to the weights' type
        for its matrix products and adds their float32 results to it."""
        with _costs.part("embed"):
            return _f32(self.embed.data()._data[tokens])

    def _logits(self, h):
        g, w = self.norm.gamma.data()._data, self.head.data()._data
        with _costs.part("head"):
            return _dense(_rms(h, g, self.norm._eps, self.norm._offset)
                          .astype(w.dtype), w)

    def forward(self, tokens):
        """Logits (B, T, V) of `tokens` (B, T)."""
        import jax
        return NDArray(jax.vmap(lambda t: self._logits(
            self._run_prompt(t)[0]))(tokens._data))

    def init_cache(self, prompt, valid_len, max_len, mem_len=None):
        """Prefill `prompt` (B, T), of which `valid_len` (B,) tokens are
        real; `mem_len` is the encoder-memory length of models that have
        one and is not used.  The leaves, ``counts`` (zeros) and the
        stream's start: ``start_tok`` = the prompt's last token,
        ``start_pos`` = its position, which the first step reads again."""
        import jax
        import jax.numpy as jnp
        tokens, n = prompt._data, valid_len._data.reshape(-1)
        B, T = tokens.shape
        if T > int(max_len):
            raise ValueError("a prompt bucket of %d exceeds max_len %d"
                             % (T, max_len))
        run = jax.vmap(self._run_prompt)(tokens, n)
        pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 2)
                                + [(0, int(max_len) - T), (0, 0)])
        last = jnp.maximum(n - 1, 0).astype(jnp.int32)
        with _costs.part("cache"):
            out = dict(self._prompt_leaves(run, n, pad),
                       counts=jnp.zeros((B, len(self.step_counts)),
                                        jnp.int32),
                       start_tok=jnp.take_along_axis(
                           tokens, last[:, None], 1)[:, 0].astype(jnp.int32),
                       start_pos=last)
        return {name: NDArray(a) for name, a in out.items()}

    def decode_step(self, tok, pos, cache, live):
        """Token `tok` (S,) at position `pos` (S,) against the cache:
        (logits (S, V) float32, the cache advanced one token, its
        ``counts`` what the step did for each slot).  `live` (S,; which
        slots hold a stream) is the model's to use or ignore."""
        logits, leaves, counts = self._step(
            tok._data, pos._data,
            {n: cache[n]._data for n in self.cache_leaves}, live._data)
        new = dict(cache)
        new.update({n: NDArray(a) for n, a in leaves.items()})
        new["counts"] = NDArray(counts)
        return NDArray(logits), new

    def step_weight_bytes(self):
        """Bytes of weights one decode step reads once: every parameter but
        the embedding, of which a step reads a row a slot."""
        import numpy as np
        return sum(math.prod(q.shape) * np.dtype(q.dtype).itemsize
                   for q in self.collect_params().values()
                   if q is not self.embed)

    def _weights_share(self, live):
        """KiB of `step_weight_bytes` a live slot's step stands for: the
        weights' bytes shared out among the live slots (S,) bool."""
        import jax.numpy as jnp
        return (self.step_weight_bytes() // 1024) \
            // jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)


class SparseDecoder(_ServedDecoder):
    """Embedding, `num_layers` of (SelectAttention, HeldExperts), a final
    RMSNorm and the output projection over the vocabulary rows held."""

    # what a decode step did for each slot, in the columns of `counts`:
    # positions attended from, positions selected, expert picks, picks of
    # held experts, picks at each layer's fullest held expert
    step_counts = ("gen.attn_context", "gen.attn_selected", "moe.picks",
                   "moe.picks_held", "moe.expert_max")
    cache_leaves = ("k", "v", "ki")

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, expert_hidden, num_experts,
                 experts_per_token, index_heads, index_dim, index_topk,
                 first_held=0, experts_held=None, rope_theta=1e7, eps=1e-6,
                 query_block=1024, key_chunk=512, expert_tile=256,
                 **kwargs):
        super().__init__(**kwargs)
        self._layers, self._units = int(num_layers), int(units)
        self._per_token = int(experts_per_token)
        self._block, self._chunk = int(query_block), int(key_chunk)
        self.embed = self.params.get("embed", shape=(vocab_size, units))
        self.attn = SelectAttention(
            num_layers, units, num_heads, num_kv_heads, head_dim,
            index_heads, index_dim, index_topk, rope_theta, eps)
        self.experts = HeldExperts(
            num_layers, units, expert_hidden, num_experts,
            experts_per_token, first_held, experts_held, eps, expert_tile)
        self.norm = RMSNorm(units, eps)
        self.head = self.params.get("head", shape=(vocab_size, units))

    def _stacks(self):
        return {"attn": self.attn.stacked(), "experts": self.experts.stacked()}

    def _run_prompt(self, tokens, valid_len=None):
        """tokens (T,) -> (h (T, D), (k, v (layers, G, T, d), ki (layers,
        T, di)))."""
        import jax
        import jax.numpy as jnp
        stacks = self._stacks()
        # the expert weights go down whole with the layer's index: the
        # many-token form reads one expert at a time, at [layer, expert],
        # and a layer's slice handed to its kernel would be a copy
        whole = {n: stacks["experts"].pop(n) for n in ("wg", "wu", "wd")}

        def layer(h, xs):
            p, i = xs
            h, k, v, ki = self.attn.prompt(p["attn"], h, self._block,
                                           self._chunk)
            h = self.experts.apply(dict(p["experts"], **whole), h, i)[0]
            return h, (k, v, ki)

        return jax.lax.scan(layer, self._embed(tokens),
                            (stacks, jnp.arange(self._layers)))

    def _prompt_leaves(self, run, valid_len, pad):
        _, (k, v, ki) = run
        return {"k": pad(k), "v": pad(v), "ki": pad(ki)}

    def _step(self, tok, pos, leaves, live):
        """Row `pos` written; `live` is not used: the attention streams
        every slot under the selection's mask."""
        import jax
        import jax.numpy as jnp
        S = tok.shape[0]
        zero = jnp.zeros((S,), jnp.int32)

        def layer(carry, xs):
            h, leaves, sel, held, full = carry
            p, i = xs
            h, leaves, s = self.attn.step(p["attn"], h, pos, i, leaves)
            h, n_held, n_full = self.experts.apply(p["experts"], h)
            return (h, leaves, sel + s, held + n_held, full + n_full), None

        (h, leaves, sel, held, full), _ = jax.lax.scan(
            layer, (self._embed(tok), leaves, zero, zero, zero),
            (self._stacks(), jnp.arange(self._layers)))
        with _costs.part("cache"):
            counts = jnp.stack(
                [self._layers * (pos + 1), sel,
                 jnp.full((S,), self._layers * self._per_token, jnp.int32),
                 held, full], axis=1).astype(jnp.int32)
        return self._logits(h), leaves, counts
