"""Decoder-only transformer whose attention caches ONE compressed row a
position in place of every head's key and value (multi-head latent
attention), with leading dense layers and then sparse expert layers routed
inside a few groups of experts, beside shared experts that take every
token (`sparse_decoder.HeldExperts`).

Pre-norm, no biases; N(x; w) = w x / sqrt(mean(x^2) + eps).  h_t is the
residual stream at position t, H heads, a head's query and key are `nope`
dims without a position and `rope` dims with one, its value `v` dims.

**Latent attention** (`LatentAttention`).  x = N(h_t).
c_q = N(Wqa x) (`q_rank`); a head's query [q_n, q_r] = Wqb c_q.
c = N(Wkc x) (`kv_rank`) and k_r = Wkr x (`rope` dims, ONE for all heads);
q_r and k_r take rotary positions (`yarn_inv_freq`, pairs (2i, 2i + 1)).
A head's key is [Wkn c, k_r], its value Wv c.  Causal softmax of
(q_n . k_n + q_r . k_r) * scale; h += Wo [o_1 .. o_H].

The cache holds c and k_r, `kv_rank + rope` values a position a layer, and
nothing else.  The two forms of the same attention:

- **expanded** (`prompt`): k_n = Wkn c and v = Wv c are formed once for the
  prompt's rows, and attention is causal multi-head attention
  (`ops.attention.latent_prefill_attention`: a Pallas flash kernel where
  the prefill is lowered for a TPU, `blocked_causal_attention`'s blocks
  elsewhere).
- **absorbed** (`step`): q~ = Wkn^T q_n a head, scores q~ . c + q_r . k_r
  over the cached rows, u = sum p c in the latent space, o = Wv u
  (`ops.attention.latent_decode_attention`: a Pallas kernel over the
  leaves as they lie where the step is lowered for a TPU, two einsums
  elsewhere).  Equal to the expanded form by associativity; no head's keys
  or values are formed from cached rows.

Types as in `sparse_decoder`: the residual stream float32, every block
rounding its normed input to the weights' type for its matrix products,
which accumulate in float32; norms, rotary positions, softmax and the
router float32; the cached rows in the weights' type.

A block holds its parameters for all of ITS layers stacked on a leading
axis.  The leading dense layers run one by one, then the model scans over
the expert layers; every leaf is sliced where it is used (`_at`).

Serving contract (`serving.GenerationEngine`), as `SparseDecoder`'s:
``init_cache`` returns, slot-major, ``ckv`` (B, layers, max_len, kv_rank)
and ``kr`` (B, layers, max_len, rope), no head axis, ``counts`` and the
stream's start (the prompt's last token and its position, read again by
the first step, which rewrites that row with what it held).
``decode_step`` writes row ``pos[slot]`` of both leaves in place and reads
rows <= ``pos``.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from ..gluon.block import HybridBlock
from ..monitor import events
from ..ndarray.ndarray import NDArray
from ..telemetry import costs as _costs
from .sparse_decoder import (HeldExperts, RMSNorm, _Stacked, _at, _dense,
                             _f32, _rms)

__all__ = ["LatentAttention", "DenseSwiGLU", "LatentDecoder",
           "yarn_inv_freq", "yarn_mscale"]


def yarn_mscale(factor, mscale):
    """YaRN's magnitude correction m(s) = 0.1 s ln(factor) + 1 (1 where the
    context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The `dim / 2` inverse frequencies of YaRN rotary positions over a
    head of `dim` (plain rotary positions at `factor` 1): a frequency that
    turns more than `beta_fast` times in the original context is kept
    (extrapolated), one that turns fewer than `beta_slow` times is divided
    by `factor` (interpolated), and between them a linear ramp over the
    frequency's index mixes the two."""
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    if factor <= 1:
        return plain.astype(np.float32)
    turns_at = lambda n: dim * math.log(original_max / (n * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rotary_pairs(x, pos, inv_freq, mscale):
    """Rotary positions on pairs (2i, 2i + 1) of the last axis: x
    (T, ..., d) float32 at positions pos (T,).  Pair i turns by
    pos * inv_freq[i]; the result lies evens first, then odds (every
    query and key alike, so their products do not see the order)."""
    import jax.numpy as jnp
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           -1)


class LatentAttention(_Stacked):
    """The attention half of every layer: pre-norm, the low-rank query, the
    latent row and the shared rotary key, in the expanded form for a prompt
    and the absorbed form for a step."""

    _names = ("ln", "wqa", "gq", "wqb", "wkc", "wkr", "gkv", "wkn", "wv",
              "wo")

    def __init__(self, layers, units, num_heads, q_rank, kv_rank, nope_dim,
                 rope_dim, v_dim, rope_theta=1e4, rope_scaling=None,
                 eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._layers = int(layers)
        self._H, self._dn, self._dr, self._dv = (int(num_heads),
                                                 int(nope_dim), int(rope_dim),
                                                 int(v_dim))
        self._eps = float(eps)
        # no `rope_scaling`: factor 1, plain rotary positions and scale.
        # cos and sin carry m(mscale) / m(mscale_all_dim), the softmax
        # scale m(mscale_all_dim)^2
        rs = rope_scaling or {}
        factor = rs.get("factor", 1.0)
        self.inv_freq = yarn_inv_freq(
            self._dr, float(rope_theta), factor,
            rs.get("original_max_position_embeddings", 0),
            rs.get("beta_fast", 32), rs.get("beta_slow", 1))
        all_dim = yarn_mscale(factor, rs.get("mscale_all_dim", 0))
        self._rot_scale = yarn_mscale(factor, rs.get("mscale", 1)) / all_dim
        self.scale = (self._dn + self._dr) ** -0.5 * all_dim * all_dim
        D, H = int(units), self._H
        self.ln = self._param("ln", (D,), "ones")
        self.wqa = self._param("wqa", (q_rank, D))
        self.gq = self._param("gq", (q_rank,), "ones")
        self.wqb = self._param("wqb", (H * (self._dn + self._dr), q_rank))
        self.wkc = self._param("wkc", (kv_rank, D))
        self.wkr = self._param("wkr", (self._dr, D))
        self.gkv = self._param("gkv", (kv_rank,), "ones")
        self.wkn = self._param("wkn", (H, self._dn, kv_rank))
        self.wv = self._param("wv", (H, self._dv, kv_rank))
        self.wo = self._param("wo", (D, H * self._dv))

    def project(self, p, h, pos):
        """One layer's projections of h (T, D) at positions pos (T,): the
        queries q_n (T, H, nope) and q_r (T, H, rope), the latent row c
        (T, kv_rank) and the shared rotary key k_r (T, rope), all in the
        weights' type."""
        T, dt = h.shape[0], p["wqa"].dtype
        rot = functools.partial(_rotary_pairs, pos=pos,
                                inv_freq=self.inv_freq,
                                mscale=self._rot_scale)
        x = _rms(h, p["ln"], self._eps).astype(dt)
        cq = _rms(_dense(x, p["wqa"]), p["gq"], self._eps).astype(dt)
        q = _dense(cq, p["wqb"]).reshape(T, self._H, self._dn + self._dr)
        c = _rms(_dense(x, p["wkc"]), p["gkv"], self._eps)
        return (q[..., :self._dn].astype(dt),
                rot(q[..., self._dn:]).astype(dt), c.astype(dt),
                rot(_dense(x, p["wkr"])).astype(dt))

    def _out(self, p, h, o):
        with _costs.part("proj"):
            return h + _dense(
                o.reshape(h.shape[0], -1).astype(p["wo"].dtype), p["wo"])

    def prompt(self, p, h, block, chunk):
        """One layer over a whole prompt h (T, D), expanded: (h +
        attention, the rows c (T, kv_rank) and k_r (T, rope) for the
        cache).  Every head's 128-wide parts come out of their products
        side by side, (T, H * d), and the rotary queries head-major: as the
        attention's kernel reads them, with no copy between
        (`ops.attention.latent_prefill_attention`).  The products and
        roundings are `project`'s."""
        import jax
        import jax.numpy as jnp
        from ..ops.attention import latent_prefill_attention
        H, dn, dt = self._H, self._dn, p["wqa"].dtype
        rot = functools.partial(_rotary_pairs, pos=jnp.arange(h.shape[0]),
                                inv_freq=self.inv_freq,
                                mscale=self._rot_scale)
        flat = lambda w: w.reshape(-1, w.shape[-1])
        with _costs.part("proj"):
            x = _rms(h, p["ln"], self._eps).astype(dt)
            cq = _rms(_dense(x, p["wqa"]), p["gq"], self._eps).astype(dt)
            c = _rms(_dense(x, p["wkc"]), p["gkv"], self._eps).astype(dt)
            kr = rot(_dense(x, p["wkr"])).astype(dt)
            wq = p["wqb"].reshape(H, dn + self._dr, -1)
            qn = _dense(cq, flat(wq[:, :dn])).astype(dt)
            qr = jax.vmap(rot)(jnp.einsum(
                "tr,hdr->htd", cq, wq[:, dn:],
                preferred_element_type=jnp.float32)).astype(dt)
            kn = _dense(c, flat(p["wkn"])).astype(dt)
            v = _dense(c, flat(p["wv"])).astype(dt)
        o = latent_prefill_attention(qn, qr, kn, kr, v, self.scale, block,
                                     chunk)
        return self._out(p, h, o), c, kr

    def step(self, p, h, pos, layer, cache):
        """One layer, one token a slot, absorbed: h (S, D) at pos (S,).
        Writes row pos of the layer's latent rows and attends over rows
        <= pos.  The attention takes the leaves whole with the layer's
        index: a layer's slice in front of a kernel would be copied."""
        import jax.numpy as jnp
        from ..ops.attention import latent_decode_attention
        f32 = jnp.float32
        with _costs.part("proj"):
            qn, qr, c, kr = self.project(p, h, pos)
        with _costs.part("cache"):
            slots = jnp.arange(h.shape[0])
            cache = dict(cache,
                         ckv=cache["ckv"].at[slots, layer, pos].set(c),
                         kr=cache["kr"].at[slots, layer, pos].set(kr))
        with _costs.part("proj"):
            q_abs = jnp.einsum("shn,hnc->shc", qn, p["wkn"],
                               preferred_element_type=f32).astype(c.dtype)
        u = latent_decode_attention(q_abs, qr, cache["ckv"], cache["kr"],
                                    layer, pos + 1, self.scale)
        with _costs.part("proj"):
            o = jnp.einsum("shc,hvc->shv", u.astype(c.dtype), p["wv"],
                           preferred_element_type=f32)
        return self._out(p, h, o), cache


class DenseSwiGLU(_Stacked):
    """The feed-forward half of a dense layer: pre-norm and one SwiGLU of
    width `hidden`; with `post_norm`, a norm of what it gives as well
    (`ln_post`), before the residual stream takes it."""

    _names = ("ln", "wg", "wu", "wd")

    def __init__(self, layers, units, hidden, eps=1e-6, post_norm=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._layers, self._eps = int(layers), float(eps)
        D, F = int(units), int(hidden)
        self.ln = self._param("ln", (D,), "ones")
        self.wg = self._param("wg", (F, D))
        self.wu = self._param("wu", (F, D))
        self.wd = self._param("wd", (D, F))
        if post_norm:
            self._names = self._names + ("ln_post",)
            self.ln_post = self._param("ln_post", (D,), "ones")

    def apply(self, p, h):
        from ..parallel import moe
        with _costs.part("ffn"):
            x = _rms(h, p["ln"], self._eps).astype(p["wg"].dtype)
            y = moe.swiglu(x, p["wg"], p["wu"], p["wd"])
            return h + (_rms(y, p["ln_post"], self._eps) if "ln_post" in p
                        else y)


class LatentDecoder(HybridBlock):
    """Embedding, `first_dense` layers of (LatentAttention, DenseSwiGLU),
    `num_layers - first_dense` of (LatentAttention, HeldExperts routed
    inside `topk_group` of `n_group` groups, gates times `routed_scale`,
    with an un-gated shared expert), a final RMSNorm and the output
    projection over the vocabulary rows held."""

    # what a decode step did for each slot, in the columns of `counts`: rows
    # attended from, rows the attention read, KiB of cache the step needs
    # moved, the same plus the slot's share of the weights (read once a
    # step), expert picks, picks of held experts, picks at each layer's
    # fullest held expert
    step_counts = ("gen.attn_context", "gen.attn_rows_read", "gen.cache_kib",
                   "gen.step_kib", "moe.picks", "moe.picks_held",
                   "moe.expert_max")

    def __init__(self, vocab_size, units, num_layers, first_dense, num_heads,
                 q_rank, kv_rank, nope_dim, rope_dim, v_dim, dense_hidden,
                 expert_hidden, num_experts, experts_per_token, n_group,
                 topk_group, routed_scale=1.0, shared_hidden=0, first_held=0,
                 experts_held=None, rope_theta=1e4, rope_scaling=None,
                 eps=1e-6, query_block=512, key_chunk=512, expert_tile=256,
                 **kwargs):
        from ..parallel import moe
        super().__init__(**kwargs)
        if not 0 < first_dense < num_layers:
            raise ValueError("%d leading dense layers of %d layers"
                             % (first_dense, num_layers))
        self._layers, self._dense = int(num_layers), int(first_dense)
        self._sparse = self._layers - self._dense
        self._per_token = int(experts_per_token)
        self._block, self._chunk = int(query_block), int(key_chunk)
        self.embed = self.params.get("embed", shape=(vocab_size, units))
        self.attn = LatentAttention(
            num_layers, units, num_heads, q_rank, kv_rank, nope_dim,
            rope_dim, v_dim, rope_theta, rope_scaling, eps)
        self.ffn = DenseSwiGLU(first_dense, units, dense_hidden, eps)
        self.experts = HeldExperts(
            self._sparse, units, expert_hidden, num_experts,
            experts_per_token, first_held, experts_held, eps, expert_tile,
            shared_hidden, route=functools.partial(
                moe.group_limited_route, n_group=int(n_group),
                topk_group=int(topk_group), scale=float(routed_scale)),
            shared_gate=False)
        self.norm = RMSNorm(units, eps)
        self.head = self.params.get("head", shape=(vocab_size, units))

    def _stacks(self):
        return {"attn": self.attn.stacked(), "ffn": self.ffn.stacked(),
                "experts": self.experts.stacked()}

    def _experts(self, p, h, i):
        """Expert layer `i`'s (from 0) expert half over h.  The expert
        weights go down whole with the layer's index: the many-token form
        reads one expert at a time, at [layer, expert]."""
        return self.experts.apply(
            _at(p["experts"], i, ("wg", "wu", "wd")), h, i)

    def _embed(self, tokens):
        with _costs.part("embed"):
            return _f32(self.embed.data()._data[tokens])

    def _logits(self, h):
        g, w = self.norm.gamma.data()._data, self.head.data()._data
        with _costs.part("head"):
            return _dense(_rms(h, g, self.norm._eps).astype(w.dtype), w)

    def _run_prompt(self, tokens):
        """tokens (T,) -> (h (T, D), c (layers, T, kv_rank), k_r (layers,
        T, rope))."""
        import jax
        import jax.numpy as jnp
        p, h = self._stacks(), self._embed(tokens)
        mix = functools.partial(self.attn.prompt, block=self._block,
                                chunk=self._chunk)
        rows = []
        for j in range(self._dense):
            h, c, kr = mix(_at(p["attn"], j), h)
            h = self.ffn.apply(_at(p["ffn"], j), h)
            rows.append((c, kr))

        def layer(h, i):
            h, c, kr = mix(_at(p["attn"], self._dense + i), h)
            return self._experts(p, h, i)[0], (c, kr)

        h, (c, kr) = jax.lax.scan(layer, h, jnp.arange(self._sparse))
        return h, jnp.concatenate([jnp.stack([r[0] for r in rows]), c]), \
            jnp.concatenate([jnp.stack([r[1] for r in rows]), kr])

    def forward(self, tokens):
        """Logits (B, T, V) of `tokens` (B, T), in the expanded form."""
        import jax
        return NDArray(jax.vmap(lambda t: self._logits(
            self._run_prompt(t)[0]))(tokens._data))

    def init_cache(self, prompt, valid_len, max_len, mem_len=None):
        """Prefill `prompt` (B, T), of which `valid_len` (B,) tokens are
        real; `mem_len` is the encoder-memory length of models that have
        one and is not used."""
        import jax
        import jax.numpy as jnp
        tokens, n = prompt._data, valid_len._data.reshape(-1)
        B, T = tokens.shape
        if T > int(max_len):
            raise ValueError("a prompt bucket of %d exceeds max_len %d"
                             % (T, max_len))
        _, c, kr = jax.vmap(self._run_prompt)(tokens)   # (B, layers, T, .)
        pad = lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, int(max_len) - T),
                                    (0, 0)])
        last = jnp.maximum(n - 1, 0).astype(jnp.int32)
        with _costs.part("cache"):
            out = {"ckv": pad(c), "kr": pad(kr),
                   "counts": jnp.zeros((B, len(self.step_counts)),
                                       jnp.int32),
                   "start_tok": jnp.take_along_axis(
                       tokens, last[:, None], 1)[:, 0].astype(jnp.int32),
                   "start_pos": last}
        return {name: NDArray(a) for name, a in out.items()}

    def step_weight_bytes(self):
        """Bytes of weights one decode step reads once: every parameter but
        the embedding, of which a step reads a row a slot."""
        return sum(math.prod(q.shape) * np.dtype(q.dtype).itemsize
                   for q in self.collect_params().values()
                   if q is not self.embed)

    def decode_step(self, tok, pos, cache, live):
        """Token `tok` (S,) at position `pos` (S,) against the cache:
        (logits (S, V) float32, the cache with row `pos` written), in the
        absorbed form.  `live` (S,; which slots hold a stream) only shares
        the weights' bytes out among `counts`: the attention reads every
        slot up to its position."""
        import jax
        import jax.numpy as jnp
        from ..ops.attention import latent_rows_read
        # trace-time side effect only, as `serve.traces` is
        events.incr("mla.absorbed_traces")
        tok, pos, live = tok._data, pos._data, live._data
        leaves = {n: cache[n]._data for n in ("ckv", "kr")}
        S = tok.shape[0]
        zero = jnp.zeros((S,), jnp.int32)
        p, h = self._stacks(), self._embed(tok)
        for j in range(self._dense):
            h, leaves = self.attn.step(_at(p["attn"], j), h, pos, j, leaves)
            h = self.ffn.apply(_at(p["ffn"], j), h)

        def layer(carry, i):
            h, leaves, held, full = carry
            n = self._dense + i
            h, leaves = self.attn.step(_at(p["attn"], n), h, pos, n, leaves)
            h, n_held, n_full = self._experts(p, h, i)
            return (h, leaves, held + n_held, full + n_full), None

        (h, leaves, held, full), _ = jax.lax.scan(
            layer, (h, leaves, zero, zero), jnp.arange(self._sparse))
        ckv, kr = leaves["ckv"], leaves["kr"]
        # a step reads rows 0..pos of both leaves and writes row pos, in
        # every layer
        row = (ckv.shape[-1] + kr.shape[-1]) * ckv.dtype.itemsize
        with _costs.part("cache"):
            cache_kib = self._layers * row * (pos + 2) // 1024
            share = (self.step_weight_bytes() // 1024) \
                // jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)
            counts = jnp.stack(
                [self._layers * (pos + 1),
                 self._layers * latent_rows_read(pos + 1, ckv),
                 cache_kib, cache_kib + jnp.where(live, share, 0),
                 jnp.full((S,), self._sparse * self._per_token, jnp.int32),
                 held, full], axis=1).astype(jnp.int32)
        new = dict(cache)
        new.update({n: NDArray(a) for n, a in leaves.items()})
        new["counts"] = NDArray(counts)
        return NDArray(self._logits(h)), new
