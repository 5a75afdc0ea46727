"""Decoder-only transformer whose layers are of two kinds in a period:
`period - 1` Gated-DeltaNet layers, whose memory is a recurrent state, then
one layer of gated softmax attention over cached rows; every layer ends in
a sparse expert layer with a shared expert (`sparse_decoder.HeldExperts`).

Pre-norm, no biases; every RMSNorm of the residual stream and of the
attention's heads is zero-centred (scale 1 + w).  h_t is the residual
stream at position t.

**Gated attention** (`GatedAttention`).  x = N(h_t).  One projection gives
each of the H query heads 2d values, its query and its gate
(`[q_h, gate_h]`); k, v over G key/value heads.  q_h and k_g take a
per-head RMSNorm and rotary positions on their first `rotary_dim` dims (the
rest pass).  Causal softmax of q.k / sqrt(d) over the group's rows;
o_h <- o_h * sigmoid(gate_h); h += Wo o.

**Gated DeltaNet** (`GatedDeltaNet`).  From x: q, k (Hk heads of dk), v, z
(Hv heads of dv), b, a (Hv).  [q, k, v] pass a causal depthwise
convolution of width K and silu; q and k are L2-normalised a head and each
repeated to Hv / Hk value heads, q scaled by dk^-1/2.  beta = sigmoid(b),
g = -exp(A_log) softplus(a + dt_bias), and the gated delta rule
(`ops.linear_attention`) gives o a value head from a state S (dk, dv)
float32.  o <- w o / rms(o) * silu(z) a head; h += Wo o.

Types as in `sparse_decoder`: the residual stream float32, every block
rounding its normed input to the weights' type for its matrix products,
which accumulate in float32; the delta rule, the decays and the norms
float32; K, V and the convolution's rows in the weights' type; the
recurrent state float32.

A block holds its parameters for all of ITS layers stacked on a leading
axis, and the model scans over PERIODS: the period's layers compile once,
whatever the depth.

Serving contract (`serving.GenerationEngine`), as `SparseDecoder`'s, with
leaves that have NO time axis beside K/V.  ``init_cache`` returns,
slot-major: ``k``, ``v`` (B, full layers, G, max_len, d); ``s``
(B, DeltaNet layers, Hv, dk, dv) float32, the recurrent states; ``c``
(B, DeltaNet layers, K - 1, conv channels), the convolution's last input
rows; ``counts``; ``start_tok``/``start_pos`` = the prompt's last token
and its position.  The first decode step reads that token AGAIN.  Rows of
K/V shrug that off (the step rewrites row `pos` with what was there); a
recurrence would apply the token twice.  So the prefill hands over ``s``
and ``c`` as they stand BEFORE the prompt's last token, and its scans stop
at ``valid_len``: padding that cached rows never read would run on
through a state.  A slot's leaves are wholly rewritten by `join`, so what
an earlier occupant left in ``s`` is never read.
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock
from ..ndarray.ndarray import NDArray
from ..telemetry import costs as _costs
from .sparse_decoder import (HeldExperts, RMSNorm, _Stacked, _at, _dense,
                             _f32, _rms, rotary)

__all__ = ["GatedAttention", "GatedDeltaNet", "HybridDecoder"]


class GatedAttention(_Stacked):
    """The mixer of a full-attention layer: pre-norm, grouped-query
    projections with the gate beside the query, per-head q/k RMSNorm,
    partial rotary positions, the output under its gate."""

    _names = ("ln", "wq", "wk", "wv", "wo", "gq", "gk")

    def __init__(self, layers, units, num_heads, num_kv_heads, head_dim,
                 rotary_dim, rope_theta=1e7, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads over %d key/value heads"
                             % (num_heads, num_kv_heads))
        self._layers = int(layers)
        self._H, self._G, self._d = num_heads, num_kv_heads, head_dim
        self._rot = int(rotary_dim)
        self._theta, self._eps = float(rope_theta), float(eps)
        D = int(units)
        self.ln = self._param("ln", (D,), "zeros")
        self.wq = self._param("wq", (num_heads * 2 * head_dim, D))
        self.wk = self._param("wk", (num_kv_heads * head_dim, D))
        self.wv = self._param("wv", (num_kv_heads * head_dim, D))
        self.wo = self._param("wo", (D, num_heads * head_dim))
        self.gq = self._param("gq", (head_dim,), "zeros")
        self.gk = self._param("gk", (head_dim,), "zeros")

    def project(self, p, h, pos):
        """One layer's projections of h (T, D) at positions pos (T,):
        q (T, H, d), k, v (T, G, d) in the weights' type and the gate
        (T, H * d) float32."""
        T, dt = h.shape[0], p["wq"].dtype
        x = _rms(h, p["ln"], self._eps, 1.0).astype(dt)
        qg = _dense(x, p["wq"]).reshape(T, self._H, 2 * self._d)
        q, gate = qg[..., :self._d], qg[..., self._d:]
        k = _dense(x, p["wk"]).reshape(T, self._G, self._d)
        v = _dense(x, p["wv"]).reshape(T, self._G, self._d)
        q = rotary(_rms(q, p["gq"], self._eps, 1.0), pos, self._theta,
                   self._rot)
        k = rotary(_rms(k, p["gk"], self._eps, 1.0), pos, self._theta,
                   self._rot)
        return q.astype(dt), k.astype(dt), v.astype(dt), gate.reshape(T, -1)

    def _out(self, p, h, o, gate):
        import jax
        with _costs.part("proj"):
            o = o.reshape(h.shape[0], -1) * jax.nn.sigmoid(gate)
            return h + _dense(o.astype(p["wo"].dtype), p["wo"])

    def prompt(self, p, h):
        """One layer over a whole prompt h (T, D): (h + attention, the
        rows k, v (G, T, d) for the cache)."""
        import jax
        import jax.numpy as jnp
        T = h.shape[0]
        with _costs.part("proj"):
            q, k, v, gate = self.project(p, h, jnp.arange(T))
        with _costs.part("attn"):
            qg = q.reshape(T, self._G, self._H // self._G, self._d)
            s = jnp.einsum("qghd,kgd->ghqk", qg, k,
                           preferred_element_type=jnp.float32) \
                / math.sqrt(self._d)
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
            o = jnp.einsum("ghqk,kgd->qghd",
                           jax.nn.softmax(s, -1).astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
        h = self._out(p, h, o, gate)
        with _costs.part("cache"):
            return h, k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def step(self, p, h, pos, layer, cache):
        """One layer, one token a slot: h (S, D) at pos (S,).  Writes row
        pos of the layer's K/V rows and attends over rows <= pos."""
        import jax.numpy as jnp
        from ..ops.attention import masked_decode_attention
        S = h.shape[0]
        with _costs.part("proj"):
            q, k, v, gate = self.project(p, h, pos)
        with _costs.part("cache"):
            at = (jnp.arange(S)[:, None], layer,
                  jnp.arange(self._G)[None, :], pos[:, None])
            cache = dict(cache, k=cache["k"].at[at].set(k),
                         v=cache["v"].at[at].set(v))
        with _costs.part("attn"):
            L = cache["k"].shape[3]
            o = masked_decode_attention(
                q, jnp.take(cache["k"], layer, axis=1),
                jnp.take(cache["v"], layer, axis=1),
                jnp.arange(L)[None, :] <= pos[:, None],
                1.0 / math.sqrt(self._d))
        return self._out(p, h, o, gate), cache


class GatedDeltaNet(_Stacked):
    """The mixer of a linear-attention layer: pre-norm, the projections,
    the short convolution, the gated delta rule, the gated norm."""

    _names = ("ln", "wq", "wk", "wv", "wz", "wb", "wa", "conv", "a_log",
              "dt_bias", "gn", "wo")

    def __init__(self, layers, units, key_heads, value_heads, key_dim,
                 value_dim, conv_width=4, chunk=64, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if value_heads % key_heads:
            raise ValueError("%d value heads over %d key heads"
                             % (value_heads, key_heads))
        self._layers = int(layers)
        self._Hk, self._Hv = int(key_heads), int(value_heads)
        self._dk, self._dv = int(key_dim), int(value_dim)
        self._K, self._chunk, self._eps = int(conv_width), int(chunk), eps
        D, nk, nv = int(units), key_heads * key_dim, value_heads * value_dim
        self.conv_channels = 2 * nk + nv
        self.ln = self._param("ln", (D,), "zeros")
        self.wq = self._param("wq", (nk, D))
        self.wk = self._param("wk", (nk, D))
        self.wv = self._param("wv", (nv, D))
        self.wz = self._param("wz", (nv, D))
        self.wb = self._param("wb", (value_heads, D))
        self.wa = self._param("wa", (value_heads, D))
        self.conv = self._param("conv", (self.conv_channels, conv_width))
        self.a_log = self._param("a_log", (value_heads,), "zeros")
        self.dt_bias = self._param("dt_bias", (value_heads,), "zeros")
        self.gn = self._param("gn", (value_dim,), "ones")
        self.wo = self._param("wo", (D, nv))

    def _project(self, p, h):
        """h (T, D) -> the convolution's input (T, channels) in the
        weights' type, z (T, Hv, dv), g and beta (T, Hv) float32."""
        import jax
        import jax.numpy as jnp
        dt = p["wq"].dtype
        x = _rms(h, p["ln"], self._eps, 1.0).astype(dt)
        qkv = jnp.concatenate([_dense(x, p[n]) for n in ("wq", "wk", "wv")],
                              -1).astype(dt)
        z = _dense(x, p["wz"]).reshape(-1, self._Hv, self._dv)
        g = -jnp.exp(_f32(p["a_log"])) * jax.nn.softplus(
            _dense(x, p["wa"]) + _f32(p["dt_bias"]))
        return qkv, z, g, jax.nn.sigmoid(_dense(x, p["wb"]))

    def _heads(self, y):
        """The convolution's output y (T, channels) float32 -> q, k
        (T, Hv, dk) and v (T, Hv, dv), ready for the delta rule."""
        import jax
        import jax.numpy as jnp
        T, nk = y.shape[0], self._Hk * self._dk
        y = jax.nn.silu(y)
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        rep = lambda a: jnp.repeat(a, self._Hv // self._Hk, axis=1)
        q = unit(y[:, :nk].reshape(T, self._Hk, self._dk))
        k = unit(y[:, nk:2 * nk].reshape(T, self._Hk, self._dk))
        return rep(q) * self._dk ** -0.5, rep(k), \
            y[:, 2 * nk:].reshape(T, self._Hv, self._dv)

    def _out(self, p, h, o, z):
        import jax
        with _costs.part("proj"):
            o = _rms(o, p["gn"], self._eps) * jax.nn.silu(z)
            return h + _dense(
                o.reshape(h.shape[0], -1).astype(p["wo"].dtype), p["wo"])

    def prompt(self, p, h, valid_len=None):
        """One layer over a whole prompt h (T, D): (h + the mixer, the
        state (Hv, dk, dv) and the convolution's rows (K - 1, channels) as
        of BEFORE position valid_len - 1; after the last position without
        `valid_len`)."""
        from ..ops import linear_attention as la
        with _costs.part("proj"):
            qkv, z, g, beta = self._project(p, h)
        with _costs.part("state"):
            y, rows = la.causal_conv(qkv, p["conv"], valid_len)
            q, k, v = self._heads(y)
            o, state = la.gated_delta_chunked(q, k, v, g, beta, valid_len,
                                              self._chunk)
        return self._out(p, h, o, z), state, rows

    def step(self, p, h, layer, cache):
        """One layer, one token a slot: h (S, D).  Reads and rewrites the
        layer's states and convolution rows in the cache."""
        import jax
        import jax.numpy as jnp
        from ..ops import linear_attention as la
        with _costs.part("proj"):
            qkv, z, g, beta = self._project(p, h)
        with _costs.part("state"):
            y, rows = la.causal_conv_step(
                qkv, jnp.take(cache["c"], layer, 1), p["conv"])
            q, k, v = self._heads(y)
            o, states = la.gated_delta_step(q, k, v, g, beta, cache["s"],
                                            layer)
        with _costs.part("cache"):
            cache = dict(cache, s=states,
                         c=jax.lax.dynamic_update_slice_in_dim(
                             cache["c"], rows[:, None], layer, axis=1))
        return self._out(p, h, o, z), cache


class HybridDecoder(HybridBlock):
    """Embedding, `num_layers` layers in periods of `period` (period - 1 x
    (GatedDeltaNet, HeldExperts), then (GatedAttention, HeldExperts)), a
    final RMSNorm and the output projection over the vocabulary rows
    held."""

    # what a decode step did for each slot, in the columns of `counts`:
    # rows attended from, KiB of recurrent and convolution state read and
    # written, KiB of cache read and written in all, expert picks, picks of
    # held experts, picks at each layer's fullest held expert
    step_counts = ("gen.attn_context", "gdn.state_kib", "gen.cache_kib",
                   "moe.picks", "moe.picks_held", "moe.expert_max")

    def __init__(self, vocab_size, units, num_layers, period, num_heads,
                 num_kv_heads, head_dim, rotary_dim, key_heads, value_heads,
                 key_dim, value_dim, conv_width, expert_hidden, num_experts,
                 experts_per_token, shared_hidden=0, first_held=0,
                 experts_held=None, rope_theta=1e7, eps=1e-6, chunk=64,
                 expert_tile=256, **kwargs):
        super().__init__(**kwargs)
        if num_layers % period:
            raise ValueError("%d layers are no whole number of periods of %d"
                             % (num_layers, period))
        self._layers, self._period = int(num_layers), int(period)
        self._periods = self._layers // self._period
        self._per_token = int(experts_per_token)
        self.embed = self.params.get("embed", shape=(vocab_size, units))
        self.attn = GatedAttention(
            self._periods, units, num_heads, num_kv_heads, head_dim,
            rotary_dim, rope_theta, eps)
        self.gdn = GatedDeltaNet(
            self._periods * (self._period - 1), units, key_heads,
            value_heads, key_dim, value_dim, conv_width, chunk, eps)
        self.experts = HeldExperts(
            num_layers, units, expert_hidden, num_experts,
            experts_per_token, first_held, experts_held, eps, expert_tile,
            shared_hidden, 1.0)
        self.norm = RMSNorm(units, eps, 1.0)
        self.head = self.params.get("head", shape=(vocab_size, units))

    def _stacks(self):
        return {"attn": self.attn.stacked(), "gdn": self.gdn.stacked(),
                "experts": self.experts.stacked()}

    def _experts(self, p, h, layer):
        """Layer `layer`'s expert half over h.  The expert weights go down
        whole with the layer's index: the many-token form reads one expert
        at a time, at [layer, expert]."""
        return self.experts.apply(
            _at(p["experts"], layer, ("wg", "wu", "wd")), h, layer)

    def _embed(self, tokens):
        with _costs.part("embed"):
            return _f32(self.embed.data()._data[tokens])

    def _logits(self, h):
        g, w = self.norm.gamma.data()._data, self.head.data()._data
        with _costs.part("head"):
            return _dense(_rms(h, g, self.norm._eps, 1.0).astype(w.dtype), w)

    def _run_prompt(self, tokens, valid_len=None):
        """tokens (T,) -> (h (T, D), k, v (full layers, G, T, d), s
        (DeltaNet layers, Hv, dk, dv), c (DeltaNet layers, K - 1,
        channels))."""
        import jax
        import jax.numpy as jnp
        P = self._period

        p = self._stacks()

        def period(h, i):
            states, rows = [], []
            for j in range(P - 1):
                h, s, c = self.gdn.prompt(
                    _at(p["gdn"], i * (P - 1) + j), h, valid_len)
                h = self._experts(p, h, i * P + j)[0]
                states.append(s)
                rows.append(c)
            h, k, v = self.attn.prompt(_at(p["attn"], i), h)
            h = self._experts(p, h, i * P + P - 1)[0]
            return h, (k, v, jnp.stack(states), jnp.stack(rows))

        h, (k, v, s, c) = jax.lax.scan(period, self._embed(tokens),
                                       jnp.arange(self._periods))
        return h, k, v, s.reshape((-1,) + s.shape[2:]), \
            c.reshape((-1,) + c.shape[2:])

    def forward(self, tokens):
        """Logits (B, T, V) of `tokens` (B, T)."""
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
        _, k, v, s, c = jax.vmap(self._run_prompt)(tokens, n)
        pad = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 2)
                                + [(0, int(max_len) - T), (0, 0)])
        last = jnp.maximum(n - 1, 0).astype(jnp.int32)
        with _costs.part("cache"):
            out = {"k": pad(k), "v": pad(v), "s": s, "c": c,
                   "counts": jnp.zeros((B, len(self.step_counts)),
                                       jnp.int32),
                   "start_tok": jnp.take_along_axis(
                       tokens, last[:, None], 1)[:, 0].astype(jnp.int32),
                   "start_pos": last}
        return {name: NDArray(a) for name, a in out.items()}

    def decode_step(self, tok, pos, cache, live):
        """Token `tok` (S,) at position `pos` (S,) against the cache:
        (logits (S, V) float32, the cache advanced one token).  `live`
        (S,; which slots hold a stream) is not used: a dead slot's state
        decays on, and `join` rewrites the whole slot before it is read."""
        import jax
        import jax.numpy as jnp
        tok, pos = tok._data, pos._data
        leaves = {n: cache[n]._data for n in ("k", "v", "s", "c")}
        S, P = tok.shape[0], self._period
        zero = jnp.zeros((S,), jnp.int32)

        p = self._stacks()

        def period(carry, i):
            h, leaves, held, full = carry
            for j in range(P - 1):
                n = i * (P - 1) + j
                h, leaves = self.gdn.step(_at(p["gdn"], n), h, n,
                                          leaves)
                h, n_held, n_full = self._experts(p, h, i * P + j)
                held, full = held + n_held, full + n_full
            h, leaves = self.attn.step(_at(p["attn"], i), h, pos, i,
                                       leaves)
            h, n_held, n_full = self._experts(p, h, i * P + P - 1)
            return (h, leaves, held + n_held, full + n_full), None

        (h, leaves, held, full), _ = jax.lax.scan(
            period, (self._embed(tok), leaves, zero, zero),
            jnp.arange(self._periods))
        size = lambda a: math.prod(a.shape[1:]) * a.dtype.itemsize
        # a step reads and writes each state and convolution row once, reads
        # K/V rows 0..pos and writes row pos, in every layer of their kind
        state = 2 * (size(leaves["s"]) + size(leaves["c"]))
        row = 2 * size(leaves["k"]) // leaves["k"].shape[3]
        with _costs.part("cache"):
            counts = jnp.stack(
                [self._periods * (pos + 1),
                 jnp.full((S,), state // 1024, jnp.int32),
                 (state + row * (pos + 2)) // 1024,
                 jnp.full((S,), self._layers * self._per_token, jnp.int32),
                 held, full], axis=1).astype(jnp.int32)
        new = dict(cache)
        new.update({n: NDArray(a) for n, a in leaves.items()})
        new["counts"] = NDArray(counts)
        return NDArray(self._logits(h)), new
