"""Decoder-only transformer whose stack of layers runs `loops` times a token
over the SAME weights (a looped, or universal, transformer), with an exit
gate after every pass that says which pass's state gives the logits.

No biases but the gate's; N(x; g) = g x / sqrt(mean(x^2) + eps).  h is the
residual stream of one position, H heads of d, R = `loops`, L layers.

    h = E[token]
    for r in 0..R-1:                      # the same L layers every pass
      for l in 0..L-1:
        a = N(h; ln1_l);  q, k, v = Wq_l a, Wk_l a, Wv_l a
        q, k take rotary positions (rotate-half over the whole head); the
          position is the token's, the same in every pass
        row (r, l, t) of the cache <- (k, v)
        o = softmax(q . k / sqrt(d)) v over rows (r, l, s <= t)
        h = h + N(Wo_l o; ln2_l)                    # a norm before AND after
        m = N(h; ln3_l);  h = h + N(Wd_l (silu(Wg_l m) * Wu_l m); ln4_l)
      h = N(h; norm)              # closes EVERY pass and feeds the next
      lam_r = sigmoid(we . h + be)                  # one gate for all passes
    p_r = lam_r prod_{j<r} (1 - lam_j) for r < R-1, p_{R-1} the rest
    r* = the first r with p_0 + .. + p_r >= exit_threshold (the last pass
         at a threshold of 1 or more: by definition, no float comparison)
    logits = Whead h^(r*)                           # already normed

Every pass is always computed: pass r of layer l attends over what pass r of
layer l wrote for the earlier positions, so every later token needs every
pass's rows.  The threshold only picks which pass's state is read out.

The blocks are `SandwichAttention` (plain multi-head attention, a norm on
both sides) and `latent_decoder.DenseSwiGLU` with its closing norm.  Each
holds its parameters for all L layers stacked on a leading axis; the model
runs TWO nested scans over the same stacks, passes outside and layers
inside, so the R x L layer bodies compile as one.

Types as in `sparse_decoder`: the residual stream float32, every block
rounding its normed input to the weights' type for its matrix products,
which accumulate in float32; norms, rotary positions, softmax, the gate and
the exit rule float32; the cached K/V in the weights' type.

Serving contract (`serving.GenerationEngine`), as `SparseDecoder`'s:
``init_cache`` returns, slot-major and head-major, ``k``, ``v``
(B, R * L, H, max_len, d) with the rows of pass r of layer l at index
r * L + l, ``counts`` and the stream's start (the prompt's last token and
its position, read again by the first step, which rewrites those rows with
what they held).  ``decode_step`` writes row ``pos[slot]`` of every (r, l)
in place, through both scans, and reads rows <= ``pos``: the attention
(`ops.attention.decode_attention`) takes the leaves whole with the index
r * L + l, for a slice in front of its kernel would be copied, a leaf's
worth a layer body.
"""
from __future__ import annotations

import math

import numpy as np

from ..gluon.block import HybridBlock
from ..monitor import events
from ..ndarray.ndarray import NDArray
from ..telemetry import costs as _costs
from .latent_decoder import DenseSwiGLU
from .sparse_decoder import (RMSNorm, _Stacked, _at, _dense, _f32, _rms,
                             rotary)

__all__ = ["SandwichAttention", "LoopedDecoder", "exit_pass"]


def exit_pass(lam, threshold):
    """The pass whose state is read out.  lam (R, ...) float32, the gates
    of passes 0..R-1 (the last one's is not used: the last pass takes what
    is left); returns (...) int32.  p_r = lam_r prod_{j<r} (1 - lam_j) for
    r < R - 1; the first r whose p_0 + .. + p_r reaches `threshold`, and
    the last pass where none does or the threshold is 1 or more."""
    import jax.numpy as jnp
    R = lam.shape[0]
    if threshold >= 1.0 or R == 1:
        return jnp.full(lam.shape[1:], R - 1, jnp.int32)
    stay = jnp.cumprod(1.0 - lam[:-1], axis=0)      # prod_{j<=r} (1 - lam_j)
    # p_0 + .. + p_r = 1 - prod_{j<=r} (1 - lam_j), summed as the rule says
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    reached = jnp.cumsum(lam[:-1] * before, axis=0) >= threshold
    return jnp.where(jnp.any(reached, 0), jnp.argmax(reached, 0),
                     R - 1).astype(jnp.int32)


class SandwichAttention(_Stacked):
    """The attention half of every layer: a norm, multi-head projections
    (every query head has keys and values of its own), rotary positions over
    the whole head, causal softmax attention, the output projection and a
    norm of what it gives."""

    _names = ("ln1", "wq", "wk", "wv", "wo", "ln2")

    def __init__(self, layers, units, num_heads, head_dim, rope_theta=1e6,
                 eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._layers = int(layers)
        self._H, self._d = int(num_heads), int(head_dim)
        self._theta, self._eps = float(rope_theta), float(eps)
        self.scale = 1.0 / math.sqrt(self._d)
        D, Hd = int(units), self._H * self._d
        self.ln1 = self._param("ln1", (D,), "ones")
        self.wq = self._param("wq", (Hd, D))
        self.wk = self._param("wk", (Hd, D))
        self.wv = self._param("wv", (Hd, D))
        self.wo = self._param("wo", (D, Hd))
        self.ln2 = self._param("ln2", (D,), "ones")

    def project(self, p, layer, h, pos):
        """Layer `layer`'s projections of h (T, D) at positions pos (T,):
        q, k, v (T, H, d) in the weights' type.  `p` is `stacked()`, whole:
        each leaf is sliced here, inside the caller's `proj` scope, so the
        slice (which XLA runs as a fusion of its own, the weights' trip to
        the chip's fast memory) bears the part's name."""
        p = _at({n: p[n] for n in ("ln1", "wq", "wk", "wv")}, layer)
        T, dt = h.shape[0], p["wq"].dtype
        x = _rms(h, p["ln1"], self._eps).astype(dt)
        heads = lambda w: _dense(x, w).reshape(T, self._H, self._d)
        return (rotary(heads(p["wq"]), pos, self._theta).astype(dt),
                rotary(heads(p["wk"]), pos, self._theta).astype(dt),
                heads(p["wv"]).astype(dt))

    def _out(self, p, layer, h, o):
        with _costs.part("proj"):
            p = _at({n: p[n] for n in ("wo", "ln2")}, layer)
            o = o.reshape(h.shape[0], -1).astype(p["wo"].dtype)
            return h + _rms(_dense(o, p["wo"]), p["ln2"], self._eps)

    def prompt(self, p, layer, h, block, chunk):
        """Layer `layer` over a whole prompt h (T, D): (h + attention, the
        rows k, v (H, T, d) for the cache)."""
        import jax.numpy as jnp
        from ..ops.attention import blocked_causal_attention
        with _costs.part("proj"):
            q, k, v = self.project(p, layer, h, jnp.arange(h.shape[0]))
        o = blocked_causal_attention(q, k, v, self.scale, block, chunk)
        h = self._out(p, layer, h, o)
        with _costs.part("cache"):
            return h, k.transpose(1, 0, 2), v.transpose(1, 0, 2)

    def step(self, p, layer, h, pos, lengths, row, cache):
        """Layer `layer`, one token a slot: h (S, D) at pos (S,).  Writes
        row pos of the leaves' index `row` and attends over the slot's rows
        below `lengths` (pos + 1, or 0 for a slot nobody sits in)."""
        from ..ops.attention import decode_attention, decode_rows_write
        with _costs.part("proj"):
            q, k, v = self.project(p, layer, h, pos)
        k_rows, v_rows = decode_rows_write(cache["k"], cache["v"], k, v, row,
                                           pos)
        # the query rounded as the keys are, then widened: both forms of
        # the attention take a float32 query and give a float32 context
        o = decode_attention(_f32(q), k_rows, v_rows, lengths,
                             scale=self.scale, layer=row)
        return self._out(p, layer, h, o), dict(cache, k=k_rows, v=v_rows)


class LoopedDecoder(HybridBlock):
    """Embedding, `num_layers` of (SandwichAttention, DenseSwiGLU with its
    closing norm) run `loops` times over the same weights, a final RMSNorm
    that closes every pass, the exit gate and the output projection."""

    # what a decode step did for each slot, in the columns of `counts`: rows
    # attended from, KiB of cache the step needs moved, the same plus the
    # slot's share of the weights (the layers' cross the bus once a PASS),
    # passes run, tokens, the pass that gave the logits (summed: its mean is
    # loops - 1 at a threshold of 1)
    step_counts = ("gen.attn_context", "gen.cache_kib", "gen.step_kib",
                   "loop.passes", "loop.tokens", "loop.exit_pass")

    def __init__(self, vocab_size, units, num_layers, num_heads, head_dim,
                 hidden, loops, exit_threshold=1.0, rope_theta=1e6,
                 eps=1e-6, query_block=512, key_chunk=512, **kwargs):
        super().__init__(**kwargs)
        if loops < 1:
            raise ValueError("%d passes over the layers" % loops)
        self._layers, self._loops = int(num_layers), int(loops)
        self._threshold = float(exit_threshold)
        self._block, self._chunk = int(query_block), int(key_chunk)
        self.embed = self.params.get("embed", shape=(vocab_size, units))
        self.attn = SandwichAttention(num_layers, units, num_heads,
                                      head_dim, rope_theta, eps)
        self.ffn = DenseSwiGLU(num_layers, units, hidden, eps,
                               post_norm=True)
        self.norm = RMSNorm(units, eps)
        self.we = self.params.get("we", shape=(1, units))
        self.be = self.params.get("be", shape=(1,), init="zeros")
        self.head = self.params.get("head", shape=(vocab_size, units))

    def _stacks(self):
        return {"attn": self.attn.stacked(), "ffn": self.ffn.stacked()}

    def _embed(self, tokens):
        with _costs.part("embed"):
            return _f32(self.embed.data()._data[tokens])

    def _close(self, h):
        """What ends a pass over h (T, D): (the normed state, which feeds
        the next pass and the head; the gate lam (T,))."""
        import jax
        import jax.numpy as jnp
        with _costs.part("head"):
            h = _rms(h, self.norm.gamma.data()._data, self.norm._eps)
            # true float32, as a router's scores: it is compared with a
            # threshold, and a rounding that crosses it reads another pass
            z = jnp.einsum("td,od->to", h, _f32(self.we.data()._data),
                           precision=jax.lax.Precision.HIGHEST)[:, 0]
            return h, jax.nn.sigmoid(z + _f32(self.be.data()._data)[0])

    def _read_out(self, hs, lam):
        """hs (R, T, D) the closed state of every pass, lam (R, T):
        (logits (T, V) float32 from each position's exit pass, that pass
        (T,))."""
        import jax.numpy as jnp
        w = self.head.data()._data
        with _costs.part("head"):
            at = exit_pass(lam, self._threshold)
            h = jnp.take_along_axis(hs, at[None, :, None], 0)[0]
            return _dense(h.astype(w.dtype), w), at

    def _ffn(self, p, layer, h):
        with _costs.part("ffn"):        # the layer's slices among them
            return self.ffn.apply(_at(p["ffn"], layer), h)

    def _run_prompt(self, tokens):
        """tokens (T,) -> (hs (R, T, D), lam (R, T), k, v (R * L, H, T,
        d))."""
        import jax
        import jax.numpy as jnp
        p = self._stacks()

        def layer(h, l):
            h, k, v = self.attn.prompt(p["attn"], l, h, self._block,
                                       self._chunk)
            return self._ffn(p, l, h), (k, v)

        def one_pass(h, _):
            h, rows = jax.lax.scan(layer, h, jnp.arange(self._layers))
            h, lam = self._close(h)
            return h, (h, lam, rows)

        _, (hs, lam, (k, v)) = jax.lax.scan(one_pass, self._embed(tokens),
                                            None, length=self._loops)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        return hs, lam, flat(k), flat(v)

    def forward(self, tokens):
        """Logits (B, T, V) of `tokens` (B, T)."""
        import jax

        def one(t):
            hs, lam, _, _ = self._run_prompt(t)
            return self._read_out(hs, lam)[0]

        return NDArray(jax.vmap(one)(tokens._data))

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
        _, _, k, v = jax.vmap(self._run_prompt)(tokens)  # (B, R*L, H, T, d)
        pad = lambda a: jnp.pad(a, [(0, 0)] * 3
                                + [(0, int(max_len) - T), (0, 0)])
        last = jnp.maximum(n - 1, 0).astype(jnp.int32)
        with _costs.part("cache"):
            out = {"k": pad(k), "v": pad(v),
                   "counts": jnp.zeros((B, len(self.step_counts)),
                                       jnp.int32),
                   "start_tok": jnp.take_along_axis(
                       tokens, last[:, None], 1)[:, 0].astype(jnp.int32),
                   "start_pos": last}
        return {name: NDArray(a) for name, a in out.items()}

    def step_weight_bytes(self):
        """Bytes of weights one decode step needs moved: the layers' once
        a PASS (nothing on the chip holds them between passes), the final
        norm, the gate and the head once; of the embedding a step reads a
        row a slot."""
        size = lambda q: math.prod(q.shape) * np.dtype(q.dtype).itemsize
        layers = [q for b in (self.attn, self.ffn)
                  for q in b.collect_params().values()]
        return self._loops * sum(map(size, layers)) + sum(
            size(q) for q in (self.norm.gamma, self.we, self.be, self.head))

    def decode_step(self, tok, pos, cache, live):
        """Token `tok` (S,) at position `pos` (S,) against the cache:
        (logits (S, V) float32, the cache with row `pos` of every (pass,
        layer) written).  A slot that is not `live` (S,; which slots hold a
        stream) attends over nothing: its logits are finite and mean
        nothing."""
        import jax
        import jax.numpy as jnp
        # trace-time side effect only, as `serve.traces` is
        events.incr("loop.traces")
        tok, pos, live = tok._data, pos._data, live._data
        leaves = {n: cache[n]._data for n in ("k", "v")}
        S, L, R = tok.shape[0], self._layers, self._loops
        lengths = jnp.where(live, pos + 1, 0).astype(jnp.int32)
        p = self._stacks()

        def one_pass(carry, r):
            def layer(carry, l):
                h, leaves = carry
                h, leaves = self.attn.step(p["attn"], l, h, pos, lengths,
                                           r * L + l, leaves)
                return (self._ffn(p, l, h), leaves), None

            (h, leaves), _ = jax.lax.scan(layer, carry, jnp.arange(L))
            h, lam = self._close(h)
            return (h, leaves), (h, lam)

        (_, leaves), (hs, lam) = jax.lax.scan(
            one_pass, (self._embed(tok), leaves), jnp.arange(R))
        logits, at = self._read_out(hs, lam)
        # a step reads rows 0..pos of both leaves and writes row pos, at
        # every (pass, layer)
        k = leaves["k"]
        row = 2 * k.shape[2] * k.shape[4] * k.dtype.itemsize
        with _costs.part("cache"):
            cache_kib = R * L * row * (pos + 2) // 1024
            share = (self.step_weight_bytes() // 1024) \
                // jnp.maximum(jnp.sum(live, dtype=jnp.int32), 1)
            counts = jnp.stack(
                [R * L * (pos + 1), cache_kib,
                 cache_kib + jnp.where(live, share, 0),
                 jnp.full((S,), R, jnp.int32), jnp.ones((S,), jnp.int32),
                 at], axis=1).astype(jnp.int32)
        new = dict(cache)
        new.update({n: NDArray(a) for n, a in leaves.items()})
        new["counts"] = NDArray(counts)
        return NDArray(logits), new
