"""Transformer encoder (BERT-style) built on Gluon layers.

Parity target: BASELINE.json config 2 (BERT-base MLM pretrain, GluonNLP
`BERTEncoder`-equivalent; ref upstream: gluon-nlp bert.py — the reference
repo itself carries only contrib attention ops, see
src/operator/contrib/transformer.cc interleaved_matmul_*).

TPU-first notes: attention is jnp einsum/matmul on the MXU; bf16-friendly;
Dense weights are laid out so tensor-parallel sharding (P('model', None))
splits heads / FFN columns cleanly over the mesh's 'model' axis.
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock
from ..gluon import nn
from ..telemetry import costs as _costs

__all__ = ["MultiHeadAttention", "CrossAttention", "PositionwiseFFN",
           "TransformerEncoderLayer", "TransformerEncoder",
           "TransformerDecoderLayer", "TransformerDecoder",
           "TransformerNMT", "transformer_nmt_base",
           "transformer_nmt_small", "BERTModel",
           "bert_base", "bert_small"]


class MultiHeadAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.0, seq_parallel=None,
                 **kwargs):
        """seq_parallel: optional (mesh, axis_name) — run attention
        ring-parallel over a sequence-sharded mesh axis
        (parallel/ring_attention.py), so context length scales with the
        number of chips on that axis."""
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._seq_parallel = seq_parallel
        self._ring_jit = {}          # home device -> jitted ring call
        if seq_parallel is not None and dropout:
            import warnings
            warnings.warn(
                "MultiHeadAttention(seq_parallel=...): attention-prob "
                "dropout is not applied on the ring-attention path "
                "(same contract as fused flash attention); residual/FFN "
                "dropout still applies")
        self.query = nn.Dense(units, flatten=False, use_bias=True)
        self.key = nn.Dense(units, flatten=False, use_bias=True)
        self.value = nn.Dense(units, flatten=False, use_bias=True)
        self.proj = nn.Dense(units, flatten=False, use_bias=True)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def _get_ring_fn(self, home):
        """Build (once per home device) the jitted resharding ring-
        attention call — rebuilding the shard_map per forward would
        retrace/recompile every step."""
        if home in self._ring_jit:
            return self._ring_jit[home]
        import functools
        import jax as _jax
        from jax.sharding import (PartitionSpec as JP, NamedSharding,
                                  SingleDeviceSharding)
        from jax import shard_map
        from ..parallel import ring_attention
        mesh, axis = self._seq_parallel
        spec = JP(None, axis)
        sh = NamedSharding(mesh, spec)
        out_sh = SingleDeviceSharding(home)
        ring = shard_map(
            functools.partial(ring_attention, axis_name=axis),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)

        jring = _jax.jit(ring)       # one cached executable per shape

        def _ring(qj, kj, vj):
            # reshard onto the sequence mesh, run the (cached) ring
            # executable, come back to the caller's device — the rest of
            # the model is single-device in imperative mode (under pjit
            # the compiler owns layouts end-to-end).  The device hops
            # stay OUTSIDE jit: a jitted computation cannot change
            # device sets.
            qj, kj, vj = (_jax.device_put(t, sh) for t in (qj, kj, vj))
            return _jax.device_put(jring(qj, kj, vj), out_sh)
        _ring.__name__ = "ring_attention"
        self._ring_jit[home] = _ring
        return _ring

    def _ring_forward(self, x):
        """Context-parallel path: q/k/v (B, T, H, d) sharded on T over
        the mesh axis, ring attention inside shard_map."""
        from ..ndarray.ndarray import apply_fn
        H = self._num_heads
        B, T, C = x.shape
        mesh, axis = self._seq_parallel
        n_shards = mesh.shape[axis]
        if T % n_shards:
            raise ValueError(
                "seq_parallel ring attention needs the sequence length "
                "to divide evenly over the %r mesh axis: T=%d, shards=%d "
                "(pad the sequence or change the mesh)"
                % (axis, T, n_shards))
        d = C // H
        q = self.query(x).reshape((B, T, H, d))
        k = self.key(x).reshape((B, T, H, d))
        v = self.value(x).reshape((B, T, H, d))
        fn = self._get_ring_fn(x.context.jax_device)
        ctx = apply_fn(fn, [q, k, v], {}, name="ring_attention")
        return self.proj(ctx.reshape((B, T, C)))

    def forward(self, x, mask=None):
        from .. import ndarray as F
        from .. import autograd
        H = self._num_heads
        from ..symbol.symbol import Symbol as _Sym
        if self._seq_parallel is not None:
            if mask is None and not isinstance(x, _Sym):
                return self._ring_forward(x)
            import warnings
            warnings.warn(
                "seq_parallel attention falls back to the single-device "
                "path (%s): the ring path supports mask=None imperative "
                "execution" % ("mask given" if mask is not None
                               else "symbol trace"))
        # fused path: whole softmax(QK^T)V is one kernel (Pallas flash on
        # TPU, fused XLA elsewhere — ops/attention.py); the score matrix
        # never hits HBM.  Attention-prob dropout is only live while
        # training, so inference fuses regardless of the dropout config.
        # Shape-free on purpose: keeps the block symbol-traceable.
        # The part is `proj` but for the attention itself (the inner scope
        # names an op)
        with _costs.part("proj"):
            if mask is None and (self.dropout is None
                                 or not autograd.is_training()):
                q, k, v = self.query(x), self.key(x), self.value(x)
                with _costs.part("attn"):
                    ctx = F._contrib_flash_attention(q, k, v, num_heads=H)
                return self.proj(ctx)
            q = _split_heads(F, self.query(x), H)
            k = _split_heads(F, self.key(x), H)
            v = _split_heads(F, self.value(x), H)
            scale = 1.0 / math.sqrt(self._units // H)
            ctx = _masked_attention(F, q, k, v, scale, H, mask,
                                    self.dropout)
            return self.proj(_merge_heads(F, ctx, H))


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        self.ffn1 = nn.Dense(hidden_size, flatten=False)
        self.ffn2 = nn.Dense(units, flatten=False)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x):
        from .. import ndarray as F
        with _costs.part("ffn"):
            h = F.LeakyReLU(self.ffn1(x), act_type="gelu")
            if self.dropout is not None:
                h = self.dropout(h)
            return self.ffn2(h)


class TransformerEncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 seq_parallel=None, **kwargs):
        super().__init__(**kwargs)
        self.attn = MultiHeadAttention(units, num_heads, dropout,
                                       seq_parallel=seq_parallel)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        h = self.attn(x, mask)
        if self.dropout is not None:
            h = self.dropout(h)
        with _costs.part("proj"):
            x = self.ln1(x + h)
        h = self.ffn(x)
        if self.dropout is not None:
            h = self.dropout(h)
        with _costs.part("ffn"):
            return self.ln2(x + h)


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, seq_parallel=None, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerEncoderLayer(
                units, hidden_size, num_heads, dropout,
                seq_parallel=seq_parallel))

    def forward(self, x, mask=None):
        for layer in self.layers._children.values():
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Token + position embeddings → encoder → MLM head."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 dropout=0.1, seq_parallel=None, output_hidden=False,
                 **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._max_length = max_length
        self.word_embed = nn.Embedding(vocab_size, units)
        self.pos_embed = nn.Embedding(max_length, units)
        self.ln = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout) if dropout else None
        self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                          num_heads, dropout,
                                          seq_parallel=seq_parallel)
        self.mlm_dense = nn.Dense(units, flatten=False, activation=None)
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        # output_hidden: stop after the MLM transform and let
        # FusedMLMCELoss own the vocab projection — the (B·T, vocab)
        # logits then never materialise (see _fused_linear_softmax_ce)
        self.decoder = None if output_hidden \
            else nn.Dense(vocab_size, flatten=False)

    def forward(self, tokens):
        from .. import ndarray as F
        _check_max_length(tokens, self._max_length, "BERT")
        with _costs.part("embed"):
            pos = _position_ids(F, tokens)
            x = self.word_embed(tokens) + self.pos_embed(pos)
            x = self.ln(x)
            if self.dropout is not None:
                x = self.dropout(x)
        x = self.encoder(x)
        with _costs.part("head"):
            h = F.LeakyReLU(self.mlm_dense(x), act_type="gelu")
            h = self.mlm_ln(h)
            return h if self.decoder is None else self.decoder(h)


class FusedMLMCELoss(HybridBlock):
    """Vocab projection fused into the softmax-CE loss, chunked over
    rows so the (B·T, vocab) logits never materialise (the LM-head
    memory wall; ref: the reference fused SoftmaxOutput for the same
    reason, one matmul earlier).  Owns the projection params — pair
    with ``BERTModel(output_hidden=True)``.

    forward(h, label): h (B, T, D) or (N, D); label (B, T) or (N,).
    Returns per-row loss (N,).
    """

    def __init__(self, vocab_size, in_units, num_chunks=0,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._vocab = vocab_size
        self._nchunk = num_chunks
        self.weight = self.params.get(
            "weight", shape=(vocab_size, in_units), dtype=dtype,
            allow_deferred_init=True)
        self.bias = self.params.get(
            "bias", shape=(vocab_size,), dtype=dtype, init="zeros",
            allow_deferred_init=True)

    def hybrid_forward(self, F, h, label, weight, bias):
        # (B, T, D) → (B·T, D): -3 merges the leading two dims, -2
        # keeps the rest (ref reshape special codes).  Symbols carry no
        # shape, so the symbolic trace assumes the 3-D (B, T, D) form;
        # already-flat (N, D) arrays pass through on the ndarray path.
        with _costs.part("head"):
            h2 = h if getattr(h, "ndim", 3) == 2 \
                else F.reshape(h, (-3, -2))
            l1 = F.reshape(label, (-1,))
            return F._fused_linear_softmax_ce(h2, weight, bias, l1,
                                              num_chunks=self._nchunk)


def bert_base(vocab_size=30522, **kwargs):
    return BERTModel(vocab_size=vocab_size, units=768, hidden_size=3072,
                     num_layers=12, num_heads=12, **kwargs)


def bert_small(vocab_size=1000, **kwargs):
    kwargs.setdefault("units", 64)
    kwargs.setdefault("hidden_size", 128)
    kwargs.setdefault("num_layers", 2)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("max_length", 128)
    return BERTModel(vocab_size=vocab_size, **kwargs)


def _position_ids(F, tokens):
    """(B, T) tokens → (T,) position indices, symbol-traceable."""
    return F.arange_like(F.reshape(
        F.slice_axis(tokens, axis=0, begin=0, end=1), (-1,)))


def _check_max_length(tokens, max_length, where):
    """Fail fast when a sequence exceeds the positional table —
    the embedding op's gather otherwise CLAMPS silently (jnp.take
    semantics) and reuses the last position vector."""
    from ..symbol.symbol import Symbol as _Sym
    if not isinstance(tokens, _Sym) and tokens.shape[1] > max_length:
        raise ValueError(
            "%s sequence length %d exceeds max_length=%d (positional "
            "embedding table)" % (where, tokens.shape[1], max_length))


def _split_heads(F, t, num_heads):
    """(B, T, C) → (B·H, T, d), shape-free F.* form (reshape codes
    only — keeps every attention block symbol-traceable)."""
    t = F.reshape(t, (0, 0, num_heads, -1))
    t = F.transpose(t, axes=(0, 2, 1, 3))
    return F.reshape(t, (-3, 0, 0))


def _merge_heads(F, t, num_heads):
    """(B·H, T, d) → (B, T, C), shape-free F.* form."""
    t = F.reshape(t, (-4, -1, num_heads, 0, 0))
    t = F.transpose(t, axes=(0, 2, 1, 3))
    return F.reshape(t, (0, 0, -3))


def _scaled_dot_attention(F, q, k, v, scale, dropout=None):
    """The ONE unfused attention body shared by MultiHeadAttention's
    fallback and CrossAttention: softmax(q kᵀ · scale) v."""
    with _costs.part("attn"):
        scores = F.batch_dot(q, k, transpose_b=True) * scale
        attn = F.softmax(scores, axis=-1)
        if dropout is not None:
            attn = dropout(attn)
        return F.batch_dot(attn, v)


def _masked_attention(F, q, k, v, scale, num_heads, mask, dropout=None):
    """`_scaled_dot_attention` under an additive `mask` that broadcasts
    over (B, H, Tq, Tk); without one, that function itself."""
    if mask is None:
        return _scaled_dot_attention(F, q, k, v, scale, dropout)
    with _costs.part("attn"):
        scores = F.batch_dot(q, k, transpose_b=True) * scale
        scores = F.reshape(scores, (-4, -1, num_heads, 0, 0)) + mask
        attn = F.reshape(F.softmax(scores, axis=-1), (-3, 0, 0))
        if dropout is not None:
            attn = dropout(attn)
        return F.batch_dot(attn, v)


class CrossAttention(HybridBlock):
    """Encoder-decoder attention: queries from the decoder stream,
    keys/values from the encoder memory (ref: Sockeye transformer
    decoder's source attention; the contrib
    interleaved_matmul_encdec_* ops are the reference's fused form).
    Shape-free throughout — symbol-traceable."""

    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._scale = 1.0 / math.sqrt(units // num_heads)
        self.query = nn.Dense(units, flatten=False, use_bias=True)
        self.key = nn.Dense(units, flatten=False, use_bias=True)
        self.value = nn.Dense(units, flatten=False, use_bias=True)
        self.proj = nn.Dense(units, flatten=False, use_bias=True)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, memory, mem_mask=None):
        """mem_mask: optional additive mask (B, 1, 1, T_mem) — 0 keep,
        large-negative for source padding."""
        from .. import ndarray as F
        H = self._num_heads
        with _costs.part("proj"):
            q = _split_heads(F, self.query(x), H)
            k = _split_heads(F, self.key(memory), H)
            v = _split_heads(F, self.value(memory), H)
            ctx = _masked_attention(F, q, k, v, self._scale, H, mem_mask,
                                    self.dropout)
            return self.proj(_merge_heads(F, ctx, H))


class _CausalSelfAttention(MultiHeadAttention):
    """Decoder self-attention: the fused flash kernel runs with
    causal=True — no (T, T) mask tensor is ever built.  Attention-prob
    dropout is NOT applied on this fused path (same contract as the
    seq_parallel ring path; residual/FFN dropout still applies) — a
    construction-time warning says so."""

    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(units, num_heads, dropout, **kwargs)
        if dropout:
            import warnings
            warnings.warn(
                "_CausalSelfAttention: attention-prob dropout is not "
                "applied on the fused causal path (residual/FFN "
                "dropout still applies)")

    def forward(self, x, mask=None):
        from .. import ndarray as F
        if mask is not None:
            raise ValueError("_CausalSelfAttention builds its causal "
                             "mask inside the fused kernel; mask= is "
                             "not supported")
        with _costs.part("proj"):
            q, k, v = self.query(x), self.key(x), self.value(x)
            with _costs.part("attn"):
                ctx = F._contrib_flash_attention(
                    q, k, v, num_heads=self._num_heads, causal=True)
            return self.proj(ctx)


class TransformerDecoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self.self_attn = _CausalSelfAttention(units, num_heads, dropout)
        self.cross_attn = CrossAttention(units, num_heads, dropout)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ln3 = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, memory, mem_mask=None):
        h = self.self_attn(x)
        if self.dropout is not None:
            h = self.dropout(h)
        with _costs.part("proj"):
            x = self.ln1(x + h)
        h = self.cross_attn(x, memory, mem_mask)
        if self.dropout is not None:
            h = self.dropout(h)
        with _costs.part("proj"):
            x = self.ln2(x + h)
        h = self.ffn(x)
        if self.dropout is not None:
            h = self.dropout(h)
        with _costs.part("ffn"):
            return self.ln3(x + h)


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(TransformerDecoderLayer(
                units, hidden_size, num_heads, dropout))

    def forward(self, x, memory, mem_mask=None):
        for layer in self.layers._children.values():
            x = layer(x, memory, mem_mask)
        return x


class TransformerNMT(HybridBlock):
    """Encoder-decoder Transformer for NMT (BASELINE config 4's second
    half — ref: Sockeye's transformer model over the reference's
    contrib interleaved_matmul_* fused attention ops).

    forward(src_tokens, tgt_tokens) → (B, T_tgt, tgt_vocab) logits,
    teacher-forced: tgt is the decoder input (shifted target), causal
    self-attention via the Pallas flash kernel.  With
    ``output_hidden=True`` the vocab projection is omitted and forward
    returns (B, T_tgt, units) hidden states — pair with
    ``FusedMLMCELoss(tgt_vocab, units)`` so the logits never
    materialise."""

    def __init__(self, src_vocab, tgt_vocab, units=512, hidden_size=2048,
                 num_layers=6, num_heads=8, max_length=1024,
                 dropout=0.1, output_hidden=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._num_heads = num_heads
        self._max_length = max_length
        self.src_embed = nn.Embedding(src_vocab, units)
        self.tgt_embed = nn.Embedding(tgt_vocab, units)
        self.pos_embed = nn.Embedding(max_length, units)
        self.enc_ln = nn.LayerNorm(in_channels=units)
        self.dec_ln = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout) if dropout else None
        self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                          num_heads, dropout)
        self.decoder = TransformerDecoder(num_layers, units, hidden_size,
                                          num_heads, dropout)
        # output_hidden: pair with FusedMLMCELoss(tgt_vocab, units) so
        # the (B·T, tgt_vocab) logits never materialise (see BERTModel)
        self.out_proj = None if output_hidden \
            else nn.Dense(tgt_vocab, flatten=False)

    def _embed(self, embed, ln, tokens):
        from .. import ndarray as F
        _check_max_length(tokens, self._max_length, "NMT")
        with _costs.part("embed"):
            x = embed(tokens) * math.sqrt(self._units) + \
                self.pos_embed(_position_ids(F, tokens))
            x = ln(x)
            if self.dropout is not None:
                x = self.dropout(x)
            return x

    def forward(self, src, tgt, src_valid_length=None):
        """src_valid_length: optional (B,) source lengths — padding
        positions are masked out of the cross-attention (two identical
        sentences padded to different lengths produce identical
        logits)."""
        from .. import ndarray as F
        mem_mask = None
        if src_valid_length is not None:
            steps = F.reshape(_position_ids(F, src), (1, -1))  # (1, Ts)
            keep = F.broadcast_lesser(
                steps, F.reshape(src_valid_length, (-1, 1)))   # (B, Ts)
            mem_mask = F.expand_dims(F.expand_dims(
                (keep - 1.0) * 1e9, axis=1), axis=1)  # (B,1,1,Ts)
        # the SAME additive mask keeps pads out of the encoder's
        # self-attention (valid rows must not depend on pad content)
        # and out of the decoder's cross-attention
        memory = self.encoder(self._embed(self.src_embed, self.enc_ln,
                                          src), mask=mem_mask)
        h = self.decoder(self._embed(self.tgt_embed, self.dec_ln, tgt),
                         memory, mem_mask)
        with _costs.part("head"):
            return h if self.out_proj is None else self.out_proj(h)


def _mem_mask_for(F, src, src_valid_len):
    """The additive (B, 1, 1, Ts) source-padding mask `forward` builds
    from src_valid_length — ONE definition shared with the cache
    path."""
    B = src.shape[0]
    steps = F.reshape(_position_ids(F, src), (1, -1))       # (1, Ts)
    keep = F.broadcast_lesser(
        steps, F.reshape(src_valid_len, (-1, 1)))           # (B, Ts)
    return F.expand_dims(F.expand_dims((keep - 1.0) * 1e9,
                                       axis=1), axis=1)


# -- explicit-cache decode (serving.generation contract) ---------------
# TransformerNMT grows init_cache/decode_step.  Per decoder layer the
# cache holds self-attention K/V rows for `max_len` positions and the
# cross-attention K/V precomputed from the encoder memory at prefill,
# every leaf slot-major and in the layout a step's attention reads:
# (B, G, T, W), G groups of P heads whose d-wide rows lie side by side
# in one W = P·d wide row (`_lane_heads`).  A step writes the one new
# row of every live slot at the slot's own position into the donated
# leaf (continuous batching = slots at DIFFERENT positions in one
# fixed-shape executable) and contracts over the leaves as they
# lie, so it reads each byte of the cache once and copies none.
# Padding is exactly neutral: attention masks underflow pad weights to
# 0 and every other op is position-wise.

_LANES = 128    # the width of a TPU vector register's minor dimension


def _lane_heads(num_heads, head_dim):
    """How many heads share one cache row: the most that divide
    `num_heads` and fit `_LANES`.  A (…, T, d) leaf with d = 64 is not
    kept that way on a TPU: XLA puts T on the lanes, and writing one
    position then rewrites whole tiles (30 ms a step for `nmt_base`'s
    twelve leaves against 1.8 ms for full-lane rows; PERF.md, PR 31)."""
    p = max(1, min(num_heads, _LANES // head_dim))
    while num_heads % p:
        p -= 1
    return p


def _cache_rows(F, t, groups):
    """(B, T, U) → (B, G, T, U/G): the cache's layout."""
    with _costs.part("cache"):
        return F.transpose(F.reshape(t, (0, 0, groups, -1)),
                           axes=(0, 2, 1, 3))


def _nmt_init_cache(self, src, src_valid_len, max_len, mem_len=None):
    """Prefill: run the encoder over `src` (B, Ts) with the padding
    mask, precompute each decoder layer's cross-attention K/V, and
    allocate zeroed self-attention K/V buffers for `max_len` decode
    positions, all in the cache's (B, G, T, W) layout.  `mem_len` pads
    the memory axis so every prompt bucket produces ONE decode
    signature."""
    from .. import ndarray as F
    B = src.shape[0]
    Ts = src.shape[1]
    H = self._num_heads
    G = H // _lane_heads(H, self._units // H)
    mem_mask = _mem_mask_for(F, src, src_valid_len)
    memory = self.encoder(self._embed(self.src_embed, self.enc_ln,
                                      src), mask=mem_mask)  # (B, Ts, U)
    if mem_len is not None and int(mem_len) > int(Ts):
        memory = F.concat(
            memory, F.zeros((B, int(mem_len) - int(Ts), self._units)),
            dim=1)
    with _costs.part("cache"):
        cache = {"src_len": src_valid_len.reshape((-1,)),
                 "counts": F.zeros((B, 1), dtype="int32")}
        zeros = F.zeros((B, G, int(max_len), self._units // G))
    for i, layer in enumerate(self.decoder.layers._children.values()):
        ca = layer.cross_attn
        with _costs.part("proj"):       # `_cache_rows` is `cache`
            cache["mem_k%d" % i] = _cache_rows(F, ca.key(memory), G)
            cache["mem_v%d" % i] = _cache_rows(F, ca.value(memory), G)
        cache["k%d" % i] = zeros                            # (B, G, L, W)
        cache["v%d" % i] = zeros
    return cache


def _nmt_decode_step(self, tok, pos, cache, live):
    """One decode step: token `tok` (B,) at target position `pos`
    (B,) against the cached K/V; `live` (B,) bool says which slots
    hold a stream.  Returns (logits (B, V), updated cache).  Each
    layer's new K/V row of every live slot is written at the slot's own
    position by `ops.attention.live_rows_write` (a position outside the
    cache writes nothing; a dead slot's rows may be written or not,
    nothing reads them), then `ops.attention.decode_attention` reads the
    leaves as they lie, a live slot's rows up to its position
    (self-attention: one query row per slot, each at its OWN position —
    the continuous-batching point)
    or its source length (cross-attention), and nothing of the others:
    no cache leaf is reshaped, transposed or rewritten.  The logits of
    a slot that is not live are finite and mean nothing.  The `counts`
    leaf says how many cached rows the step covered for each slot in
    one layer, self + memory (`step_counts`)."""
    import jax.numpy as jnp
    from ..ndarray.ndarray import NDArray
    from ..ops.attention import (decode_attention, decode_rows_read,
                                 live_rows_plan, live_rows_write)
    H, U = self._num_heads, self._units
    d = U // H
    P = _lane_heads(H, d)
    B, G, L, W = cache["k0"].shape
    scale = 1.0 / math.sqrt(d)
    with _costs.part("embed"):
        x = self.tgt_embed(tok.reshape((-1, 1))) * math.sqrt(U) \
            + self.pos_embed(pos.reshape((-1, 1)))          # (B, 1, U)
        x = self.dec_ln(x)
    live = live._data
    self_len = jnp.where(live, pos._data + 1, 0).astype(jnp.int32)
    mem_len = jnp.where(live, cache["src_len"]._data, 0).astype(jnp.int32)
    with _costs.part("cache"):
        plan = live_rows_plan(pos._data, live, L)     # once for all layers
    new_cache = dict(cache)

    def _write(i, k_new, v_new):
        k, v = live_rows_write(cache["k%d" % i]._data, cache["v%d" % i]._data,
                               k_new._data.reshape(B, G, W),
                               v_new._data.reshape(B, G, W), plan)
        kc = new_cache["k%d" % i] = NDArray(k)
        vc = new_cache["v%d" % i] = NDArray(v)
        return kc, vc

    def _attend(q, k, v, lengths):
        # the P heads of a group lie side by side on the row's W lanes,
        # in the query as in the leaves
        with _costs.part("attn"):
            ctx = decode_attention(q._data.reshape(B, G, W), k._data,
                                   v._data, lengths, heads=P, scale=scale)
            return NDArray(ctx.reshape(B, 1, U))

    for i, layer in enumerate(self.decoder.layers._children.values()):
        # a layer's two mixers are `proj` but for what `_write` (`cache`)
        # and `_attend` (`attn`) name themselves
        with _costs.part("proj"):
            sa = layer.self_attn
            kc, vc = _write(i, sa.key(x), sa.value(x))
            x = layer.ln1(x + sa.proj(_attend(sa.query(x), kc, vc,
                                              self_len)))
            ca = layer.cross_attn
            ctx = _attend(ca.query(x), cache["mem_k%d" % i],
                          cache["mem_v%d" % i], mem_len)
            x = layer.ln2(x + ca.proj(ctx))
        with _costs.part("ffn"):
            x = layer.ln3(x + layer.ffn(x))
    if self.out_proj is None:
        raise ValueError("decode_step needs the vocab projection "
                         "(build TransformerNMT without "
                         "output_hidden=True for generation)")
    with _costs.part("cache"):
        new_cache["counts"] = NDArray((
            decode_rows_read(self_len, cache["k0"]._data)
            + decode_rows_read(mem_len, cache["mem_k0"]._data))[:, None])
    with _costs.part("head"):
        return self.out_proj(x).reshape((0, -1)), new_cache


# what `counts` counts, a column a name: the engine adds each, summed over
# the live slots, to the counter of that name once a step
TransformerNMT.step_counts = ("gen.attn_rows_read",)
TransformerNMT.init_cache = _nmt_init_cache
TransformerNMT.decode_step = _nmt_decode_step


def transformer_nmt_base(src_vocab, tgt_vocab, **kwargs):
    """Sockeye/"base" geometry: 6+6 layers, 512 units, 8 heads."""
    return TransformerNMT(src_vocab, tgt_vocab, units=512,
                          hidden_size=2048, num_layers=6, num_heads=8,
                          **kwargs)


def transformer_nmt_small(src_vocab, tgt_vocab, **kwargs):
    kwargs.setdefault("units", 64)
    kwargs.setdefault("hidden_size", 128)
    kwargs.setdefault("num_layers", 2)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("max_length", 128)
    return TransformerNMT(src_vocab, tgt_vocab, **kwargs)
