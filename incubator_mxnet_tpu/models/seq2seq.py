"""GNMT-style LSTM seq2seq NMT (BASELINE config 4).

Parity target: Sockeye's GNMT config on the reference — multi-layer
LSTM encoder, LSTM decoder with dot attention over encoder states,
trained with the bucketing executor (ref: the reference provides the
fused RNN op src/operator/rnn.cc + BucketingModule
python/mxnet/module/bucketing_module.py; Sockeye assembles them).

Two assemblies here:
- `Seq2Seq` (Gluon): imperative/hybridizable encoder-decoder with
  attention; bucketing happens naturally through the jit cache (one
  executable per padded length — the TPU realisation of per-bucket
  executors sharing memory).
- `gnmt_sym_gen`: a Symbol generator for the legacy BucketingModule
  path (the literal Sockeye mechanism), used by tests to exercise
  switch_bucket.
"""
from __future__ import annotations

from ..gluon.block import HybridBlock
from ..gluon import nn, rnn

__all__ = ["Seq2Seq", "GNMT", "gnmt_large", "gnmt_sym_gen"]


class Seq2Seq(HybridBlock):
    """Encoder-decoder with dot attention, teacher-forced training.

    src/tgt: (B, T) int token ids ((B, Ts) and (B, Tt) may differ).
    Returns logits (B, Tt, vocab)."""

    def __init__(self, src_vocab, tgt_vocab, embed_dim=32, hidden=64,
                 num_layers=2, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        self.src_embed = nn.Embedding(src_vocab, embed_dim)
        self.tgt_embed = nn.Embedding(tgt_vocab, embed_dim)
        # TNC layout matches the fused RNN op's native layout
        self.encoder = rnn.LSTM(hidden, num_layers=num_layers,
                                layout="TNC")
        self.decoder = rnn.LSTM(hidden, num_layers=num_layers,
                                layout="TNC")
        self.att_dense = nn.Dense(hidden, flatten=False, use_bias=False)
        self.proj = nn.Dense(tgt_vocab, flatten=False)

    def forward(self, src, tgt):
        from .. import ndarray as F
        enc_in = self.src_embed(src).transpose((1, 0, 2))     # (Ts, B, E)
        B = src.shape[0]
        enc_out, enc_states = self.encoder(
            enc_in, self.encoder.begin_state(batch_size=B))   # (Ts, B, H)
        dec_in = self.tgt_embed(tgt).transpose((1, 0, 2))     # (Tt, B, E)
        # GNMT: the decoder recurrence starts from the encoder's final
        # (h, c) so source information flows through the state path,
        # not only through the attention readout
        dec_out, _ = self.decoder(dec_in, enc_states)         # (Tt, B, H)
        # dot attention: every decoder step attends over encoder states
        q = dec_out.transpose((1, 0, 2))                      # (B, Tt, H)
        k = enc_out.transpose((1, 0, 2))                      # (B, Ts, H)
        scores = F.batch_dot(q, k, transpose_b=True)          # (B, Tt, Ts)
        attn = F.softmax(scores, axis=-1)
        ctx = F.batch_dot(attn, k)                            # (B, Tt, H)
        mix = self.att_dense(ctx) + q
        return self.proj(mix)                                 # (B, Tt, V)

    # -- explicit-cache decode (serving.generation contract) -----------
    # Every cache leaf is SLOT-MAJOR (axis 0 = request/slot), so the
    # GenerationEngine's join is an indexed in-place write of the
    # slot's row along that axis.  Exactness under right-padding: RNN_varlen freezes the
    # encoder recurrence at src_valid_len (the decoder init state is
    # the state AT the prompt's real end, not after the pad tail), the
    # zeroed pad outputs are additionally masked out of the attention
    # softmax with -1e9 (exp underflows to exactly 0), so a padded
    # prompt decodes token-identically to the unpadded forward —
    # the contrib.text.decode greedy-parity oracle rides on this.

    def init_cache(self, src, src_valid_len, max_len=None, mem_len=None):
        """Prefill: encode `src` (B, Ts) int ids with valid lengths
        `src_valid_len` (B,) and return the decode cache.  `mem_len`
        pads the attention memory out to a fixed length so every
        prompt bucket yields ONE decode-executable signature
        (`max_len` is unused — LSTM decode state is O(1) in emitted
        tokens).  Leaves: enc_k (B, M, H), src_len (B,), h/c
        (B, L, H)."""
        from .. import ndarray as F
        B = src.shape[0]
        Ts = src.shape[1]
        enc_in = self.src_embed(src).transpose((1, 0, 2))   # (Ts, B, E)
        s0 = self.encoder.begin_state(batch_size=B)
        enc_out, h, c = F.RNN_varlen(
            enc_in, self.encoder.parameters.data(), s0[0], s0[1],
            src_valid_len, state_size=self._hidden,
            num_layers=self.encoder._num_layers, mode="lstm")
        k = enc_out.transpose((1, 0, 2))                    # (B, Ts, H)
        if mem_len is not None and int(mem_len) > int(Ts):
            k = F.concat(k, F.zeros((B, int(mem_len) - int(Ts),
                                     self._hidden)), dim=1)
        return {"enc_k": k,
                "src_len": src_valid_len.reshape((-1,)),
                "h": h.transpose((1, 0, 2)),                # (B, L, H)
                "c": c.transpose((1, 0, 2))}

    def decode_step(self, tok, pos, cache, live):
        """One decode step: feed token `tok` (B,) at target position
        `pos` (B,; unused — LSTM state carries position) and return
        (next-token logits (B, V), updated cache).  `live` (B,; which
        slots hold a stream) is unused: a step costs the same for a slot
        nobody sits in."""
        from .. import ndarray as F
        x = self.tgt_embed(tok.reshape((-1, 1)))            # (B, 1, E)
        x = x.transpose((1, 0, 2))                          # (1, B, E)
        states = [cache["h"].transpose((1, 0, 2)),
                  cache["c"].transpose((1, 0, 2))]
        dec_out, new_states = self.decoder(x, states)       # (1, B, H)
        q = dec_out.transpose((1, 0, 2))                    # (B, 1, H)
        k = cache["enc_k"]                                  # (B, M, H)
        scores = F.batch_dot(q, k, transpose_b=True)        # (B, 1, M)
        M = k.shape[1]
        steps = F.arange(0, M).reshape((1, 1, M))
        invalid = steps >= cache["src_len"].reshape((-1, 1, 1))
        attn = F.softmax(scores + invalid * -1e9, axis=-1)
        ctx = F.batch_dot(attn, k)                          # (B, 1, H)
        mix = self.att_dense(ctx) + q
        logits = self.proj(mix)                             # (B, 1, V)
        new_cache = dict(cache)
        new_cache["h"] = new_states[0].transpose((1, 0, 2))
        new_cache["c"] = new_states[1].transpose((1, 0, 2))
        return logits.reshape((0, -1)), new_cache


class GNMT(HybridBlock):
    """GNMT-architecture LSTM seq2seq at reference geometry (BASELINE
    config 4 headline model; the small `Seq2Seq` above stays as the
    test/smoke model).

    Parity target: the Sockeye GNMT config on the reference — a
    bidirectional bottom encoder layer, residual unidirectional layers
    above it, a deep unidirectional decoder initialised from the
    encoder state, and Luong dot attention over encoder outputs (ref:
    Sockeye GNMT config over the reference's fused RNN op
    src/operator/rnn.cc; GNMT paper arch — bi bottom layer, residuals
    from the 3rd layer).

    TPU-first notes: every LSTM layer is one `lax.scan` over the fused
    RNN op (gates batched into a single (B, 4H) matmul per step — MXU-
    shaped at large batch); attention is two batched matmuls; with
    ``output_hidden=True`` the vocab projection is fused into the
    chunked softmax-CE (`FusedMLMCELoss`) so the (B·T, 32k) logits
    never materialise.

    src/tgt: (B, Ts)/(B, Tt) int ids.  Returns logits (B, Tt, vocab),
    or the pre-projection mix (B, Tt, H) with ``output_hidden=True``.
    ``src_valid_len`` (B,) optionally masks attention over source pad
    positions.
    """

    def __init__(self, src_vocab, tgt_vocab, embed_dim=1024, hidden=1024,
                 enc_layers=4, dec_layers=4, output_hidden=False,
                 **kwargs):
        super().__init__(**kwargs)
        assert enc_layers >= 2, "GNMT: bi bottom layer + >=1 uni layer"
        self._hidden = hidden
        self._dec_layers = dec_layers
        self._output_hidden = output_hidden
        self.src_embed = nn.Embedding(src_vocab, embed_dim)
        self.tgt_embed = nn.Embedding(tgt_vocab, embed_dim)
        # bottom layer reads the source in both directions
        self.enc_bi = rnn.LSTM(hidden, num_layers=1, bidirectional=True,
                               layout="TNC")
        # unidirectional stack above it; residual adds once widths
        # match (GNMT: residuals from the 3rd layer)
        self._uni = []
        for i in range(enc_layers - 1):
            layer = rnn.LSTM(hidden, num_layers=1, layout="TNC")
            setattr(self, "enc_uni%d" % i, layer)
            self._uni.append(layer)
        self.decoder = rnn.LSTM(hidden, num_layers=dec_layers,
                                layout="TNC")
        self.att_dense = nn.Dense(hidden, flatten=False, use_bias=False)
        if not output_hidden:
            self.proj = nn.Dense(tgt_vocab, flatten=False)

    def forward(self, src, tgt, src_valid_len=None):
        from .. import ndarray as F
        B = src.shape[0]
        x = self.src_embed(src).transpose((1, 0, 2))        # (Ts, B, E)
        h, _ = self.enc_bi(x, self.enc_bi.begin_state(batch_size=B))
        states = None                                       # (Ts, B, 2H)
        for i, layer in enumerate(self._uni):
            out, states = layer(h, layer.begin_state(batch_size=B))
            # first uni layer narrows 2H -> H (no residual possible)
            h = out if i == 0 else out + h
        # decoder recurrence starts from the top encoder layer's final
        # (h, c), tiled across decoder layers, so source information
        # flows through the state path as well as the attention readout
        dh = F.concat(*([states[0]] * self._dec_layers), dim=0)
        dc = F.concat(*([states[1]] * self._dec_layers), dim=0)
        d_in = self.tgt_embed(tgt).transpose((1, 0, 2))     # (Tt, B, E)
        dec_out, _ = self.decoder(d_in, [dh, dc])           # (Tt, B, H)
        q = dec_out.transpose((1, 0, 2))                    # (B, Tt, H)
        k = h.transpose((1, 0, 2))                          # (B, Ts, H)
        scores = F.batch_dot(q, k, transpose_b=True) \
            * (1.0 / float(self._hidden) ** 0.5)            # (B, Tt, Ts)
        if src_valid_len is not None:
            # additive -1e9 over source pad columns
            Ts = k.shape[1]
            pos = F.arange(0, Ts).reshape((1, 1, Ts))
            invalid = pos >= src_valid_len.reshape((B, 1, 1))
            scores = scores + invalid * -1e9
        attn = F.softmax(scores, axis=-1)
        ctx = F.batch_dot(attn, k)                          # (B, Tt, H)
        mix = self.att_dense(ctx) + q
        if self._output_hidden:
            return mix
        return self.proj(mix)                               # (B, Tt, V)


def gnmt_large(src_vocab=32000, tgt_vocab=32000, **kwargs):
    """Config-4 headline geometry: 4x1024 encoder (bi bottom), 4x1024
    decoder, 1024 embeddings, 32k vocab (~175M params)."""
    kwargs.setdefault("embed_dim", 1024)
    kwargs.setdefault("hidden", 1024)
    kwargs.setdefault("enc_layers", 4)
    kwargs.setdefault("dec_layers", 4)
    return GNMT(src_vocab, tgt_vocab, **kwargs)


def gnmt_sym_gen(vocab, embed_dim=32, hidden=64, num_layers=1):
    """Symbol generator for BucketingModule (ref: example/rnn/bucketing
    sym_gen + Sockeye's bucketing executor): bucket_key = sequence
    length; graph = Embedding → fused RNN(LSTM) → FC → SoftmaxOutput."""
    from .. import symbol as sym
    from ..ops.rnn import rnn_param_size

    def sym_gen(seq_len):
        data = sym.var("data")            # (B, T) ids
        label = sym.var("softmax_label")  # (B, T) next-token ids
        embed_w = sym.var("embed_weight", shape=(vocab, embed_dim))
        emb = sym.Embedding(data, embed_w, input_dim=vocab,
                            output_dim=embed_dim)
        tnc = sym.transpose(emb, axes=(1, 0, 2))       # (T, B, E)
        params = sym.var("rnn_params",
                         shape=(rnn_param_size("lstm", num_layers,
                                               embed_dim, hidden),))
        # batch-size-agnostic zero initial states built from the data
        # (the bucketing executor rebinds per bucket, so no var can
        # carry a batch dimension)
        zeros_tb1 = sym.slice_axis(sym.sum(emb, axis=2, keepdims=True)
                                   * 0.0, axis=1, begin=0, end=1)
        z1 = sym.transpose(zeros_tb1, axes=(1, 0, 2))  # (1, B, 1)
        init = sym.broadcast_axis(z1, axis=(2,), size=(hidden,))
        if num_layers > 1:
            init = sym.tile(init, reps=(num_layers, 1, 1))
        rnn_out = sym.RNN(tnc, params, init, init, mode="lstm",
                          state_size=hidden, num_layers=num_layers)
        btc = sym.transpose(rnn_out[0], axes=(1, 0, 2))
        fc_w = sym.var("fc_weight", shape=(vocab, hidden))
        fc_b = sym.var("fc_bias", shape=(vocab,))
        logits = sym.FullyConnected(
            sym.reshape(btc, shape=(-1, hidden)), fc_w, fc_b,
            num_hidden=vocab)
        out_sym = sym.SoftmaxOutput(logits,
                                    sym.reshape(label, shape=(-1,)))
        return out_sym, ["data"], ["softmax_label"]

    return sym_gen
