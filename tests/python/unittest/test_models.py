"""Model-level convergence smokes (ref: tests/python/train/ — small
end-to-end training with an accuracy/loss threshold)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, autograd as ag


def test_bert_mlm_convergence_smoke():
    """Tiny BERT overfits a fixed batch: MLM loss must drop sharply.
    (ref model: BASELINE config 2, BERT-base MLM pretrain.)"""
    from incubator_mxnet_tpu.models.transformer import bert_small
    vocab = 64
    net = bert_small(vocab_size=vocab, units=32, hidden_size=64,
                     num_layers=2, num_heads=4, max_length=16, dropout=0.0)
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    rs = np.random.RandomState(0)
    B, T = 4, 16
    tokens = nd.array(rs.randint(0, vocab, (B, T)).astype(np.int32),
                      dtype="int32")
    labels = nd.array(rs.randint(0, vocab, (B, T)).astype(np.float32))

    losses = []
    for _ in range(60):
        with ag.record():
            logits = net(tokens)
            l = loss_fn(logits.reshape((B * T, -1)), labels.reshape((-1,)))
            l.backward()
        trainer.step(B)
        losses.append(float(l.asnumpy().mean()))
    assert losses[-1] < losses[0] * 0.5, \
        "MLM loss did not converge: %s -> %s" % (losses[0], losses[-1])
    # quality threshold, not just loss movement (ref:
    # tests/python/train asserts accuracy > threshold)
    pred = net(tokens).reshape((B * T, -1)).asnumpy().argmax(axis=1)
    acc = float((pred == labels.asnumpy().reshape(-1)).mean())
    assert acc >= 0.9, "MLM train accuracy %.3f < 0.9" % acc


def test_resnet_classification_convergence_smoke():
    """8-class toy images; resnet18 trains above chance quickly
    (ref: tests/python/train/test_conv.py MNIST convergence smoke)."""
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet18_v1
    net = resnet18_v1(classes=8)
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    rs = np.random.RandomState(1)
    B = 16
    # separable data: class k has mean k in channel 0
    y = rs.randint(0, 8, B)
    x = rs.randn(B, 3, 32, 32).astype(np.float32) * 0.1
    x[:, 0] += y[:, None, None]
    xb, yb = nd.array(x), nd.array(y.astype(np.float32))
    first = None
    for i in range(25):
        with ag.record():
            l = loss_fn(net(xb), yb)
            l.backward()
        trainer.step(B)
        if first is None:
            first = float(l.asnumpy().mean())
    last = float(l.asnumpy().mean())
    assert last < first * 0.5, (first, last)
    # accuracy threshold (ref: tests/python/train/test_conv.py asserts
    # final train accuracy > 0.93 on MNIST; same contract, synthetic).
    # train_mode: batch statistics — predict-mode BN running stats need
    # ~80 steps to catch up (momentum 0.9), which this smoke doesn't run
    with ag.train_mode():
        pred = net(xb).asnumpy().argmax(axis=1)
    acc = float((pred == y).mean())
    assert acc >= 0.93, "train accuracy %.3f < 0.93" % acc


def test_seq2seq_copy_convergence():
    """GNMT-style LSTM seq2seq (config 4) learns the copy task."""
    from incubator_mxnet_tpu.models.seq2seq import Seq2Seq
    vocab = 12
    net = Seq2Seq(vocab, vocab, embed_dim=16, hidden=32, num_layers=1)
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.02})
    rs = np.random.RandomState(0)
    B, T = 8, 6
    src_np = rs.randint(2, vocab, (B, T)).astype(np.float32)
    src = nd.array(src_np)
    # teacher forcing: decoder input = <bos>=1 + shifted target
    dec_in = nd.array(np.concatenate(
        [np.ones((B, 1), np.float32), src_np[:, :-1]], axis=1))
    first = last = None
    for _ in range(60):
        with ag.record():
            logits = net(src, dec_in)
            l = loss_fn(logits.reshape((B * T, -1)),
                        src.reshape((-1,)))
            l.backward()
        trainer.step(B)
        last = float(l.asnumpy().mean())
        if first is None:
            first = last
    assert last < first * 0.3, (first, last)
    # copy-task token accuracy ≥ 0.9 (quality threshold, ref:
    # tests/python/train contract)
    pred = net(src, dec_in).reshape((B * T, -1)).asnumpy().argmax(axis=1)
    tok_acc = float((pred == src_np.reshape(-1)).mean())
    assert tok_acc >= 0.9, "copy-task token accuracy %.3f < 0.9" % tok_acc


def test_gnmt_bucketing_module_training():
    """Config 4's bucketing executor: one LM trained across THREE
    buckets with shared params (ref: example/rnn/bucketing +
    BucketingModule.switch_bucket)."""
    from incubator_mxnet_tpu.models.seq2seq import gnmt_sym_gen
    from incubator_mxnet_tpu.io import DataBatch

    vocab = 16
    gen = gnmt_sym_gen(vocab, embed_dim=8, hidden=16, num_layers=1)
    bm = mx.mod.BucketingModule(gen, default_bucket_key=12)
    bm.bind(data_shapes=[("data", (4, 12))],
            label_shapes=[("softmax_label", (4, 12))])
    bm.init_params()
    bm.init_optimizer(optimizer="adam",
                      optimizer_params={"learning_rate": 0.05})
    rs = np.random.RandomState(1)
    buckets = [6, 9, 12]

    def make_batch(T):
        # predictable next-token sequence: x[t+1] = (x[t] + 1) % vocab
        start = rs.randint(0, vocab, (4, 1))
        seq = (start + np.arange(T + 1)) % vocab
        d = nd.array(seq[:, :-1].astype(np.float32))
        lab = nd.array(seq[:, 1:].astype(np.float32))
        return DataBatch([d], label=[lab], bucket_key=T,
                         provide_data=[("data", (4, T))],
                         provide_label=[("softmax_label", (4, T))])

    losses = []
    for step in range(60):
        batch = make_batch(buckets[step % 3])
        bm.forward(batch, is_train=True)
        out = bm.get_outputs()[0].asnumpy()     # softmax probs (4*T, V)
        lab = batch.label[0].asnumpy().reshape(-1).astype(int)
        losses.append(float(-np.log(
            out[np.arange(len(lab)), lab] + 1e-9).mean()))
        bm.backward()
        bm.update()
    assert len(bm._buckets) == 3                # all buckets compiled
    assert np.mean(losses[-9:]) < np.mean(losses[:3]) * 0.75, \
        (np.mean(losses[:3]), np.mean(losses[-9:]))


def test_wide_deep_accuracy_threshold():
    """Config 5 quality threshold: Wide&Deep separates a synthetic
    feature-presence rule to ≥0.9 train accuracy (ref:
    tests/python/train contract — accuracy, not loss movement)."""
    from incubator_mxnet_tpu.models import wide_deep
    rs = np.random.RandomState(4)
    B, F, V = 64, 8, 200
    idx_np = rs.randint(0, V, (B, F)).astype(np.int32)
    val_np = rs.rand(B, F).astype(np.float32)
    # label: does the row contain any "hot" feature id (< 20)?
    y_np = (idx_np < 20).any(axis=1).astype(np.float32)

    net = wide_deep(num_features=V, embed_dim=8, hidden=(32,))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 5e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    idx, vals, y = (nd.array(idx_np, dtype="int32"), nd.array(val_np),
                    nd.array(y_np))
    for _ in range(80):
        with ag.record():
            l = loss_fn(net(idx, vals), y)
            l.backward()
        trainer.step(B)
    pred = net(idx, vals).asnumpy().argmax(axis=1)
    acc = float((pred == y_np).mean())
    assert acc >= 0.9, "wide&deep train accuracy %.3f < 0.9" % acc


def test_transformer_nmt_forward_and_causality():
    """Config 4's Transformer NMT half (Sockeye transformer): shapes,
    and the decoder is CAUSAL — changing a future target token must
    not change earlier positions' logits."""
    from incubator_mxnet_tpu.models import transformer_nmt_small
    rs = np.random.RandomState(7)
    net = transformer_nmt_small(src_vocab=50, tgt_vocab=60, dropout=0.0)
    net.initialize()
    src = nd.array(rs.randint(0, 50, (2, 9)).astype(np.float32),
                   dtype="int32")
    tgt = rs.randint(0, 60, (2, 8)).astype(np.int32)
    out1 = net(src, nd.array(tgt, dtype="int32")).asnumpy()
    assert out1.shape == (2, 8, 60)
    tgt2 = tgt.copy()
    tgt2[:, 5] = (tgt2[:, 5] + 7) % 60          # mutate a LATER token
    out2 = net(src, nd.array(tgt2, dtype="int32")).asnumpy()
    np.testing.assert_allclose(out1[:, :5], out2[:, :5],
                               rtol=1e-5, atol=1e-5)
    assert np.abs(out1[:, 5:] - out2[:, 5:]).max() > 1e-4


def test_transformer_nmt_copy_task_convergence():
    """Teacher-forced copy task: loss collapses and token accuracy
    passes threshold (the GNMT test's quality contract, transformer
    flavour)."""
    from incubator_mxnet_tpu.models import transformer_nmt_small
    rs = np.random.RandomState(8)
    vocab = 20
    net = transformer_nmt_small(src_vocab=vocab, tgt_vocab=vocab,
                                dropout=0.0)
    net.initialize()
    net.hybridize()
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 3e-3})
    B, T = 8, 8
    src_np = rs.randint(2, vocab, (B, T)).astype(np.int32)
    src = nd.array(src_np, dtype="int32")
    # decoder input = [BOS(=1), y_0..y_{T-2}]; target = src itself
    dec_in = nd.array(
        np.concatenate([np.ones((B, 1), np.int32), src_np[:, :-1]],
                       axis=1), dtype="int32")
    lab = nd.array(src_np.astype(np.float32))
    first = last = None
    for i in range(60):
        with ag.record():
            logits = net(src, dec_in)
            l = loss_fn(logits.reshape((B * T, -1)),
                        lab.reshape((-1,)))
            l.backward()
        trainer.step(B)
        if i == 0:
            first = float(l.asnumpy().mean())
    last = float(l.asnumpy().mean())
    assert last < first * 0.3, (first, last)
    pred = net(src, dec_in).reshape((B * T, -1)).asnumpy().argmax(1)
    acc = float((pred == src_np.reshape(-1)).mean())
    assert acc >= 0.9, acc


def test_transformer_nmt_symbol_traceable():
    """The whole encoder-decoder traces with Symbol inputs (export
    path): shape-free attention helpers, F.* embeddings (review r4)."""
    import warnings
    import incubator_mxnet_tpu.symbol as S
    from incubator_mxnet_tpu.models import transformer_nmt_small
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        net = transformer_nmt_small(src_vocab=20, tgt_vocab=20)
    net.initialize()
    out = net(S.var("src"), S.var("tgt"))
    assert out.tojson()


def test_transformer_nmt_source_padding_invariance():
    """With src_valid_length, PAD rows are masked out of the
    cross-attention: the same sentence padded to different lengths
    yields identical logits (review r4)."""
    import warnings
    from incubator_mxnet_tpu.models import transformer_nmt_small
    rs = np.random.RandomState(9)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        net = transformer_nmt_small(src_vocab=30, tgt_vocab=30,
                                    dropout=0.0)
    net.initialize()
    sent = rs.randint(2, 30, (1, 5)).astype(np.int32)
    tgt = nd.array(rs.randint(2, 30, (1, 6)).astype(np.int32),
                   dtype="int32")
    vlen = nd.array(np.array([5], np.float32))

    def run(pad_to):
        src = np.zeros((1, pad_to), np.int32)
        src[:, :5] = sent
        return net(nd.array(src, dtype="int32"), tgt,
                   src_valid_length=vlen).asnumpy()

    np.testing.assert_allclose(run(8), run(12), rtol=1e-4, atol=1e-4)
    # and WITHOUT the mask the padding leaks (the gap being guarded)
    def run_nomask(pad_to):
        src = np.zeros((1, pad_to), np.int32)
        src[:, :5] = sent
        return net(nd.array(src, dtype="int32"), tgt).asnumpy()
    assert np.abs(run_nomask(8) - run_nomask(12)).max() > 1e-4


def test_transformer_nmt_max_length_guard():
    import warnings
    from incubator_mxnet_tpu.models import transformer_nmt_small
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        net = transformer_nmt_small(src_vocab=20, tgt_vocab=20,
                                    max_length=16)
    net.initialize()
    import pytest as _pytest
    src = nd.array(np.zeros((1, 32), np.int32), dtype="int32")
    tgt = nd.array(np.zeros((1, 8), np.int32), dtype="int32")
    with _pytest.raises(ValueError, match="max_length"):
        net(src, tgt)


def test_transformer_nmt_fused_head_matches_dense():
    """output_hidden + FusedMLMCELoss == dense out_proj + fused CE:
    same loss, same encoder/decoder gradients (r4 head fusion)."""
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import transformer_nmt_small
    from incubator_mxnet_tpu.models.transformer import FusedMLMCELoss

    vocab, B, T = 40, 2, 8
    rs = np.random.RandomState(2)
    src_np = rs.randint(0, vocab, (B, T)).astype("int32")
    tgt_np = rs.randint(0, vocab, (B, T)).astype("int32")
    lab_np = rs.randint(0, vocab, (B, T)).astype("float32")
    w_np = (rs.randn(vocab, 64) * 0.05).astype("float32")

    def run(fused):
        mx.random.seed(9)
        net = transformer_nmt_small(src_vocab=vocab, tgt_vocab=vocab,
                                    dropout=0.0, units=64,
                                    output_hidden=fused)
        net.initialize(force_reinit=True)
        src, tgt = nd.array(src_np, dtype="int32"), \
            nd.array(tgt_np, dtype="int32")
        net(src, tgt)               # materialise deferred params first
        lab = nd.array(lab_np)
        if fused:
            head = FusedMLMCELoss(vocab, 64, num_chunks=2)
            head.initialize()
            head.weight.set_data(nd.array(w_np))
            head.bias.set_data(nd.zeros((vocab,)))
            with ag.record():
                loss = head(net(src, tgt), lab).mean()
                loss.backward()
        else:
            net.out_proj.weight.set_data(nd.array(w_np))
            net.out_proj.bias.set_data(nd.zeros((vocab,)))
            with ag.record():
                logits = net(src, tgt)
                loss = nd._fused_softmax_ce(
                    logits.reshape((B * T, vocab)),
                    lab.reshape((-1,))).mean()
                loss.backward()
        # positional gradient list (auto prefixes differ between the
        # two fresh nets); the dense run drops its out_proj params so
        # both lists cover exactly the encoder/decoder/embeddings
        skip = set()
        if not fused:
            skip = {id(q) for q in net.out_proj.collect_params()
                    .values()}
        grads = [p.grad().asnumpy()
                 for p in net.collect_params().values()
                 if p.grad_req != "null" and id(p) not in skip]
        return float(loss.asscalar()), grads

    loss_d, grads_d = run(False)
    loss_f, grads_f = run(True)
    np.testing.assert_allclose(loss_d, loss_f, rtol=2e-5, atol=2e-5)
    assert len(grads_d) == len(grads_f) > 20
    for i, (gd, gf) in enumerate(zip(grads_d, grads_f)):
        np.testing.assert_allclose(gd, gf, rtol=2e-4, atol=2e-4,
                                   err_msg="grad #%d" % i)


@pytest.mark.slow
def test_quality_config_converges_and_matches_r5_shape():
    """The bench quality config (internal quality-regression baseline,
    tests/assets/r5/quality_curve.json) must converge directionally at
    reduced scale on the CPU corpus: loss strictly drops, accuracy
    clearly beats chance.

    slow-marked: ~200s of CPU training is nightly-tier budget — inside
    the 870s tier-1 cap it was starving the tail of the corpus of any
    run time at all.  The r5 reference-artifact checks stay in tier-1
    below."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
    try:
        import bench

        # amp=3.0 (strong templates) + 4 epochs: the 512-sample CPU
        # smoke converges AND the BN running stats settle enough for
        # eval-mode accuracy (~0.99 here); the chip config runs the
        # hard amp=0.18 curve (r5 reference: 0.96 final)
        out = bench.run_quality(epochs=4, batch=64, train_n=512,
                                eval_n=128, amp=3.0)
    finally:
        sys.path.pop(0)
    curve = out["quality_loss_curve"]
    assert curve[-1] < curve[0] * 0.8, curve
    assert out["quality_resnet18_synth_eval_acc"] > 0.7, out


def test_quality_r5_reference_artifact_well_formed():
    """The committed r5 reference artifact is well-formed (the cheap
    half of the quality tier — the ~200s convergence run above is
    slow-marked)."""
    import json
    import os
    ref_path = os.path.join(os.path.dirname(__file__), "..", "..",
                            "assets", "r5", "quality_curve.json")
    with open(ref_path) as f:
        ref = json.load(f)
    assert ref["quality_resnet18_synth_eval_acc"] >= 0.9
    assert len(ref["quality_loss_curve"]) == len(ref["quality_acc_curve"])
