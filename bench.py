"""Benchmark: all five BASELINE configs, single chip, within one budget.

North-star config 1 (BASELINE.json): **Gluon hybridize → CachedOp →
gluon.Trainer** — the user-facing imperative loop (`autograd.record`,
`loss.backward()`, `trainer.step`), exactly the reference's benchmark
path.  The same compiled step is then fed from the native C++ RecordIO
pipeline for the END-TO-END number (decode→augment→H2D→step,
overlapped), and the pure-jax ShardedTrainer (pod-scale path) is
reported alongside.

Prints ONE JSON line:
  {"metric": ..., "value": imgs/sec/chip (CachedOp path), "unit": ...,
   "vs_baseline": r, ...all other configs...}
vs_baseline normalises against the V100 target from BASELINE.md
(~1400 img/s fp16 ResNet-50, the "≥ V100 per chip" north star; marked [L]
there — no reference-published number was recoverable).

Budget discipline (VERDICT r3 #2): the five BASELINE configs
(resnet50/bert/ssd512/faster-rcnn/gnmt/wide&deep) run FIRST and are
sized to always fit MXNET_BENCH_BUDGET_S (default 720); io/e2e/sharded
extras run after and are skipped once the budget is spent.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# The persistent XLA compilation cache is placed by
# incubator_mxnet_tpu.compile_cache.enable(), called under __main__
# (parent and every per-config child) — never when a tool or a test
# imports this module.  No cache directory is set here.

V100_IMAGES_PER_SEC = 1400.0   # BASELINE.md north-star denominator [L]

_REC_PATH = os.path.join("/tmp", "bench_io_512.rec")
_REC_N = 512


def _dependent_sync(net):
    """Block on the buffers the LAST step's program produced.  (An
    earlier setup needed a device->host read here because
    block_until_ready could return early; chip_smoke.py's
    `read_after_block_s` shows a read after the block waits <1 ms on
    this chip — PR 21 — so the block is the fence.)"""
    import jax
    # trainable params only: a grad_req='null' buffer (BatchNorm
    # running stats, frozen params) is never rebound by the step, so
    # blocking on it would NOT fence the update
    jax.block_until_ready([q.data()._data
                           for q in net.collect_params().values()
                           if q._grad_req != "null"])


def _ensure_rec(n_images=_REC_N, path=_REC_PATH):
    """Synthetic JPEG RecordIO corpus (cached across runs in /tmp)."""
    from incubator_mxnet_tpu.io import recordio
    if os.path.exists(path):
        return path
    rs = np.random.RandomState(0)
    tmp = path + ".tmp"     # write-then-rename: no truncated leftovers
    rec = recordio.MXRecordIO(tmp, "w")
    for i in range(n_images):
        img = rs.randint(0, 255, (256, 313, 3), dtype=np.uint8)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img,
            quality=90))
    rec.close()
    os.replace(tmp, path)
    return path


def run_cachedop(batch=128, warmup=3, iters=16, extra=None):
    """North-star config 1: hybridized Gluon net + autograd + Trainer.

    Also produces (into `extra`, budget-permitting) the INPUT-FED
    end-to-end number reusing the SAME compiled train step: native C++
    RecordIO decode/augment threads → host cast → H2D → fused step,
    overlapped — the difference between a benchmark and a training
    system (VERDICT r3 #1)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b

    ctx = mx.gpu()          # reference-style: train on the accelerator
    net = resnet50_v1b(classes=1000)
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    x = nd.array(np.random.randn(batch, 3, 224, 224).astype(np.float32),
                 ctx=ctx, dtype="bfloat16")
    y = nd.array(np.random.randint(0, 1000, batch).astype(np.float32),
                 ctx=ctx)

    def step(xb, yb):
        with ag.record():
            l = loss_fn(net(xb), yb)
            l.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step(x, y)
    _dependent_sync(net)
    # median of 3 timed windows (one 16-iter window made the headline
    # a noise sample) + a spread field so a round-over-round delta can
    # be judged against the in-run variance
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            step(x, y)
        _dependent_sync(net)
        rates.append(batch * iters / (time.perf_counter() - t0))
    rates.sort()
    rate = rates[1]

    if extra is None:
        return rate
    extra["resnet50_window_rates"] = [round(r, 1) for r in rates]
    extra["resnet50_spread_pct"] = round(
        100.0 * (rates[-1] - rates[0]) / rate, 2)

    # ---- end-to-end: same train step, inputs from the multi-process
    # decode service through the async device feed (ISSUE 6 on top of
    # ISSUE 2): worker processes decode into shared-memory slabs, the
    # feed device_puts the slab views directly — uint8 end-to-end (4x
    # fewer H2D bytes), NEXT batch's H2D overlapped with the current
    # step, mean/std+cast fused INTO the step executable
    # (HybridBlock.set_input_transform) ----
    svc = None
    try:
        from incubator_mxnet_tpu.io.device_feed import (
            DeviceFeed, feed_counters, normalize_transform)
        from incubator_mxnet_tpu.io.decode_service import (
            DecodeService, DecodeServiceUnavailable)
        from incubator_mxnet_tpu import config as _cfg
        path = _ensure_rec()
        wire = _cfg.get("MXNET_FEED_WIRE_DTYPE")        # default uint8
        depth = _cfg.get("MXNET_FEED_DEPTH")
        # H2D bandwidth probe: per-batch input transfer — not decode,
        # not compute — can bound the e2e rate.  Reported so the e2e
        # number is attributable.
        probe = np.random.randn(batch, 3, 224, 224).astype(np.float32)
        t0 = time.perf_counter()
        nd.array(probe, ctx=ctx).wait_to_read()
        h2d = probe.nbytes / (time.perf_counter() - t0)
        extra["h2d_bytes_per_sec"] = round(h2d, 0)

        # the knob is authoritative when SET (0 = disabled → native
        # fallback, per its registered doc); only unset means auto
        io_workers = (int(_cfg.get("MXNET_IO_WORKERS"))
                      if "MXNET_IO_WORKERS" in os.environ
                      else min(4, os.cpu_count() or 1))
        try:
            if io_workers < 1:
                raise DecodeServiceUnavailable(
                    "MXNET_IO_WORKERS=0: decode service disabled")
            svc = DecodeService(
                path, batch, (3, 224, 224), workers=io_workers,
                resize=256, rand_crop=True, rand_mirror=True,
                shuffle=True, dtype=wire)
            svc.reset()         # bring the pool up (or fall back) NOW
            extra["resnet50_e2e_io_backend"] = "decode_service"
            extra["resnet50_e2e_io_workers"] = svc.workers

            def _epoch():
                # slab views go straight into the feed's device_put;
                # labels flatten to the (batch,) the compiled loss
                # expects (slab labels are (count, label_width))
                for sb in svc:
                    yield sb.data, (sb.label[:, 0] % 1000)

            feed = DeviceFeed(_epoch, ctx=ctx, depth=depth)
        except DecodeServiceUnavailable:
            # sandboxed host: native C++ threaded reader (PR 2 path)
            from incubator_mxnet_tpu.io import native
            if not native.available():
                raise RuntimeError("decode service and native io both "
                                   "unavailable")
            reader = native.NativeImageRecordReader(
                path, batch_size=batch, data_shape=(3, 224, 224),
                resize=256, rand_crop=True, rand_mirror=True,
                shuffle=True, dtype=wire)
            extra["resnet50_e2e_io_backend"] = "native"
            extra["resnet50_e2e_io_workers"] = 0

            def _host_labels(b):
                data, label = b
                return data, (label.reshape(label.shape[0], -1)[:, 0]
                              .astype(np.float32) % 1000)

            feed = DeviceFeed(reader, ctx=ctx, depth=depth,
                              transform=_host_labels)
        # wire→bf16 (x-127.5)/64 runs ON DEVICE inside the fused step
        # (a host-side ml_dtypes convert is a single-core C loop,
        # measured ~12x slower than the whole train step); the reader
        # ships raw pixels either way — only the wire width differs
        net.set_input_transform(normalize_transform(
            127.5, 64.0, "bfloat16"))
        # the transform invalidated the cached step: warm the fused
        # executable for the e2e input signature OUTSIDE the timed loop
        # (the old path reused the synthetic-signature executable; this
        # one fuses the normalize, so its first call pays the compile)
        rs_w = np.random.RandomState(0)
        wx = rs_w.randint(0, 256, (batch, 3, 224, 224)).astype(
            np.uint8 if wire == "uint8" else np.float32)
        step(nd.array(wx, ctx=ctx),
             nd.array(np.zeros(batch, np.float32), ctx=ctx))
        _dependent_sync(net)
        c0 = feed_counters()
        n = 0
        t0 = time.perf_counter()
        for data, label in feed:
            if data.shape[0] != batch:
                continue                # keep the compiled signature
            step(data, label)
            n += batch
        _dependent_sync(net)
        e2e = n / (time.perf_counter() - t0)
        net.set_input_transform(None)
        extra["resnet50_e2e_input_fed_images_per_sec"] = round(e2e, 2)
        extra["resnet50_e2e_fraction_of_synthetic"] = round(e2e / rate, 3)
        # what the measured H2D rate allows at the wire bytes/img —
        # the e2e ceiling
        wire_img_bytes = 3 * 224 * 224 * (4 if wire == "float32" else 1)
        extra["resnet50_e2e_h2d_bound_images_per_sec"] = round(
            h2d / wire_img_bytes, 1)
        extra["resnet50_e2e_wire_dtype"] = wire
        extra["resnet50_e2e_feed_depth"] = depth
        # per-stage feed counters (µs/bytes deltas for THIS loop):
        # read=source wall, transfer=H2D wall, stall=chip starved,
        # step=compute wall between batches (monitor.events 'feed.*')
        extra["resnet50_e2e_feed_counters"] = {
            k: v - c0.get(k, 0) for k, v in feed_counters().items()}
    except Exception as e:
        extra["resnet50_e2e_error"] = str(e)[:120]
    finally:
        if svc is not None:
            svc.close()             # stop the worker pool + free shm
    return rate


def run_bert(batch=16, seq=512, warmup=2, iters=10):
    """North-star config 2: BERT-base MLM pretrain step, tokens/sec/chip.

    Same user-facing path as config 1 (hybridize → CachedOp → Trainer),
    bf16 compute (LayerNorm model: no BN-state writeback tax) with the
    Pallas flash attention kernels forced and the memory-exact fused
    softmax-CE — together these moved the fitting batch from 8 (r3) to
    16 and +42% tokens/s.  Synthetic MLM: predict the token ids at
    every position (dense CE over the vocab) — same compute shape as a
    100%-masked MLM step.
    """
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu import config as _cfg
    from incubator_mxnet_tpu.models.transformer import (bert_base,
                                                        FusedMLMCELoss)

    _cfg.set("MXNET_USE_PALLAS", "2")
    ctx = mx.gpu()
    # output_hidden + FusedMLMCELoss: the vocab projection is fused
    # into a chunked CE (the (B·T, 30522) logits never materialise) —
    # this is what moves the fitting batch past 16 (r4)
    net = bert_base(dropout=0.0, output_hidden=True)
    net.initialize(ctx=ctx)
    net.cast("bfloat16")
    net.hybridize(static_alloc=True, static_shape=True)
    loss_b = FusedMLMCELoss(30522, 768)
    loss_b.initialize(ctx=ctx)
    loss_b.cast("bfloat16")
    loss_b.hybridize()
    all_params = {**net.collect_params(), **loss_b.collect_params()}
    trainer = gluon.Trainer(all_params, "adam", {"learning_rate": 1e-4})
    rs = np.random.RandomState(0)
    tokens = nd.array(rs.randint(0, 30522, (batch, seq)).astype(np.int32),
                      ctx=ctx, dtype="int32")
    labels = nd.array(rs.randint(0, 30522, (batch, seq)).astype(np.float32),
                      ctx=ctx)

    def step():
        with ag.record():
            h = net(tokens)
            l = loss_b(h, labels)
            l.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    return batch * seq * iters / (time.perf_counter() - t0)


def _params_m(*blocks):
    """Total parameter count (millions) across blocks."""
    n = 0
    for blk in blocks:
        n += sum(int(np.prod(p.shape))
                 for p in blk.collect_params().values())
    return round(n / 1e6, 1)


def run_ssd(batch=8, size=512, warmup=2, iters=10, extra=None):
    """Config 3a: SSD-512 on VGG16-reduced-atrous — the reference's
    actual benchmark model (ref: example/ssd symbol_vgg16_reduced.py;
    24.5k anchors, 27M params) — images/sec/chip (hybridize →
    CachedOp → Trainer, MultiBoxTarget loss like example/ssd).  The
    small-convnet ssd_512 stays as the test smoke model (r4's stand-in
    headline — VERDICT r4 weak #1)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import ssd_512_vgg16, SSDTrainLoss

    ctx = mx.gpu()
    net = ssd_512_vgg16(classes=20)
    net.initialize(ctx=ctx)
    net.hybridize()
    # hybridized target+CE+smooth-L1 block: net -> loss is ONE fused
    # train-step executable (+34% vs the eager composition, r4)
    loss_b = SSDTrainLoss()
    loss_b.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    rs = np.random.RandomState(0)
    # bf16 input: conv weights cast into the activation dtype inside
    # the program (r4: +15% on this config, 43 -> 50 img/s)
    x = nd.array(rs.randn(batch, 3, size, size).astype(np.float32),
                 ctx=ctx, dtype="bfloat16")
    # one gt box per image: [cls, x1, y1, x2, y2] normalized
    labels = np.zeros((batch, 1, 5), np.float32)
    labels[:, 0] = [1, 0.2, 0.2, 0.7, 0.7]
    y = nd.array(labels, ctx=ctx)

    def step():
        with ag.record():
            anchors, cls_preds, box_preds = net(x)
            loss = loss_b(anchors, cls_preds, box_preds, y)
            loss.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    dt = time.perf_counter() - t0       # before the metadata walk
    if extra is not None:
        extra["ssd512_model"] = "vgg16_reduced_atrous"
        extra["ssd512_params_m"] = _params_m(net)
    return batch * iters / dt


def run_rcnn(batch=2, height=600, width=800, warmup=2, iters=10,
             extra=None):
    """Config 3b: Faster-RCNN on resnet50_v1b at 600x800, 128 sampled
    rois/img — the reference's benchmark geometry (ref: example/rcnn
    train_end2end: resnet conv4 feature + conv5 head, BATCH_ROIS=128,
    600px short side) — images/sec/chip.  RPN → Proposal (top-2000
    padded NMS) → ProposalTarget → ROIAlign → heads; fixed shapes keep
    it ONE XLA executable.  The small custom backbone (r4's stand-in)
    stays as the test smoke model."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import (faster_rcnn_resnet50_v1b,
                                            RCNNTrainLoss)

    ctx = mx.gpu()
    net = faster_rcnn_resnet50_v1b(classes=20)
    net.initialize(ctx=ctx)
    net.hybridize()
    # hybridized head loss: ~4x vs the eager op chain (r4)
    loss_b = RCNNTrainLoss()
    loss_b.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 1e-3, "momentum": 0.9})
    rs = np.random.RandomState(0)
    # bf16 input: conv weights cast into the activation dtype inside
    # the program (same as the other convnet configs)
    x = nd.array(rs.randn(batch, 3, height, width).astype(np.float32),
                 ctx=ctx, dtype="bfloat16")
    im_info = nd.array(np.tile([height, width, 1.0],
                               (batch, 1)).astype(np.float32), ctx=ctx)
    gt = np.zeros((batch, 2, 5), np.float32)
    gt[:, 0] = [60, 60, 260, 260, 1]
    gt[:, 1] = [200, 200, 420, 420, 2]
    gt_boxes = nd.array(gt, ctx=ctx)

    def step():
        with ag.record():
            # 128 sampled rois PER IMAGE (ref train_end2end BATCH_ROIS)
            (cls_pred, box_pred, rois, labels, targets, weights,
             rpn_cls, rpn_box) = net(x, im_info, gt_boxes=gt_boxes,
                                     batch_rois=128 * batch)
            loss = loss_b(cls_pred, box_pred, labels, targets, weights)
            loss.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    dt = time.perf_counter() - t0       # before the metadata walk
    if extra is not None:
        extra["rcnn_model"] = "resnet50_v1b_600x800_rois128"
        extra["rcnn_params_m"] = _params_m(net)
    return batch * iters / dt


def run_gnmt(batch=128, src_len=50, tgt_len=50, warmup=2, iters=10,
             extra=None):
    """Config 4: GNMT at reference geometry — 4x1024 encoder (bi
    bottom layer, residual stack), 4x1024 decoder, 1024 embeddings,
    32k vocab, seq 50 (~175M params; ref: Sockeye GNMT config over the
    fused RNN op) — target tokens/sec.  bf16 compute; the vocab
    projection is fused into the chunked softmax-CE so the (B·50, 32k)
    logits never materialise.  The 2x256 `Seq2Seq` (r4's stand-in)
    stays as the test smoke model."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import gnmt_large
    from incubator_mxnet_tpu.models.transformer import FusedMLMCELoss

    ctx = mx.gpu()
    vocab = 32000
    net = gnmt_large(output_hidden=True)
    net.initialize(ctx=ctx)
    net.cast("bfloat16")
    net.hybridize(static_alloc=True, static_shape=True)
    loss_b = FusedMLMCELoss(vocab, 1024)
    loss_b.initialize(ctx=ctx)
    loss_b.cast("bfloat16")
    loss_b.hybridize()
    trainer = gluon.Trainer(
        {**net.collect_params(), **loss_b.collect_params()}, "adam",
        {"learning_rate": 1e-3})
    rs = np.random.RandomState(0)
    src = nd.array(rs.randint(0, vocab, (batch, src_len)), ctx=ctx,
                   dtype="int32")
    tgt = nd.array(rs.randint(0, vocab, (batch, tgt_len)), ctx=ctx,
                   dtype="int32")
    lab = nd.array(rs.randint(0, vocab, (batch, tgt_len)).astype(
        np.float32), ctx=ctx)

    def step():
        with ag.record():
            h = net(src, tgt)
            loss = loss_b(h, lab)
            loss.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    dt = time.perf_counter() - t0       # before the metadata walk
    if extra is not None:
        extra["gnmt_model"] = "gnmt_4x1024_bi_vocab32k_seq50"
        extra["gnmt_params_m"] = _params_m(net, loss_b)
    return batch * tgt_len * iters / dt


def run_transformer_nmt(batch=64, src_len=64, tgt_len=64, warmup=2,
                        iters=10):
    """Config 4b: Transformer NMT (Sockeye transformer_nmt_base:
    6 layers, 512 units, 32k vocab) training at seq 64 (Sockeye-era
    sentence lengths — VERDICT r4 weak #4), target tokens/sec —
    teacher-forced, causal flash self-attention."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import TransformerNMT
    from incubator_mxnet_tpu.models.transformer import FusedMLMCELoss

    ctx = mx.gpu()
    vocab = 32000
    # output_hidden + fused chunked CE: the (B·T, 32000) logits never
    # materialise (same head fusion as the BERT config, r4)
    net = TransformerNMT(vocab, vocab, units=512, hidden_size=2048,
                         num_layers=6, num_heads=8, dropout=0.0,
                         output_hidden=True)
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    loss_b = FusedMLMCELoss(vocab, 512)
    loss_b.initialize(ctx=ctx)
    loss_b.hybridize()
    trainer = gluon.Trainer(
        {**net.collect_params(), **loss_b.collect_params()}, "adam",
        {"learning_rate": 1e-4})
    rs = np.random.RandomState(0)
    src = nd.array(rs.randint(0, vocab, (batch, src_len)), ctx=ctx,
                   dtype="int32")
    tgt = nd.array(rs.randint(0, vocab, (batch, tgt_len)), ctx=ctx,
                   dtype="int32")
    lab = nd.array(rs.randint(0, vocab, (batch, tgt_len)).astype(
        np.float32), ctx=ctx)

    def step():
        with ag.record():
            h = net(src, tgt)
            loss = loss_b(h, lab)
            loss.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    return batch * tgt_len * iters / (time.perf_counter() - t0)


def run_wide_deep(batch=2048, fields=16, warmup=3, iters=40,
                  sparse=False):
    """Config 5: Wide&Deep recommender, samples/sec.

    Headline = the TPU-native path: dense-gather embedding gradients,
    hybridized → ONE fused train-step executable (the r4 profiler
    showed the old eager sparse-path bench spending its whole step on
    per-op dispatch).  sparse=True measures the row_sparse lazy-update
    path (parity with the reference's example/sparse/wide_deep
    FComputeEx design) via the r5 `BucketedSparseTrainer`: device-side
    unique-row buckets + sentinel-row lazy updates, ONE executable per
    bucket — the vocab-sized dense gradient never exists, which is the
    path that scales to million-row vocabularies."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models import wide_deep

    ctx = mx.gpu()
    num_features = 100000
    net = wide_deep(num_features=num_features, embed_dim=16,
                    sparse_grad=sparse)
    net.initialize(ctx=ctx)
    rs = np.random.RandomState(0)
    idx = nd.array(rs.randint(0, num_features, (batch, fields)),
                   ctx=ctx, dtype="int32")
    vals = nd.array(rs.rand(batch, fields).astype(np.float32), ctx=ctx)
    y = nd.array(rs.randint(0, 2, batch).astype(np.float32), ctx=ctx)

    if sparse:
        from incubator_mxnet_tpu.contrib.sparse_jit import \
            BucketedSparseTrainer
        net(idx, vals)                  # materialize deferred shapes
        jt = BucketedSparseTrainer(net, optimizer="adam", lr=1e-3)
        for _ in range(warmup):
            loss = jt.step(idx, vals, y)
        float(loss.asnumpy())           # honest D2H sync
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = jt.step(idx, vals, y)
        float(loss.asnumpy())
        return batch * iters / (time.perf_counter() - t0)

    net.hybridize(static_alloc=True, static_shape=True)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    sce.hybridize()

    def step():
        with ag.record():
            loss = sce(net(idx, vals), y)
            loss.backward()
        trainer.step(batch)

    for _ in range(warmup):
        step()
    _dependent_sync(net)
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    _dependent_sync(net)
    return batch * iters / (time.perf_counter() - t0)


def run_serve(n_images=512, max_batch=32, seed=0, extra=None):
    """Serving config (ISSUE 3): the bucketed dynamic-batching
    InferenceEngine vs the sequential batch-1 baseline on the SAME
    model — a model_zoo thumbnail ResNet-18 under a mixed-size request
    stream (the organic-traffic shape that recompiles an eager server
    to death).  CPU ok.  Reports throughput, p50/p99 latency, the
    batch-fill/pad-waste economics, and the zero-recompile check:
    `serve_traces_after_warmup_delta` MUST be 0 — every request size
    landed on a warmed bucket executable."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    ctx = mx.gpu()
    net = resnet18_v1(classes=10, thumbnail=True)
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    rs = np.random.RandomState(seed)
    imgs = rs.rand(n_images, 3, 32, 32).astype(np.float32)

    # ---- sequential batch-1 baseline: ONE warmed executable, one
    # image per call, per-call sync (what an eager `block(x)` server
    # does once its single compiled shape is warm — the best case for
    # the unbatched path, since organic traffic would also recompile)
    x1 = nd.array(imgs[:1], ctx=ctx)
    net(x1).asnumpy()                   # warm the batch-1 executable
    t0 = time.perf_counter()
    for i in range(n_images):
        out = net(nd.array(imgs[i:i + 1], ctx=ctx))
        # a server RETURNS each result: one-element D2H per request
        # (async dispatch without it would only measure enqueue)
        float(out.reshape((-1,))[:1].asnumpy()[0])
    base_rate = n_images / (time.perf_counter() - t0)

    # ---- engine: warm every bucket, then a mixed-size request stream
    eng = net.inference_engine(ctx=ctx, max_batch=max_batch,
                               queue_cap=max(64, n_images))
    warm = eng.warmup(example_shape=(3, 32, 32), wire_dtype="float32")
    traces0 = events.get("serve.traces")
    c0 = events.snapshot("serve.")
    futs = []
    t0 = time.perf_counter()
    i = 0
    while i < n_images:
        k = int(rs.choice((1, 1, 2, 3, 5, 8)))      # organic size mix
        k = min(k, n_images - i)
        if k == 1:
            futs.append((1, eng.submit(imgs[i])))
        else:
            futs.append((k, eng.submit_batch(imgs[i:i + k])))
        i += k
    for _, f in futs:
        # same per-request one-element D2H the baseline pays — a
        # server RETURNS results on both paths (symmetric comparison)
        r = f.result(timeout=120)
        float(r.reshape((-1,))[:1].asnumpy()[0])
    eng_rate = n_images / (time.perf_counter() - t0)
    delta = {k: v - c0.get(k, 0)
             for k, v in events.snapshot("serve.").items()}
    e2e = events.percentiles("serve.e2e_us", (50, 99))
    inf = events.percentiles("serve.infer_us", (50, 99))
    eng.close()
    out = {
        "serve_engine_images_per_sec": round(eng_rate, 2),
        "serve_baseline_batch1_images_per_sec": round(base_rate, 2),
        "serve_speedup_vs_batch1": round(eng_rate / base_rate, 2),
        "serve_model": "resnet18_v1_thumbnail_32x32",
        "serve_n_images": n_images,
        "serve_requests": delta.get("serve.requests", 0),
        "serve_batches": delta.get("serve.batches", 0),
        "serve_batch_fill": delta.get("serve.batch_fill", 0),
        "serve_pad_waste": delta.get("serve.pad_waste", 0),
        "serve_rejected": delta.get("serve.rejected", 0),
        "serve_p50_e2e_ms": round(e2e.get("p50", 0) / 1e3, 3),
        "serve_p99_e2e_ms": round(e2e.get("p99", 0) / 1e3, 3),
        "serve_p50_infer_us": int(inf.get("p50", 0)),
        "serve_p99_infer_us": int(inf.get("p99", 0)),
        "serve_buckets": warm["buckets"],
        "serve_warmup_wall_s": warm["wall_s"],
        # the zero-recompile contract: 0 new traces after warmup under
        # the mixed-size stream
        "serve_traces_after_warmup_delta":
            events.get("serve.traces") - traces0,
    }
    # counter/percentile snapshot block (ISSUE 4): bench runs double as
    # telemetry fixtures — teletop --file renders this, and the
    # BENCH_serve.json trajectory keeps the tails next to the rates
    from incubator_mxnet_tpu import telemetry
    out["telemetry"] = telemetry.snapshot_dict()
    if extra is not None:
        extra.update(out)
    return out


def measure_serve_capacity(eng, data, seconds, batch=8):
    """Closed-loop saturation rate (images/s) of a warmed engine with
    bounded outstanding work, submitted on the engine's default (top)
    lane.  Shared by the serve_overload scenario and
    tools/check_serve.py so the CI gate and the bench measure the SAME
    capacity the 2x offered load is derived from."""
    n = max(batch, (len(data) // batch - 1) * batch)
    t0 = time.perf_counter()
    futs, done, i = [], 0, 0
    while time.perf_counter() < t0 + seconds:
        off = (i * batch) % n
        futs.append(eng.submit_batch(data[off:off + batch]))
        i += 1
        if len(futs) >= 8:
            futs.pop(0).result(timeout=120)
            done += batch
    for f in futs:
        f.result(timeout=120)
        done += batch
    return done / (time.perf_counter() - t0)


def overload_deadline_s(max_batch, capacity_ips, factor=3.5,
                        floor_s=0.25):
    """Deadline bound for the overload scenarios, SELF-CALIBRATED to
    the measured batch service time (`max_batch / capacity`): a fixed
    wall-clock bound is 1.5 service times on a throttled CPU VM and
    100 on a real chip — neither exercises deadline-aware scheduling
    honestly.  One definition, imported by tools/check_serve.py, so
    the CI gate cannot drift from the bench contract."""
    return max(floor_s, factor * max_batch / max(capacity_ips, 1e-6))


def run_serve_overload(duration_s=6.0, capacity_s=2.0, hi_frac=0.2,
                       hi_deadline=None, lo_deadline=None, seed=0,
                       extra=None):
    """Overload scenario (ISSUE 8): open-loop Poisson arrivals at 2x
    the engine's MEASURED capacity, split across priority lanes (hi
    gets a tight deadline, lo a loose one and a 0.5 queue quota).  The
    contract under sustained overload: the hi lane's p99 stays within
    its deadline while the EXCESS lo work is shed with typed errors
    (Shed / QueueFull / DeadlineExceeded) instead of queueing the
    whole engine into uniform deadline collapse.  Open-loop matters:
    a closed-loop client slows down with the server and hides the
    overload; Poisson arrivals keep offering work at the nominal rate
    no matter how the engine responds.  Reports per-lane p50/p99/p999
    (from the labeled serve.e2e_us rings) + shed fractions."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.serving import (Shed, QueueFull,
                                             DeadlineExceeded)
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    ctx = mx.gpu()
    net = resnet18_v1(classes=10, thumbnail=True)
    net.initialize(ctx=ctx)
    net.hybridize(static_alloc=True, static_shape=True)
    rs = np.random.RandomState(seed)
    imgs = rs.rand(256, 3, 32, 32).astype(np.float32)

    # lane names unique to this scenario ("hi"/"lo", not the default
    # "high"/...) so the labeled rings aren't polluted by a preceding
    # run_serve in the same process; the capacity phase submits on its
    # own top lane ("cap", the default) for the same reason — the
    # hi/lo rings must hold OVERLOAD samples only
    # max_batch 8, not run_serve's 32: the deadline bound has to hold
    # against the BATCH service time (~bucket/capacity), and a 32-wide
    # CPU bucket alone eats the whole hi deadline
    eng = net.inference_engine(ctx=ctx, max_batch=8, queue_cap=64,
                               lanes=("cap", "hi", "lo"),
                               lane_quotas=(1.0, 1.0, 0.5))
    eng.warmup(example_shape=(3, 32, 32), wire_dtype="float32")

    # ---- capacity: closed-loop saturation (bounded outstanding work)
    capacity = measure_serve_capacity(eng, imgs, capacity_s)

    # deadlines self-calibrate to the MEASURED batch service time; the
    # bound used is stated in the record (overload_deadline_s)
    if hi_deadline is None:
        hi_deadline = overload_deadline_s(8, capacity)
    if lo_deadline is None:
        lo_deadline = 2.0 * hi_deadline

    # ---- overload: open-loop Poisson at 2x capacity
    rate = 2.0 * capacity
    c0 = events.snapshot("serve.")
    served = {"hi": 0, "lo": 0}
    shed = {"hi": 0, "lo": 0}
    pending = []
    t0 = time.perf_counter()
    next_t, n_offered = t0, 0
    while True:
        now = time.perf_counter()
        if now >= t0 + duration_s:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        next_t += rs.exponential(1.0 / rate)
        lane = "hi" if rs.rand() < hi_frac else "lo"
        dl = hi_deadline if lane == "hi" else lo_deadline
        n_offered += 1
        try:
            pending.append((lane, eng.submit(
                imgs[n_offered % 256], deadline=dl, lane=lane,
                tenant="t%d" % (n_offered % 4))))
        except (Shed, QueueFull, DeadlineExceeded):
            shed[lane] += 1
    wall = time.perf_counter() - t0
    for lane, f in pending:
        try:
            f.result(timeout=120)
            served[lane] += 1
        except (Shed, QueueFull, DeadlineExceeded):
            shed[lane] += 1
    eng.close()

    delta = {k: v - c0.get(k, 0)
             for k, v in events.snapshot("serve.").items()}
    achieved = n_offered / wall
    lanes_pct = {r["labels"]["lane"]: r
                 for r in events.labeled_percentiles(
                     "serve.e2e_us", (50, 99, 99.9))
                 if r["labels"].get("lane") in ("hi", "lo")}
    out = {
        "serve_overload_capacity_ips": round(capacity, 1),
        "serve_overload_offered_ips": round(rate, 1),
        "serve_overload_achieved_offer_ips": round(achieved, 1),
        "serve_overload_duration_s": round(wall, 2),
        "serve_overload_hi_deadline_ms": round(hi_deadline * 1e3, 1),
        "serve_overload_lo_deadline_ms": round(lo_deadline * 1e3, 1),
        "serve_overload_offered": n_offered,
        "serve_overload_shed_delta": delta.get("serve.shed", 0),
    }
    for lane in ("hi", "lo"):
        p = lanes_pct.get(lane, {})
        out["serve_overload_%s_p50_ms" % lane] = \
            round(p.get("p50", 0) / 1e3, 2)
        out["serve_overload_%s_p99_ms" % lane] = \
            round(p.get("p99", 0) / 1e3, 2)
        out["serve_overload_%s_p999_ms" % lane] = \
            round(p.get("p99.9", 0) / 1e3, 2)
        out["serve_overload_%s_served" % lane] = served[lane]
        out["serve_overload_%s_shed" % lane] = shed[lane]
        tot = max(1, served[lane] + shed[lane])
        out["serve_overload_%s_shed_fraction" % lane] = \
            round(shed[lane] / tot, 3)
    out["serve_overload_shed_fraction"] = round(
        (shed["hi"] + shed["lo"]) / max(1, n_offered), 3)
    out["serve_overload_hi_p99_within_deadline"] = bool(
        lanes_pct.get("hi", {}).get("p99", float("inf"))
        <= hi_deadline * 1e6)
    # the verdict is only meaningful when the open loop actually
    # overloaded the engine — a starved submitter (busy VM) can't
    # prove or disprove the shed contract
    if achieved >= 1.3 * capacity:
        out["serve_overload_ok"] = bool(
            out["serve_overload_hi_p99_within_deadline"]
            and out["serve_overload_shed_fraction"] > 0.01)
    else:
        out["serve_overload_ok"] = None
    if extra is not None:
        extra.update(out)
    return out


def build_generation_model(vocab=31, seed=0, ctx=None):
    """Small Seq2Seq generation model + priming forward — shared by
    `bench.py generate` (on the accelerator) and tools/check_decode.py
    (the CPU CI gate) so both measure the same workload."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models import Seq2Seq
    mx.random.seed(seed)
    net = Seq2Seq(vocab, vocab, embed_dim=24, hidden=32, num_layers=2)
    net.initialize(force_reinit=True, ctx=ctx)
    net(nd.array(np.ones((1, 4), np.int32), ctx=ctx),
        nd.array(np.ones((1, 1), np.int32), ctx=ctx))  # concrete shapes
    return net


def measure_generate_capacity(eng, prompts, seconds, max_new,
                              lane=None):
    """Closed-loop generation saturation (requests/s) with bounded
    outstanding work — the denominator the 2x open-loop offer is
    derived from.  Shared with tools/check_decode.py."""
    t0 = time.perf_counter()
    streams, done, i = [], 0, 0
    depth = max(4, eng.stats()["slots"] * 2)
    while time.perf_counter() < t0 + seconds:
        streams.append(eng.submit(prompts[i % len(prompts)],
                                  max_new_tokens=max_new, lane=lane))
        i += 1
        if len(streams) >= depth:
            streams.pop(0).result(timeout=120)
            done += 1
    for s in streams:
        s.result(timeout=120)
        done += 1
    return done / (time.perf_counter() - t0)


def _generate_overload(eng, prompts, rate, duration_s, hi_frac,
                       hi_lane, lo_lane, hi_deadline, lo_deadline,
                       max_new, rs):
    """Open-loop Poisson generation traffic at `rate` req/s: the
    client never slows down with the server, so the overload is real.
    Generation lengths are HETEROGENEOUS (uniform in [3, max_new] per
    request, drawn from the shared schedule RNG so both engines see
    identical work) — the regime continuous batching exists for: a
    drain batch holds every freed slot hostage to its longest
    sequence, a continuous batch backfills it immediately.  Returns
    (offered, served, shed, wall)."""
    from incubator_mxnet_tpu.serving import (Shed, QueueFull,
                                             DeadlineExceeded)
    served = {hi_lane: 0, lo_lane: 0}
    shed = {hi_lane: 0, lo_lane: 0}
    pending = []
    t0 = time.perf_counter()
    next_t, n_offered = t0, 0
    while True:
        now = time.perf_counter()
        if now >= t0 + duration_s:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        next_t += rs.exponential(1.0 / rate)
        lane = hi_lane if rs.rand() < hi_frac else lo_lane
        dl = hi_deadline if lane == hi_lane else lo_deadline
        mn = int(rs.randint(3, max_new + 1))
        n_offered += 1
        try:
            pending.append((lane, eng.submit(
                prompts[n_offered % len(prompts)],
                max_new_tokens=mn, deadline=dl, lane=lane)))
        except (Shed, QueueFull, DeadlineExceeded):
            shed[lane] += 1
    wall = time.perf_counter() - t0
    for lane, s in pending:
        try:
            s.result(timeout=120)
            served[lane] += 1
        except (Shed, QueueFull, DeadlineExceeded):
            shed[lane] += 1
    return n_offered, served, shed, wall


def run_generate(duration_s=5.0, capacity_s=1.5, hi_frac=0.2,
                 slots=4, max_len=24, max_new=12, seed=0, extra=None):
    """Generation serving bench (ISSUE 14): the KV-cached
    continuous-batching GenerationEngine under open-loop Poisson
    traffic at 2x its MEASURED capacity, 20/80 hi/lo lane mix.

    Reports tokens/s, per-lane TTFT p50/p99 and inter-token p99 (the
    generation tails users feel), the zero-recompile check, and the
    tentpole A/B: the SAME Poisson schedule driven at a drain-batching
    engine (continuous=False — a new batch only forms when every slot
    is free).  Continuous batching must beat drain on TTFT p99 under
    overload: that win is what `generate_ok` gates (judged only when
    the open loop actually achieved 2x — a starved submitter proves
    nothing)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.serving import GenerationEngine

    ctx = mx.tpu(0)
    net = build_generation_model(seed=seed, ctx=ctx)
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(3, 31, (int(n),))
               for n in rs.choice((3, 4, 5, 6, 7, 8), 64)]

    out = {"generate_model": "seq2seq_small_v31",
           "generate_slots": slots, "generate_max_len": max_len,
           "generate_max_new_tokens": max_new}
    results = {}
    # continuous first (it also supplies the measured capacity the
    # drain phase's offered rate reuses — same schedule, same rate)
    capacity = None
    for mode, lanes in (("cb", ("cap", "hi", "lo")),
                        ("drain", ("dcap", "dhi", "dlo"))):
        eng = GenerationEngine(
            net, bos=1, eos=2, ctx=ctx, slots=slots, max_len=max_len,
            prompt_buckets=(4, 8), queue_cap=64,
            lanes=lanes, lane_quotas=(1.0, 1.0, 0.5),
            continuous=(mode == "cb"))
        warm = eng.warmup()
        traces0 = events.get("serve.traces")
        if capacity is None:
            capacity = measure_generate_capacity(
                eng, prompts, capacity_s, max_new)
            # deadline self-calibrated to the measured per-request
            # service wall (the overload_deadline_s discipline)
            svc = 1.0 / max(capacity / slots, 1e-6)
            hi_deadline = max(0.5, 3.5 * svc)
            lo_deadline = 2.0 * hi_deadline
            out["generate_capacity_rps"] = round(capacity, 2)
            out["generate_hi_deadline_ms"] = round(hi_deadline * 1e3, 1)
            out["generate_warmup_wall_s"] = warm["wall_s"]
            out["generate_kv_cache_bytes"] = warm["kv_cache"]["total"]
        rate = 2.0 * capacity
        tok0 = events.get("gen.tokens")
        rs_phase = np.random.RandomState(seed + 17)     # SAME schedule
        offered, served, shed, wall = _generate_overload(
            eng, prompts, rate, duration_s, hi_frac,
            lanes[1], lanes[2], hi_deadline, lo_deadline, max_new,
            rs_phase)
        traces_delta = events.get("serve.traces") - traces0
        toks = events.get("gen.tokens") - tok0
        eng.close()
        lanes_pct = {r["labels"]["lane"]: r
                     for r in events.labeled_percentiles(
                         "gen.ttft_us", (50, 99))
                     if r["labels"].get("lane") in (lanes[1], lanes[2])}
        hi = lanes_pct.get(lanes[1], {})
        # inter-token from THIS phase's hi-lane labeled ring — the
        # unlabeled aggregate mixes capacity/drain-phase samples (the
        # same leak check_decode avoids via unique lane names)
        it_pct = {r["labels"]["lane"]: r
                  for r in events.labeled_percentiles(
                      "gen.intertoken_us", (50, 99))}
        it_hi = it_pct.get(lanes[1], {})
        results[mode] = {
            "intertoken_p50_ms": it_hi.get("p50", 0) / 1e3,
            "intertoken_p99_ms": it_hi.get("p99", 0) / 1e3,
            "offered": offered, "wall": wall,
            "achieved_rps": offered / max(wall, 1e-9),
            "served_hi": served[lanes[1]], "served_lo": served[lanes[2]],
            "shed_hi": shed[lanes[1]], "shed_lo": shed[lanes[2]],
            "tokens": toks, "tokens_per_sec": toks / max(wall, 1e-9),
            "ttft_hi_p50_ms": hi.get("p50", 0) / 1e3,
            "ttft_hi_p99_ms": hi.get("p99", 0) / 1e3,
            "traces_delta": traces_delta,
        }
    cb, dr = results["cb"], results["drain"]
    out.update({
        "generate_offered_rps": round(2.0 * capacity, 2),
        "generate_achieved_rps": round(cb["achieved_rps"], 2),
        "generate_tokens_per_sec": round(cb["tokens_per_sec"], 1),
        "generate_ttft_p50_ms": round(cb["ttft_hi_p50_ms"], 2),
        "generate_ttft_p99_ms": round(cb["ttft_hi_p99_ms"], 2),
        "generate_intertoken_p50_ms": round(
            cb["intertoken_p50_ms"], 3),
        "generate_intertoken_p99_ms": round(
            cb["intertoken_p99_ms"], 3),
        "generate_shed_fraction": round(
            (cb["shed_hi"] + cb["shed_lo"]) / max(1, cb["offered"]), 3),
        "generate_traces_after_warmup_delta": cb["traces_delta"],
        "generate_cb_ttft_p99_ms": round(cb["ttft_hi_p99_ms"], 2),
        "generate_drain_ttft_p99_ms": round(dr["ttft_hi_p99_ms"], 2),
        "generate_drain_tokens_per_sec": round(dr["tokens_per_sec"], 1),
        "generate_cb_win": bool(
            cb["ttft_hi_p99_ms"] < dr["ttft_hi_p99_ms"]),
    })
    achieved_2x = (cb["achieved_rps"] >= 1.3 * capacity
                   and dr["achieved_rps"] >= 1.3 * capacity)
    if achieved_2x:
        out["generate_ok"] = bool(
            out["generate_cb_win"]
            and cb["traces_delta"] == 0
            and cb["ttft_hi_p99_ms"] <= hi_deadline * 1e3)
    else:
        out["generate_ok"] = None       # never actually overloaded
    if extra is not None:
        extra.update(out)
    return out


def _peak_hbm_block():
    """``{"peak_hbm_bytes": {device: {bytes, source}}}`` for a bench
    json block (ISSUE 20): the per-device peak watermark memwatch
    observed this process (max across phases, forced sample so it
    works with MXNET_MEMWATCH=0 too), with the sampling source
    spelled out — PJRT ``memory_stats`` on a real accelerator, the
    ``live_arrays`` fallback on this CPU host — so a trajectory diff
    can tell a real footprint regression from a measurement-source
    change.  {} when nothing is measurable."""
    try:
        from incubator_mxnet_tpu.telemetry import memwatch as _mw
        smp = _mw.sample(tag="bench", force=True)
        if not smp:
            return {}
        marks = _mw.watermarks()
        out = {}
        for dev, row in (smp.get("devices") or {}).items():
            peak = max([int(row.get("peak_bytes", 0)),
                        int(row.get("used_bytes", 0))] +
                       [int(m.get(dev, 0)) for m in marks.values()])
            out[dev] = {"bytes": peak,
                        "source": str(row.get("source", "?"))}
        return {"peak_hbm_bytes": out} if out else {}
    except Exception:               # noqa: BLE001 — observability
        return {}                   # must never fail a bench


def _merge_bench_serve(patch, rc=0):
    """Merge `patch` keys into BENCH_serve.json's parsed record
    (creating it if absent) — `bench.py generate` rides in the same
    trajectory file as the one-shot serve numbers."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "BENCH_serve.json")
    parsed = {}
    try:
        with open(path) as fh:
            parsed = json.load(fh).get("parsed", {})
    except Exception:
        pass
    parsed.update(patch)
    return _write_bench_serve(parsed, rc=rc)


def _write_bench_serve(parsed, rc=0):
    """BENCH_serve.json in the BENCH_r* schema ({n, cmd, rc, tail,
    parsed}) so the perf-trajectory tooling picks the serving numbers
    up alongside the training rounds."""
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    n = 0
    for f in os.listdir(here):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", f)
        if m:
            n = max(n, int(m.group(1)))
    parsed = dict(parsed)
    parsed.update(_peak_hbm_block())
    line = json.dumps(parsed)
    blob = {"n": n, "cmd": "python bench.py serve", "rc": rc,
            "tail": line + "\n", "parsed": parsed}
    with open(os.path.join(here, "BENCH_serve.json"), "w") as fh:
        json.dump(blob, fh, indent=2)
    return line


def build_sharded_trainer(batch):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import nd, parallel
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b

    net = resnet50_v1b(classes=1000)
    net.initialize()
    net(nd.array(np.zeros((2, 3, 224, 224), np.float32)))

    def loss_fn(logits, labels):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=-1)
        return -jnp.mean(ll)

    trainer = parallel.ShardedTrainer(net, loss_fn=loss_fn,
                                      optimizer="sgd", lr=0.1,
                                      momentum=0.9, wd=1e-4)
    # bf16 compute: params to bf16 (tree-wide); optimizer math upcasts
    # to f32 internally (sgd_momentum_tree) — mp_sgd semantics
    trainer.params = {k: (v.astype(jnp.bfloat16)
                          if v.dtype == jnp.float32 and "running" not in k
                          and "gamma" not in k and "beta" not in k else v)
                      for k, v in trainer.params.items()}
    trainer.opt_state = trainer._opt_init(trainer.params)
    return trainer


def run_sharded(batch=256, warmup=2, iters=16):
    import jax
    import jax.numpy as jnp
    trainer = build_sharded_trainer(batch)
    x = np.random.randn(batch, 3, 224, 224).astype(np.float32)
    y = np.random.randint(0, 1000, batch)
    xb = jnp.asarray(x, dtype=jnp.bfloat16)
    for _ in range(warmup):
        loss = trainer.step(xb, y)
    float(np.asarray(loss))        # D2H read: the honest sync
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(xb, y)
    float(np.asarray(loss))
    return batch * iters / (time.perf_counter() - t0)


_ELASTIC_CHILD_MARK = "_BENCH_ELASTIC_CHILD"


def run_elastic(n_devices=8, kill_at=6, steps=16, steps_per_epoch=8):
    """MULTICHIP elastic scenario (ISSUE 7): kill a replica at step K
    on the n-way virtual mesh, re-admit it at the next epoch boundary;
    report steps lost + recovery wall-time.  Self-bootstrapping child
    process (dryrun_multichip's recipe): the virtual CPU platform is
    forced before jax backend init, so the caller's jax state — a real
    chip, a different device count — is never disturbed."""
    if os.environ.get(_ELASTIC_CHILD_MARK) != "1":
        import re
        import subprocess
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % n_devices).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env[_ELASTIC_CHILD_MARK] = "1"
        # the scenario's mesh-shrink black box is a real dump (the
        # trigger fires for real): scratch dir, not the checkout
        env.setdefault("MXNET_BLACKBOX_DIR", "/tmp")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--elastic-child", str(n_devices), str(kill_at),
               str(steps), str(steps_per_epoch)]
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=420, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in reversed((res.stdout or "").strip().splitlines()
                             or [""]):
            if line.startswith("{"):
                return json.loads(line)
        tail = (res.stderr or res.stdout or "").strip().splitlines()
        raise RuntimeError("elastic child failed (rc=%d): %s"
                           % (res.returncode,
                              tail[-1] if tail else "no output"))
    return _elastic_scenario(n_devices, kill_at, steps,
                             steps_per_epoch)


def _elastic_scenario(n_devices, kill_at, steps, steps_per_epoch):
    """Child-side body of run_elastic: runs on the virtual mesh."""
    import math
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    # CPU-mesh scenario: its host executables stay out of the
    # persistent cache (XLA:CPU entries are tied to the build host's
    # CPU, and the cache directory travels with a copied checkout)
    jax.config.update("jax_enable_compilation_cache", False)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import config as _ecfg, fault, gluon, nd, \
        parallel
    from incubator_mxnet_tpu.monitor import events

    in_dim, classes = 32, 8
    # batch divisible by every mesh width a single-replica loss visits
    batch = n_devices * (n_devices - 1) \
        // math.gcd(n_devices, n_devices - 1)

    def build(mesh, lr_factor):
        mx.random.seed(11)
        net = gluon.nn.HybridSequential(prefix="bel_")
        net.add(gluon.nn.Dense(64, in_units=in_dim, activation="relu",
                               prefix="bel_d1_"),
                gluon.nn.Dense(classes, in_units=64, prefix="bel_d2_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, in_dim)))
        return parallel.ShardedTrainer(net, optimizer="adam",
                                       lr=1e-2 * lr_factor, mesh=mesh)

    def data_fn(step, n_replicas):
        rs = np.random.RandomState(1000 + step)
        return (rs.randn(batch, in_dim).astype(np.float32),
                rs.randint(0, classes, batch))

    ck = tempfile.mkdtemp(prefix="bench_elastic_ck_")
    _ecfg.set("MXNET_FAULT_PLAN", "mesh.replica_down@%d" % kill_at)
    fault.reset_from_config()
    t0 = time.perf_counter()
    try:
        et = parallel.ElasticTrainer(
            build, ckpt_dir=ck, steps_per_epoch=steps_per_epoch,
            ckpt_interval=2, seed=5, handle_sigterm=False)
        losses = et.run(data_fn, steps)
    finally:
        fault.clear()
        _ecfg.unset("MXNET_FAULT_PLAN")
    wall = time.perf_counter() - t0

    shrinks = [t for t in et.transitions if t["kind"] == "shrink"]
    grows = [t for t in et.transitions if t["kind"] == "grow"]
    out = {
        "elastic_devices": n_devices,
        "zero_level": getattr(et.trainer, "zero", 0),
        "elastic_kill_step": kill_at,
        "elastic_steps_total": steps,
        "elastic_final_replicas": et.n_replicas,
        "elastic_wall_s": round(wall, 2),
        "elastic_shrinks": events.get("mesh.shrinks"),
        "elastic_grows": events.get("mesh.grows"),
        "elastic_losses_finite": bool(
            all(np.isfinite(v) for v in losses.values())),
    }
    if shrinks:
        s = shrinks[0]
        out.update({
            "elastic_shrink_step": s["step"],
            "elastic_lost_replica": s["lost"][0],
            # the acceptance numbers: work re-done and wall-clock from
            # detection to training again on the smaller mesh
            "elastic_steps_lost": s["steps_lost"],
            "elastic_recovery_s": s["wall_s"],
        })
    if grows:
        g = grows[0]
        out.update({"elastic_readmit_step": g["step"],
                    "elastic_regrow_s": g["wall_s"]})
    if et.last_blackbox:
        out["elastic_blackbox"] = os.path.basename(et.last_blackbox)
    if et.fleet is not None:
        # the merged per-replica view (ISSUE 11): step/dispatch/
        # collective µs per replica as the supervisor last saw them
        out["fleet"] = et.fleet.block()
    print(json.dumps(out))
    return out


def _write_multichip_elastic(parsed, rc=0):
    """MULTICHIP_elastic.json in the MULTICHIP_r* schema
    ({n_devices, rc, ok, skipped, tail}) so the multichip trajectory
    tooling picks the elastic scenario up alongside the scaling runs."""
    parsed = dict(parsed)
    parsed.update(_peak_hbm_block())
    # ok only when the scenario actually EXERCISED elasticity: a clean
    # rc with no shrink/grow means the fault never fired (heartbeat
    # regression, kill_at >= steps) — reporting that as a pass would be
    # a trajectory lie, not a robustness proof
    exercised = (parsed.get("elastic_shrink_step") is not None
                 and parsed.get("elastic_readmit_step") is not None)
    if exercised:
        tail = ("elastic ok: %d->%d@step%s (lost r%s, %s step(s) lost, "
                "recovery %.2fs) regrow@step%s (%.2fs) final=%d "
                "replicas\n"
                % (parsed.get("elastic_devices", 0),
                   parsed.get("elastic_devices", 1) - 1,
                   parsed.get("elastic_shrink_step", "?"),
                   parsed.get("elastic_lost_replica", "?"),
                   parsed.get("elastic_steps_lost", "?"),
                   parsed.get("elastic_recovery_s", 0.0),
                   parsed.get("elastic_readmit_step", "?"),
                   parsed.get("elastic_regrow_s", 0.0),
                   parsed.get("elastic_final_replicas", 0)))
    else:
        tail = ("elastic FAILED: scenario completed (rc=%d) but the "
                "mesh never shrank/regrew — fault plan did not fire\n"
                % rc)
    blob = {"n_devices": parsed.get("elastic_devices", 0), "rc": rc,
            "ok": rc == 0 and exercised, "skipped": False, "tail": tail,
            "parsed": parsed}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "MULTICHIP_elastic.json"), "w") as fh:
        json.dump(blob, fh, indent=2)


def _fleet_straggler_proof(n_devices, inject_at=4, stale=6, steps=12):
    """Fleet-observability proof on the virtual mesh (ISSUE 11), run
    inside the multichip child:

    1. **Straggler detection beats heartbeat staleness.**  An
       ElasticTrainer with ``mesh.replica_slow@inject_at`` injected
       and a large ``down_steps`` (the replica is alive-but-slow, the
       mesh must NOT shrink): the victim's published step times
       inflate, the skew detector (window 3 here) flags it and the
       ring gets a ``mesh.straggler`` event naming it — strictly
       before step ``inject_at + stale``, when heartbeat staleness
       would first have said "slow".
    2. **Cross-process trace merge.**  A 2-worker DecodeService feeds
       a consumer loop that stamps the global step; the workers'
       decode intervals are re-parented as ``io.decode`` spans under
       the consumer's span with the WORKER pids.  A black-box dump's
       embedded trace is then run through ``blackbox merge``: the
       merged timeline must contain spans from >= 2 processes
       correlated on the same (trace_id, step).
    """
    import tempfile

    from incubator_mxnet_tpu import config as _fcfg, fault, gluon, \
        nd, parallel, telemetry
    from incubator_mxnet_tpu.io.decode_service import (
        DecodeService, DecodeServiceUnavailable)
    from incubator_mxnet_tpu.telemetry import flightrec
    from incubator_mxnet_tpu.tools.blackbox import merge_traces

    in_dim, classes = 32, 8
    batch = n_devices * 2
    prev_tel = telemetry.enable()
    _fcfg.set("MXNET_STRAGGLER_WINDOW", "3")
    _fcfg.set("MXNET_FAULT_PLAN", "mesh.replica_slow@%d" % inject_at)
    fault.reset_from_config()
    flightrec.clear()

    def build(mesh, lr_factor):
        import incubator_mxnet_tpu as mx
        mx.random.seed(17)
        net = gluon.nn.HybridSequential(prefix="bfl_")
        net.add(gluon.nn.Dense(32, in_units=in_dim, activation="relu",
                               prefix="bfl_d1_"),
                gluon.nn.Dense(classes, in_units=32, prefix="bfl_d2_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, in_dim)))
        return parallel.ShardedTrainer(net, optimizer="sgd",
                                       lr=1e-2 * lr_factor, mesh=mesh)

    def data_fn(step, n_replicas):
        rs = np.random.RandomState(2000 + step)
        return (rs.randn(batch, in_dim).astype(np.float32),
                rs.randint(0, classes, batch))

    out = {"injected_replica": n_devices - 1,
           "inject_step": inject_at,
           "heartbeat_slow_step": inject_at + stale}
    try:
        ck = tempfile.mkdtemp(prefix="bench_fleet_ck_")
        et = parallel.ElasticTrainer(
            build, ckpt_dir=ck, ckpt_interval=4, seed=7,
            handle_sigterm=False, stale_steps=stale,
            down_steps=10 * steps)      # alive-but-slow: never shrink
        et.run(data_fn, steps)
        strag = [e for e in flightrec.ring_snapshot()
                 if e["kind"] == "mesh" and e["name"] == "straggler"]
        out["fleet_view"] = et.fleet.block() if et.fleet else {}
        if strag:
            out["straggler_replica"] = strag[0].get("replica")
            out["straggler_detected_step"] = strag[0].get("step")
            out["straggler_step_us"] = strag[0].get("step_us")
            out["straggler_fleet_median_us"] = \
                strag[0].get("fleet_median_us")
        out["straggler_ok"] = bool(
            strag
            and strag[0].get("replica") == n_devices - 1
            and strag[0].get("step", 10 ** 9)
            < out["heartbeat_slow_step"])

        # -- cross-process trace merge proof ---------------------------
        try:
            rec = _ensure_rec()
            svc = DecodeService(rec, 16, (3, 96, 96), workers=2,
                                resize=112, dtype="uint8")
            try:
                it = iter(svc)
                for s in range(4):
                    telemetry.set_global_step(1000 + s)
                    with telemetry.span("fleet.consume", replica=0):
                        next(it)
            finally:
                telemetry.set_global_step(None)
                svc.close()
            dump = flightrec.dump_blackbox(
                path=os.path.join("/tmp", "bench-fleet-trace.json"),
                reason="fleet-proof")
            merged_path = os.path.join("/tmp",
                                       "bench-fleet-merged.trace.json")
            summary = merge_traces([dump], out_path=merged_path)
            out["trace_processes"] = len(summary["processes"])
            out["trace_cross_process_steps"] = \
                summary["cross_process_steps"][:8]
            out["trace_cross_process_traces"] = \
                len(summary["cross_process_traces"])
            out["trace_merged_events"] = summary["events"]
            out["trace_ok"] = bool(
                len(summary["processes"]) >= 2
                and summary["cross_process_steps"]
                and summary["cross_process_traces"])
        except DecodeServiceUnavailable as e:
            # host incapability is a WAIVER, not a failure (the
            # check_feed/DecodeService degradation convention): the
            # trace proof needs worker processes this host can't run
            out["trace_ok"] = None
            out["trace_waived_host"] = \
                "decode service unavailable: %s" % e
        out["ok"] = bool(out["straggler_ok"]
                         and out.get("trace_ok") is not False)
    finally:
        fault.clear()
        _fcfg.unset("MXNET_FAULT_PLAN")
        _fcfg.unset("MXNET_STRAGGLER_WINDOW")
        telemetry.enable(prev_tel)
    return out


def _compile_loop_proof(n_devices):
    """ISSUE 18 acceptance, measured: (1) lax.scan layer-stacking
    collapses N per-layer executables into one with compile-wall AND
    dispatch reductions and bit parity; (2) the history-trained
    autotuner's bucket cap beats `costs.suggest_bucket_mb` on >= 2
    mesh configs by measured step wall (the probes this sweep writes
    ARE the evidence the tuner reads back — the loop, closed in one
    run)."""
    import tempfile
    import jax as _j
    import jax.numpy as jnp
    from incubator_mxnet_tpu import gluon, nd, parallel
    from incubator_mxnet_tpu.compile import autotune, stacking
    from incubator_mxnet_tpu.telemetry import costs as _tc
    from incubator_mxnet_tpu.telemetry import history as _hist
    import incubator_mxnet_tpu as mx

    out = {"ok": False}
    if not os.environ.get("MXNET_HISTORY_DIR"):
        os.environ["MXNET_HISTORY_DIR"] = \
            tempfile.mkdtemp(prefix="mxtpu-bench-hist-")
        _hist.reset()

    # -- (1) layer-stacking: 8 structurally-identical dense layers.
    # D=256 sits where BOTH wins are measurable on a host-bound mesh:
    # at much larger D the per-layer compute hides the per-dispatch
    # overhead scan removes (and scan's serialization can even lose)
    sdim, slayers = 256, 8

    def layer(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    rng = np.random.RandomState(18)
    params = [{"w": jnp.asarray(rng.randn(sdim, sdim)
                                .astype(np.float32) * 0.05),
               "b": jnp.zeros((sdim,), jnp.float32)}
              for _ in range(slayers)]
    xs = jnp.ones((8, sdim), jnp.float32)
    m = stacking.measure(layer, params, xs, calls=20,
                         label="bench.stack")
    out["stacking"] = m
    stack_ok = bool(m["parity_ok"]
                    and m["executables_stacked"]
                    < m["executables_unstacked"]
                    and m["compile_wall_stacked_s"]
                    < m["compile_wall_unstacked_s"]
                    and m["dispatch_stacked_us"]
                    <= m["dispatch_unstacked_us"] * 1.05)

    # -- (2) tuned-vs-heuristic bucket cap on 2 mesh configs: sweep a
    # cap ladder (heuristic included as a candidate), probe each
    # measured step wall into the durable history, then ask the tuner.
    # The sweep runs ZeRO-3: the heuristic's 1/32 param-bytes rule was
    # calibrated on the zero=2 gradient path and is blind to the
    # forward/backward param all-gathers zero=3 adds — exactly the
    # traffic shift a history-trained tuner sees and a one-shot
    # heuristic cannot.  D=2048 puts ~67MB of params behind the cap,
    # so the heuristic lands MID-ladder (~2MB), not on the clamp floor
    D, L, CLS = 2048, 4, 16

    def make_net():
        mx.random.seed(12)
        net = gluon.nn.HybridSequential(prefix="ct_")
        for i in range(L):
            net.add(gluon.nn.Dense(D, in_units=D, activation="relu",
                                   prefix="ct_d%d_" % i))
        net.add(gluon.nn.Dense(CLS, in_units=D, prefix="ct_out_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, D)))
        return net

    def build_tr(ndev, cap_mb):
        prev = os.environ.get("MXNET_ZERO_BUCKET_MB")
        os.environ["MXNET_ZERO_BUCKET_MB"] = str(cap_mb)
        try:
            mesh = parallel.make_mesh((ndev,), ("data",),
                                      devices=_j.devices()[:ndev])
            tr = parallel.ShardedTrainer(make_net(), optimizer="adam",
                                         lr=1e-3, mesh=mesh, zero=3)
            x = np.random.randn(ndev * 2, D).astype(np.float32)
            y = np.random.randint(0, CLS, ndev * 2)
            _j.block_until_ready(tr.step(x, y))     # warm compile
            return tr, x, y
        finally:
            if prev is None:
                os.environ.pop("MXNET_ZERO_BUCKET_MB", None)
            else:
                os.environ["MXNET_ZERO_BUCKET_MB"] = prev

    tune_cfgs = []
    beats = 0
    cfg_sizes = sorted({min(4, n_devices), n_devices}) or [2]
    if len(cfg_sizes) == 1:
        cfg_sizes = sorted({2, cfg_sizes[0]})
    for ndev in cfg_sizes:
        label = "bench.tune.nd%d" % ndev
        first = build_tr(ndev, 1.0)
        total = sum(v.nbytes for v in first[0].params.values())
        heur = _tc.suggest_bucket_mb(total, ndev)
        caps = sorted({1.0, 4.0, 16.0, round(float(heur), 2)})
        cfgs = {1.0: first}
        for cap in caps:
            if cap not in cfgs:
                cfgs[cap] = build_tr(ndev, cap)
        # interleaved best-of (the MULTICHIP sweep discipline): one VM
        # hiccup cannot poison a single cap's number
        walls = {cap: float("inf") for cap in caps}
        for _ in range(4):
            for cap in caps:
                tr, x, y = cfgs[cap]
                t0 = time.perf_counter()
                for _ in range(3):
                    loss = tr.step(x, y)
                _j.block_until_ready(loss)
                walls[cap] = min(
                    walls[cap],
                    (time.perf_counter() - t0) / 3 * 1e6)
        del cfgs, first             # free this mesh's trainers
        for cap in caps:
            autotune.note_probe("zero_bucket_mb", label, cap,
                                walls[cap])
        tuned = autotune.suggest_bucket_cap(total, ndev, label=label,
                                            ladder=caps)
        heur_key = round(float(heur), 2)
        cfg = {"n_devices": ndev, "param_bytes": int(total),
               "heuristic_cap_mb": heur_key,
               "tuned_cap_mb": float(tuned),
               "tuned_source": autotune.decisions()[-1]["source"],
               "heuristic_step_us": int(walls[heur_key]),
               "tuned_step_us": int(walls[float(tuned)]),
               "caps_swept": {str(c): int(w)
                              for c, w in walls.items()}}
        cfg["beat_heuristic"] = bool(cfg["tuned_step_us"]
                                     < cfg["heuristic_step_us"])
        beats += int(cfg["beat_heuristic"])
        tune_cfgs.append(cfg)
    out["autotune"] = {"configs": tune_cfgs,
                       "configs_beating_heuristic": beats}
    tune_ok = beats >= 2

    out["stacking_ok"] = stack_ok
    out["autotune_ok"] = tune_ok
    out["ok"] = bool(stack_ok and tune_ok)
    return out


_MULTICHIP_CHILD_MARK = "_BENCH_MULTICHIP_CHILD"


def run_multichip(n_devices=8):
    """MULTICHIP weak-scaling sweep (ISSUE 10): the overlap-first
    ZeRO-2/3 path vs the legacy single-executable step, 1->N replicas
    on an n-device virtual CPU mesh, with a per-stage breakdown
    (dispatch / collective / compute) per replica count and the ZeRO-3
    per-replica memory proof.  Self-bootstrapping child (run_elastic's
    recipe)."""
    if os.environ.get(_MULTICHIP_CHILD_MARK) != "1":
        import re
        import subprocess
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % n_devices).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env[_MULTICHIP_CHILD_MARK] = "1"
        env.setdefault("MXNET_BLACKBOX_DIR", "/tmp")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--multichip-child", str(n_devices)]
        # 900s: the sweep plus the ISSUE 11 fleet proof (an elastic
        # run + a 2-worker decode service) plus the ISSUE 18 compile
        # proof (a bucket-cap sweep + two pre-warm children) in one
        # child
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=900, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in reversed((res.stdout or "").strip().splitlines()
                             or [""]):
            if line.startswith("{"):
                return json.loads(line)
        tail = (res.stderr or res.stdout or "").strip().splitlines()
        raise RuntimeError("multichip child failed (rc=%d): %s"
                           % (res.returncode,
                              tail[-1] if tail else "no output"))
    return _multichip_scenario(n_devices)


def _multichip_scenario(n_devices):
    """Child-side sweep.  Workload: an update-dominated dense MLP with
    adam — the workload class of the weight-update-sharding paper
    (PAPERS.md), where the optimizer + collective path IS the
    multi-replica cost the tentpole attacks.  The resnet18 continuity
    sweep (r05's harness) lives in dryrun_multichip; its numbers ride
    in the tail there."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    # CPU-mesh scenario: no persistent cache (see _elastic_scenario)
    jax.config.update("jax_enable_compilation_cache", False)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd, parallel
    from incubator_mxnet_tpu.telemetry import costs as _tc

    D, L, CLS = 1024, 4, 16

    def make_net():
        mx.random.seed(12)
        net = gluon.nn.HybridSequential(prefix="mc_")
        for i in range(L):
            net.add(gluon.nn.Dense(D, in_units=D, activation="relu",
                                   prefix="mc_d%d_" % i))
        net.add(gluon.nn.Dense(CLS, in_units=D, prefix="mc_out_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, D)))
        return net

    def build(ndev, zero, no_collectives=False):
        mesh = parallel.make_mesh((ndev,), ("data",),
                                  devices=jax.devices()[:ndev])
        tr = parallel.ShardedTrainer(make_net(), optimizer="adam",
                                     lr=1e-3, mesh=mesh, zero=zero)
        x = np.random.randn(ndev * 2, D).astype(np.float32)
        y = np.random.randint(0, CLS, ndev * 2)
        loss = tr.step(x, y)            # warm compile
        import jax as _j
        _j.block_until_ready(loss)
        return tr, x, y

    sizes = []
    nd_ = 1
    while nd_ <= n_devices:
        sizes.append(nd_)
        nd_ *= 2
    cfgs = {}
    for ndev in sizes:
        for zero in (0, 2):
            cfgs[(zero, ndev)] = build(ndev, zero)
    import jax as _j
    best = {k: float("inf") for k in cfgs}
    disp = {k: 0.0 for k in cfgs}
    trials = 3
    for _ in range(trials):             # interleaved: one VM hiccup
        for key, (tr, x, y) in cfgs.items():    # cannot poison a config
            t0 = time.perf_counter()
            d_us = 0.0
            for _ in range(3):
                d0 = time.perf_counter()
                loss = tr.step(x, y)
                d_us += time.perf_counter() - d0
            _j.block_until_ready(loss)
            wall = (time.perf_counter() - t0) / 3
            if wall < best[key]:
                best[key] = wall
                # dispatch wall = async call-return (the host-side
                # share of the step; on this backend donation makes it
                # track the previous step's completion, so it is an
                # upper bound)
                disp[key] = d_us / 3
    eff = best[(2, 1)] / best[(2, sizes[-1])]
    eff_legacy = best[(0, 1)] / best[(0, sizes[-1])]

    # per-stage breakdown: compute baseline = the 1-replica step's
    # per-replica work serialized over the host's cores (what the
    # hardware can at best time-slice); collective+overhead = the rest
    cores = os.cpu_count() or 1
    breakdown = {}
    for ndev in sizes:
        step_us = best[(2, ndev)] * 1e6
        compute_us = best[(2, 1)] * 1e6 * max(1.0, ndev / cores)
        breakdown[str(ndev)] = {
            "step_us": int(step_us),
            "dispatch_us": int(disp[(2, ndev)] * 1e6),
            "compute_floor_us": int(compute_us),
            "collective_overhead_us": int(max(0.0,
                                              step_us - compute_us)),
            "legacy_step_us": int(best[(0, ndev)] * 1e6),
        }

    # ZeRO-3 memory proof on the full mesh
    tr3, x3, y3 = build(n_devices, 3)
    plan = tr3._zero_plan
    pb_local = sum(v.addressable_shards[0].data.nbytes
                   for v in tr3.params.values())
    pb_full = sum(v.nbytes for v in tr3.params.values())
    gb_local = sum(
        leaf.addressable_shards[0].data.nbytes
        for leaf in _j.tree_util.tree_leaves(tr3.opt_state))
    gb_full = sum(leaf.nbytes
                  for leaf in _j.tree_util.tree_leaves(tr3.opt_state))
    # wire bytes of the HEADLINE (8-dev zero=2) step only — the global
    # registry also holds the 1/2/4-dev and zero=3 trainers' rows,
    # which are not part of this step's per-step wire
    plan8 = cfgs[(2, sizes[-1])][0]._zero_plan
    d8 = plan8.describe()
    wire8 = d8["solo_bytes"] * 2 + d8["concat_bytes"]   # RS+AG / psum

    out = {
        "multichip_devices": n_devices,
        "zero_level": 2,
        "overlap_schedule": cfgs[(2, sizes[-1])][0]._zero_schedule,
        "bucket_cap_mb": round(plan.cap_mb, 2),
        "weak_eff": round(eff, 3),
        "weak_eff_legacy": round(eff_legacy, 3),
        "weak_eff_gain": round(eff / eff_legacy, 2) if eff_legacy
        else 0.0,
        "step_time_gain_at_%d" % sizes[-1]: round(
            best[(0, sizes[-1])] / best[(2, sizes[-1])], 2),
        "weak_scaling": {str(n): int(best[(2, n)] * 1e6)
                         for n in sizes},
        "weak_scaling_legacy": {str(n): int(best[(0, n)] * 1e6)
                                for n in sizes},
        "weak_scaling_breakdown": breakdown,
        "zero3_param_bytes_per_replica": pb_local,
        "zero3_param_frac_of_unsharded": round(pb_local / pb_full, 4),
        "zero3_opt_frac_of_unsharded": round(gb_local / gb_full, 4),
        "collective_cost_rows": len(plan8._cost_keys),
        "collective_wire_bytes_per_step": int(wire8),
        "host_cores": cores,
        # honest context: on a 2-core host, 8 virtual replicas' compute
        # alone serializes 8/cores-fold — the eff ceiling for a
        # compute/bandwidth-bound workload is cores/N regardless of
        # implementation.  The gain over the legacy path is the
        # tentpole's measurable effect.
        "host_bound_note": (
            "N virtual devices share %d host cores and one memory "
            "bus; weak_eff is bounded by ~cores/N plus the "
            "update/collective share the ZeRO path removes" % cores),
    }
    # fleet observability proof (ISSUE 11): straggler injected via
    # mesh.replica_slow → detected from published step times BEFORE
    # heartbeat staleness; 2-worker decode spans merged into one
    # cross-process chrome trace correlated on the global step.
    # Guarded: a failing proof must report ok=false, never destroy the
    # completed scaling sweep above (the JSON line IS the result)
    try:
        out["fleet"] = _fleet_straggler_proof(n_devices)
    except Exception as e:          # noqa: BLE001
        out["fleet"] = {"ok": False, "error": ("%s: %s" % (
            type(e).__name__, e))[:200]}
    # compile-loop proof (ISSUE 18): layer-stacking deltas + parity,
    # autotuned-vs-heuristic bucket cap on 2 mesh configs, pre-warm
    # manifest warm-start.  Same guard discipline as the fleet proof
    try:
        out["compile"] = _compile_loop_proof(n_devices)
    except Exception as e:          # noqa: BLE001
        out["compile"] = {"ok": False, "error": ("%s: %s" % (
            type(e).__name__, e))[:200]}
    print(json.dumps(out))
    return out


def _write_multichip_scaling(parsed, rc=0):
    """MULTICHIP_scaling.json in the MULTICHIP_r* schema ({n_devices,
    rc, ok, skipped, tail, parsed}).  ok = the sweep ran, the
    overlap-first path beat the legacy path, and ZeRO-3's per-replica
    memory is genuinely sharded — the claims this PR makes, measured;
    the raw weak_eff rides in parsed + tail with host context."""
    parsed = dict(parsed)
    parsed.update(_peak_hbm_block())
    eff = parsed.get("weak_eff", 0.0)
    eff_l = parsed.get("weak_eff_legacy", 0.0)
    frac = parsed.get("zero3_param_frac_of_unsharded", 1.0)
    exercised = (eff > 0 and eff_l > 0
                 and parsed.get("collective_cost_rows", 0) > 0)
    improved = eff > eff_l and frac <= 0.5
    # the ISSUE 10 acceptance bar (weak_eff >= 0.3) is ENFORCED on
    # hosts whose compute ceiling (cores/N: N virtual replicas
    # time-slice the host cores) can reach it; below that ceiling the
    # bar is waived as host-bound — explicitly recorded either way so
    # a regression on a capable host cannot hide behind ok=true
    cores = parsed.get("host_cores", 0) or 1
    ndev = parsed.get("multichip_devices", 1) or 1
    ceiling = cores / float(ndev)
    target_met = eff >= 0.3
    waived = ceiling < 0.3
    parsed["weak_eff_target"] = 0.3
    parsed["weak_eff_target_met"] = target_met
    parsed["weak_eff_target_waived_host_bound"] = (not target_met
                                                   and waived)
    fleet = parsed.get("fleet", {})
    comp = parsed.get("compile", {})
    cstack = comp.get("stacking", {})
    ctune = comp.get("autotune", {})
    tail = ("multichip scaling: weak_eff=%.2f (legacy %.2f, %.1fx) "
            "zero=%s sched=%s buckets cap=%.1fMB zero3 param "
            "bytes/replica=%.0f%% of unsharded, %d collective rows, "
            "%d host cores%s\n"
            "fleet: straggler r%s detected@step%s (heartbeat would "
            "say slow@step%s), trace merge %s proc / steps %s -> %s\n"
            "compile: stack %s exes -> %s (compile wall %.2fs -> "
            "%.2fs, dispatch %sus -> %sus, parity %s), tuner beat "
            "heuristic on %s/2 cfgs -> %s\n"
            % (eff, eff_l, parsed.get("weak_eff_gain", 0.0),
               parsed.get("zero_level"),
               parsed.get("overlap_schedule"),
               parsed.get("bucket_cap_mb", 0.0), frac * 100,
               parsed.get("collective_cost_rows", 0),
               parsed.get("host_cores", 0),
               "" if eff >= 0.3 else " [host-bound: see "
               "host_bound_note]",
               fleet.get("straggler_replica", "?"),
               fleet.get("straggler_detected_step", "?"),
               fleet.get("heartbeat_slow_step", "?"),
               fleet.get("trace_processes", 0),
               fleet.get("trace_cross_process_steps", []),
               "ok" if fleet.get("ok") else "FAILED",
               cstack.get("executables_unstacked", "?"),
               cstack.get("executables_stacked", "?"),
               cstack.get("compile_wall_unstacked_s", 0.0),
               cstack.get("compile_wall_stacked_s", 0.0),
               cstack.get("dispatch_unstacked_us", "?"),
               cstack.get("dispatch_stacked_us", "?"),
               cstack.get("parity_ok", "?"),
               ctune.get("configs_beating_heuristic", 0),
               "ok" if comp.get("ok") else "FAILED"))
    blob = {"n_devices": parsed.get("multichip_devices", 0), "rc": rc,
            "ok": (rc == 0 and exercised and improved
                   and (target_met or waived)
                   and bool(fleet.get("ok"))
                   and bool(comp.get("ok"))),
            "skipped": False, "tail": tail, "parsed": parsed}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "MULTICHIP_scaling.json"), "w") as fh:
        json.dump(blob, fh, indent=2)


_INTEGRITY_CHILD_MARK = "_BENCH_INTEGRITY_CHILD"


def run_integrity(n_devices=4, steps=10, steps_per_epoch=4):
    """End-to-end integrity chaos scenario (ISSUE 9 acceptance): ONE
    run injecting a checkpoint bitflip, in-flight record corruption,
    and a replica divergence — training must complete with the
    corrupt checkpoint salvaged from keep-K, exactly the poisoned
    records quarantined (budget respected, clean-record stream
    bit-identical to an uninjected pass), the divergent replica
    evicted and re-admitted, and black-box forensics naming each
    culprit.  Self-bootstrapping child on an n-device virtual CPU
    mesh (run_elastic's recipe)."""
    if os.environ.get(_INTEGRITY_CHILD_MARK) != "1":
        import re
        import subprocess
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % n_devices).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env[_INTEGRITY_CHILD_MARK] = "1"
        env.setdefault("MXNET_BLACKBOX_DIR", "/tmp")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--integrity-child", str(n_devices), str(steps),
               str(steps_per_epoch)]
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=420, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in reversed((res.stdout or "").strip().splitlines()
                             or [""]):
            if line.startswith("{"):
                return json.loads(line)
        tail = (res.stderr or res.stdout or "").strip().splitlines()
        raise RuntimeError("integrity child failed (rc=%d): %s"
                           % (res.returncode,
                              tail[-1] if tail else "no output"))
    return _integrity_scenario(n_devices, steps, steps_per_epoch)


def _integrity_scenario(n_devices, steps, steps_per_epoch):
    """Child-side body of run_integrity."""
    import math
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    # CPU-mesh scenario: no persistent cache (see _elastic_scenario)
    jax.config.update("jax_enable_compilation_cache", False)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import config as _icfg, fault, gluon, \
        integrity, nd, parallel
    from incubator_mxnet_tpu.io import recordio
    from incubator_mxnet_tpu.monitor import events

    out = {}
    t0 = time.perf_counter()

    # ---- phase 1: corrupt-record quarantine on the record pipeline --
    n_rec, poisoned = 32, 2
    d = tempfile.mkdtemp(prefix="bench_integrity_io_")
    rec = os.path.join(d, "data.rec")
    w = recordio.MXRecordIO(rec, "w")
    for i in range(n_rec):
        img = ((np.arange(16 * 16 * 3, dtype=np.int64) * 7 + i * 13)
               % 251).astype(np.uint8).reshape(16, 16, 3)
        w.write(recordio.pack_img((0, float(i), i, 0), img,
                                  img_fmt=".jpg"))
    w.close()
    recordio.write_crc_sidecar(rec)

    def collect():
        it = mx.io.ImageRecordIter(path_imgrec=rec,
                                   data_shape=(3, 16, 16),
                                   batch_size=8, dtype="uint8")
        got = {}
        for b in it:
            k = b.data[0].shape[0] - b.pad
            lab = b.label[0].asnumpy()
            arr = b.data[0].asnumpy()
            for j in range(k):
                got[int(lab[j])] = arr[j].copy()
        it.close()
        return got

    base = collect()
    c0 = events.get("io.decode.records_corrupt")
    fault.install("io.corrupt", at_calls=[5], times=poisoned)
    try:
        got = collect()
    finally:
        fault.clear("io.corrupt")
    quarantined = events.get("io.decode.records_corrupt") - c0
    budget = int(_icfg.get("MXNET_IO_CORRUPT_BUDGET"))
    out.update({
        "integrity_records_total": n_rec,
        "integrity_records_poisoned": poisoned,
        "integrity_records_quarantined": int(quarantined),
        "integrity_corrupt_budget": budget,
        "integrity_budget_respected": bool(quarantined <= budget),
        "integrity_clean_stream_bit_identical": bool(
            len(got) == n_rec - quarantined and
            all(np.array_equal(base[k], got[k]) for k in got)),
        "integrity_quarantine_file": os.path.basename(
            integrity.quarantine_path()),
    })

    # ---- phase 2: checkpoint bitflip + replica divergence, one
    # elastic run — salvage then eviction then re-admission ----------
    in_dim, classes = 32, 8
    batch = n_devices * (n_devices - 1) \
        // math.gcd(n_devices, n_devices - 1)

    def build(mesh, lr_factor):
        mx.random.seed(11)
        net = gluon.nn.HybridSequential(prefix="biz_")
        net.add(gluon.nn.Dense(64, in_units=in_dim, activation="relu",
                               prefix="biz_d1_"),
                gluon.nn.Dense(classes, in_units=64, prefix="biz_d2_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, in_dim)))
        return parallel.ShardedTrainer(net, optimizer="adam",
                                       lr=1e-2 * lr_factor, mesh=mesh)

    def data_fn(step, n_replicas):
        rs = np.random.RandomState(1000 + step)
        return (rs.randn(batch, in_dim).astype(np.float32),
                rs.randint(0, classes, batch))

    ck = tempfile.mkdtemp(prefix="bench_integrity_ck_")
    # both at step 6: the bitflip corrupts the checkpoint published at
    # step 6 (end of step 5), the audit then detects the divergence AT
    # step 6 — so the eviction's restore finds its newest checkpoint
    # corrupt and must salvage the previous one from keep-K (the
    # full detect → quarantine → salvage → evict chain in one step)
    bitflip_at, diverge_at = 6, 6
    _icfg.set("MXNET_FAULT_PLAN",
              "ckpt.bitflip@%dx1;mesh.replica_divergence@%dx1"
              % (bitflip_at, diverge_at))
    fault.reset_from_config()
    try:
        et = parallel.ElasticTrainer(
            build, ckpt_dir=ck, steps_per_epoch=steps_per_epoch,
            ckpt_interval=2, seed=5, handle_sigterm=False,
            audit_interval=2)
        losses = et.run(data_fn, steps)
    finally:
        fault.clear()
        _icfg.unset("MXNET_FAULT_PLAN")

    shrinks = [t for t in et.transitions if t["kind"] == "shrink"]
    sdc_shr = [t for t in shrinks if t.get("reason") == "sdc"]
    grows = [t for t in et.transitions if t["kind"] == "grow"]
    out.update({
        "integrity_devices": n_devices,
        "integrity_steps_total": steps,
        "integrity_ckpt_bitflip_step": bitflip_at,
        "integrity_sdc_injected_step": diverge_at,
        "integrity_ckpt_corrupt": events.get("integrity.ckpt_corrupt"),
        "integrity_ckpt_salvaged": events.get(
            "integrity.ckpt_salvaged"),
        "integrity_sdc_detected": events.get("integrity.sdc"),
        "integrity_sdc_evicted": events.get("mesh.sdc_evicted"),
        "integrity_final_replicas": et.n_replicas,
        "integrity_losses_finite": bool(
            all(np.isfinite(v) for v in losses.values())),
        "integrity_wall_s": round(time.perf_counter() - t0, 2),
    })
    if sdc_shr:
        s = sdc_shr[0]
        out.update({
            "integrity_sdc_evicted_replica": s["lost"][0],
            "integrity_sdc_evict_step": s["step"],
            "integrity_salvage_resumed_step": s["resumed_step"],
        })
    if grows:
        out["integrity_readmit_step"] = grows[0]["step"]
    if et.last_blackbox:
        out["integrity_blackbox"] = os.path.basename(et.last_blackbox)
    print(json.dumps(out))
    return out


def _write_bench_integrity(parsed, rc=0):
    """BENCH_integrity.json: the chaos scenario's proof artifact —
    ok only when every injected corruption was DETECTED and RECOVERED
    (quarantine exact + budget respected + clean stream bit-identical,
    checkpoint salvaged, divergent replica evicted, run completed)."""
    parsed = dict(parsed)
    parsed.update(_peak_hbm_block())
    exercised = (
        parsed.get("integrity_records_quarantined") ==
        parsed.get("integrity_records_poisoned") and
        parsed.get("integrity_budget_respected") is True and
        parsed.get("integrity_clean_stream_bit_identical") is True and
        parsed.get("integrity_ckpt_corrupt", 0) >= 1 and
        parsed.get("integrity_ckpt_salvaged", 0) >= 1 and
        parsed.get("integrity_sdc_detected", 0) >= 1 and
        parsed.get("integrity_sdc_evicted", 0) >= 1 and
        parsed.get("integrity_readmit_step") is not None and
        parsed.get("integrity_losses_finite") is True)
    if exercised:
        tail = ("integrity ok: %d/%d poisoned records quarantined "
                "(clean stream bit-identical), ckpt bitflip@%s "
                "salvaged (resumed step %s), SDC replica %s evicted@"
                "%s readmitted@%s, final=%d replicas, blackbox=%s\n"
                % (parsed.get("integrity_records_quarantined"),
                   parsed.get("integrity_records_poisoned"),
                   parsed.get("integrity_ckpt_bitflip_step"),
                   parsed.get("integrity_salvage_resumed_step", "?"),
                   parsed.get("integrity_sdc_evicted_replica", "?"),
                   parsed.get("integrity_sdc_evict_step", "?"),
                   parsed.get("integrity_readmit_step", "?"),
                   parsed.get("integrity_final_replicas", 0),
                   parsed.get("integrity_blackbox", "?")))
    else:
        tail = ("integrity FAILED: rc=%d but a corruption went "
                "undetected or unrecovered — parsed has the per-leg "
                "booleans\n" % rc)
    blob = {"n_devices": parsed.get("integrity_devices", 0), "rc": rc,
            "ok": rc == 0 and exercised, "skipped": False,
            "tail": tail, "parsed": parsed}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_integrity.json"), "w") as fh:
        json.dump(blob, fh, indent=2)


def _cfg_integrity():
    parsed = run_integrity()
    try:
        _write_bench_integrity(parsed)      # proof artifact rides along
    except Exception:
        pass
    return parsed


_CTL_CHILD_MARK = "_BENCH_CTL_CHILD"


def run_controlplane(n_devices=4, duration_s=14.0, capacity_s=2.0,
                     seed=0):
    """Control-plane chaos scenario (ISSUE 16 acceptance): ONE run in
    which the load doubles mid-run AND a bad model version ships —
    and the fleet recovers BOTH without an operator.  A
    FleetSupervisor watches the live SLO surface; the bad canary
    (model.bad_version: stalls + sign-flips) must be rolled back
    automatically with the breaching rule named in a proactive
    blackbox dump, and the load spike (serve.load_spike doubles the
    open-loop Poisson rate) must drive a ledger-admitted scale-up
    that brings the hi lane back inside its deadline.
    Self-bootstrapping child on an n-device virtual CPU host
    (run_integrity's recipe)."""
    if os.environ.get(_CTL_CHILD_MARK) != "1":
        import re
        import subprocess
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % n_devices).strip()
        env["JAX_PLATFORMS"] = "cpu"
        env[_CTL_CHILD_MARK] = "1"
        env.setdefault("MXNET_BLACKBOX_DIR", "/tmp")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--controlplane-child", str(n_devices),
               str(duration_s), str(capacity_s), str(seed)]
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=420, env=env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in reversed((res.stdout or "").strip().splitlines()
                             or [""]):
            if line.startswith("{"):
                return json.loads(line)
        tail = (res.stderr or res.stdout or "").strip().splitlines()
        raise RuntimeError("controlplane child failed (rc=%d): %s"
                           % (res.returncode,
                              tail[-1] if tail else "no output"))
    return _controlplane_scenario(n_devices, duration_s, capacity_s,
                                  seed)


def build_controlplane_model(seed=0, in_dim=32):
    """Small Dense net + priming forward — shared by
    `bench.py controlplane` and tools/check_controlplane.py so the CI
    gate and the bench exercise the same workload."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize(ctx=mx.cpu())
    rs = np.random.RandomState(seed)
    net(nd.array(rs.randn(2, in_dim).astype(np.float32)))
    return net


def controlplane_trial(n_devices=4, duration_s=14.0, capacity_s=2.0,
                       seed=0, stall_s=0.04):
    """The supervised-fleet chaos timeline — shared by the bench
    scenario and tools/check_controlplane.py (same contract
    discipline as measure_serve_capacity):

      t=0      v1 serving (1 replica); every batch stalls `stall_s`
               (fault: serve.slow) so the service time is
               SLEEP-DOMINATED — capacity is ~batch/stall per
               replica and scale-out genuinely multiplies it even on
               a 1-core virtual-device host.  Open-loop Poisson at
               0.7x measured capacity across hi/lo lanes
      t=1.0s   a BAD v2 ships through the supervisor
               (fault: model.bad_version) -> its version-labeled
               rules must fire -> automatic rollback + blackbox dump
      t=4.5s   the load DOUBLES (fault: serve.load_spike) -> the lo
               lane's shed burn fires -> supervisor scales the
               replica set up through the ledger
      end      hi-lane outcomes submitted after the scale-up settles
               must be back inside the deadline

    Verdict `controlplane_ok`: True / False / None (None = the open
    loop never actually overloaded the engine — a starved submitter
    can't prove the scale leg either way)."""
    import threading
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import config as _icfg
    from incubator_mxnet_tpu import fault
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.serving import (
        FleetSupervisor, ModelRegistry, Shed, QueueFull,
        DeadlineExceeded, EngineClosed, CircuitOpen)
    from incubator_mxnet_tpu.telemetry import slo as _slo

    flow_errors = (Shed, QueueFull, DeadlineExceeded, EngineClosed,
                   CircuitOpen)
    rs = np.random.RandomState(seed)
    in_dim = 32
    data = rs.rand(256, in_dim).astype(np.float32)
    pool = [mx.cpu(i) for i in range(n_devices)]

    reg = ModelRegistry(devices=pool)
    reg.register("m", build_controlplane_model(seed, in_dim),
                 replicas=1, version="v1", example_shape=(in_dim,),
                 max_batch=8, queue_cap=64,
                 lanes=("cap", "hi", "lo"),
                 lane_quotas=(1.0, 1.0, 0.75))
    reg.warmup("m")
    eng = reg.engine("m")
    # pin the service time: every batch (v1, canary, and any replica
    # the supervisor adds) takes >= stall_s, so measured capacity is
    # ~max_batch/stall per replica and a second replica really does
    # double it
    fault.install("serve.slow", at_calls=[1], times=10 ** 9,
                  seconds=stall_s)
    capacity = measure_serve_capacity(eng, data, capacity_s)
    hi_dl = overload_deadline_s(8, capacity)
    lo_dl = 2.0 * hi_dl
    reg.install_slo_rules(targets={"hi": hi_dl, "lo": lo_dl},
                          fast_s=1.0, slow_s=2.5)
    # the bad version's taint: stall well past the hi deadline so the
    # canary's OWN labeled rules (shed burn / p99) must catch it
    _icfg.set("MXNET_CTL_DEGRADE_S", 2.0 * hi_dl)

    sup = FleetSupervisor(
        reg, "m", lanes=("hi", "lo"), min_replicas=1,
        max_replicas=n_devices, tick_s=0.25, up_rounds=2,
        down_rounds=200, cooldown_s=2.0, observe_rounds=2,
        canary_fraction=0.3, fast_s=1.0, slow_s=2.5)
    sup.start()

    results, rlock = [], threading.Lock()
    deploy_err = [None]

    def _deploy():
        fault.install("model.bad_version")
        try:
            sup.deploy(build_controlplane_model(seed + 1, in_dim),
                       "v2")
        except Exception as e:      # noqa: BLE001 — reported in the
            deploy_err[0] = str(e)[:200]    # verdict, not fatal

    def _track(lane, t_sub, fut):
        def cb(f):
            t = time.perf_counter()
            try:
                f.result()
                ok = True
            except flow_errors:
                ok = False
            with rlock:
                results.append((lane, t_sub, t, ok))
        fut.add_done_callback(cb)

    rate0 = 0.7 * capacity
    rate = rate0
    hi_frac = 0.35
    t0 = time.perf_counter()
    next_t, n_offered = t0, 0
    deployed = spike_armed = spiked = False
    t_spike = t_scale = None
    n_spike_offered = 0
    while True:
        now = time.perf_counter()
        if now >= t0 + duration_s:
            break
        if not deployed and now - t0 >= 1.0:
            deployed = True
            threading.Thread(target=_deploy, daemon=True).start()
        if not spike_armed and now - t0 >= 4.5:
            spike_armed = True
            fault.install("serve.load_spike")
        if spike_armed and not spiked \
                and fault.should_fire("serve.load_spike"):
            spiked, t_spike, rate = True, now, 2.0 * rate0
        if t_scale is None \
                and events.get("controlplane.scale_ups") >= 1:
            t_scale = now
        if now < next_t:
            time.sleep(min(next_t - now, 0.002))
            continue
        next_t += rs.exponential(1.0 / rate)
        lane = "hi" if rs.rand() < hi_frac else "lo"
        dl = hi_dl if lane == "hi" else lo_dl
        n_offered += 1
        if spiked:
            n_spike_offered += 1
        try:
            _track(lane, now, reg.submit(
                "m", data[n_offered % 256], deadline=dl, lane=lane,
                tenant="t%d" % (n_offered % 4)))
        except flow_errors:
            with rlock:
                results.append((lane, now, now, False))
    wall = time.perf_counter() - t0
    # drain: every pending future resolves through its callback
    reg.drain_all(timeout=60.0)
    time.sleep(0.2)
    if t_scale is None and events.get("controlplane.scale_ups") >= 1:
        t_scale = time.perf_counter()       # landed during drain
    sup.stop()
    status = sup.status()
    last_rb = sup.last_rollback

    with rlock:
        rows = list(results)
    achieved_spike = (n_spike_offered / max(1e-6, wall -
                      (t_spike - t0))) if t_spike is not None else 0.0
    overloaded = bool(t_spike is not None
                      and achieved_spike >= 1.15 * capacity)
    # post-scale hi outcomes, after a settle window; a SHED request
    # counts as +inf latency — "p99 recovered" must not be satisfied
    # by shedding the lane
    post = sorted((t_done - t_sub) if ok else float("inf")
                  for lane, t_sub, t_done, ok in rows
                  if lane == "hi" and t_scale is not None
                  and t_sub >= t_scale + 0.5)
    hi_p99_post = post[min(len(post) - 1,
                           int(0.99 * len(post)))] if post else None

    rollbacks = events.get("controlplane.rollbacks")
    scale_ups = events.get("controlplane.scale_ups")
    bb = (last_rb or {}).get("blackbox")
    out = {
        "controlplane_devices": n_devices,
        "controlplane_capacity_ips": round(capacity, 1),
        "controlplane_hi_deadline_ms": round(hi_dl * 1e3, 1),
        "controlplane_duration_s": round(wall, 2),
        "controlplane_offered": n_offered,
        "controlplane_spike_achieved_ips": round(achieved_spike, 1),
        "controlplane_overloaded": overloaded,
        "controlplane_deploys": events.get("controlplane.deploys"),
        "controlplane_deploy_error": deploy_err[0],
        "controlplane_rollbacks": rollbacks,
        "controlplane_rollback_rule": (last_rb or {}).get("rule"),
        "controlplane_rollback_version":
            (last_rb or {}).get("version"),
        "controlplane_rollback_blackbox":
            os.path.basename(bb) if bb else None,
        "controlplane_scale_ups": scale_ups,
        "controlplane_scale_denied":
            events.get("controlplane.scale_denied"),
        "controlplane_replicas_final": status["replicas"],
        "controlplane_hi_post_scale_n": len(post),
        "controlplane_hi_p99_post_scale_ms":
            (round(hi_p99_post * 1e3, 1)
             if hi_p99_post not in (None, float("inf"))
             else (None if hi_p99_post is None else "inf")),
    }
    canary_ok = bool(
        rollbacks >= 1 and out["controlplane_rollback_rule"]
        and out["controlplane_rollback_version"] == "v2"
        and bb and os.path.exists(bb))
    scale_judgeable = overloaded and len(post) >= 20
    scale_ok = bool(
        scale_judgeable and scale_ups >= 1
        and hi_p99_post is not None and hi_p99_post <= hi_dl)
    if canary_ok and scale_ok:
        out["controlplane_ok"] = True
    elif canary_ok and not scale_judgeable:
        out["controlplane_ok"] = None       # starved open loop: the
                                            # scale leg is unjudged
    else:
        out["controlplane_ok"] = False
    # teardown in dependency order; config/fault/rules must not leak
    # into the next trial (the gate runs best-of-3 in one process)
    sup.close()
    fault.clear()
    _slo.clear_rules()
    reg.close()
    _icfg.unset("MXNET_CTL_DEGRADE_S")
    return out


def _controlplane_scenario(n_devices, duration_s, capacity_s, seed):
    """Child-side body of run_controlplane."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    out = controlplane_trial(n_devices, duration_s, capacity_s, seed)
    print(json.dumps(out))
    return out


def _write_bench_controlplane(parsed, rc=0):
    """BENCH_controlplane.json: the chaos scenario's proof artifact —
    ok only when the fleet recovered BOTH injected incidents on its
    own (bad version rolled back with the breaching rule named +
    blackbox dumped, load spike absorbed by a ledger-admitted
    scale-up with the hi lane back inside its deadline)."""
    parsed = dict(parsed)
    parsed.update(_peak_hbm_block())
    ok = parsed.get("controlplane_ok")
    if ok is True:
        tail = ("controlplane ok: v2 rolled back by rule %s "
                "(blackbox=%s), load spike absorbed by scale-up to "
                "%s replicas (hi p99 post-scale %sms <= %sms), zero "
                "operator steps\n"
                % (parsed.get("controlplane_rollback_rule"),
                   parsed.get("controlplane_rollback_blackbox"),
                   parsed.get("controlplane_replicas_final"),
                   parsed.get("controlplane_hi_p99_post_scale_ms"),
                   parsed.get("controlplane_hi_deadline_ms")))
    elif ok is None:
        tail = ("controlplane INCONCLUSIVE: canary leg green but the "
                "open loop never overloaded the engine (achieved %s "
                "ips vs capacity %s) — scale leg unjudged\n"
                % (parsed.get("controlplane_spike_achieved_ips"),
                   parsed.get("controlplane_capacity_ips")))
    else:
        tail = ("controlplane FAILED: rc=%d — parsed has the per-leg "
                "evidence (rollback rule/blackbox, scale-ups, "
                "post-scale p99)\n" % rc)
    blob = {"n_devices": parsed.get("controlplane_devices", 0),
            "rc": rc, "ok": ok is True, "skipped": ok is None,
            "tail": tail, "parsed": parsed}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "BENCH_controlplane.json"),
              "w") as fh:
        json.dump(blob, fh, indent=2)


def _cfg_controlplane():
    parsed = run_controlplane()
    try:
        _write_bench_controlplane(
            parsed, rc=0 if parsed.get("controlplane_ok")
            is not False else 1)            # proof artifact rides
    except Exception:
        pass
    return parsed


def run_int8_infer(batch=64, warmup=3, iters=20):
    """Optional extra: post-training-quantized (int8, naive calib)
    ResNet-50 inference, images/sec — the deploy-side MXU int8 story
    (ref: example/quantization/imagenet_inference.py)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.contrib.quantization import quantize_net
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b

    ctx = mx.gpu()
    net = resnet50_v1b(classes=1000)
    net.initialize(ctx=ctx)
    rs = np.random.RandomState(0)
    calib = [nd.array(rs.randn(8, 3, 224, 224).astype(np.float32),
                      ctx=ctx) for _ in range(2)]
    net(calib[0])
    qnet = quantize_net(net, calib_data=calib, calib_mode="naive")
    qnet.hybridize(static_alloc=True, static_shape=True)
    x = nd.array(rs.randn(batch, 3, 224, 224).astype(np.float32),
                 ctx=ctx)
    for _ in range(warmup):
        out = qnet(x)
    float(out.reshape((-1,))[:1].asnumpy()[0])    # forced D2H sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = qnet(x)
    float(out.reshape((-1,))[:1].asnumpy()[0])
    return batch * iters / (time.perf_counter() - t0)


def _quality_dataset(n=6144, classes=10, size=32, noise=1.0,
                     amp=0.18, seed=7):
    """Deterministic CIFAR-shaped synthetic set: class = weak fixed
    random template (amp ≪ noise) + per-sample gaussian noise.  The
    per-pixel SNR is ~amp/noise = 0.18, so single pixels carry almost
    no signal and the net must integrate the whole template over
    several epochs — the loss/accuracy CURVE (not just the endpoint)
    is the regression baseline."""
    rs = np.random.RandomState(seed)
    templates = amp * rs.randn(classes, 3, size, size).astype(np.float32)
    y = rs.randint(0, classes, n).astype(np.float32)
    x = templates[y.astype(int)] + \
        noise * rs.randn(n, 3, size, size).astype(np.float32)
    return x, y


def run_quality(epochs=8, batch=256, train_n=5120, eval_n=1024,
                amp=0.18):
    """Optional quality config (VERDICT r4 next #8): a budgeted ON-CHIP
    convergence run — thumbnail ResNet-18 (the resnet20-class CIFAR
    geometry) on a deterministic synthetic 10-class set — so "matches
    reference model quality" has an internal regression baseline
    (BASELINE.md's quality row; SURVEY §6).  Emits final eval accuracy
    + a per-epoch loss curve; tests/assets/r5/quality_curve.json holds
    the r5 reference curve."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    ctx = mx.gpu()
    mx.random.seed(42)
    net = resnet18_v1(classes=10, thumbnail=True)
    net.initialize(ctx=ctx, init=mx.init.Xavier())
    net.hybridize(static_alloc=True, static_shape=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9,
                             "wd": 1e-4})
    x_np, y_np = _quality_dataset(train_n + eval_n, amp=amp)
    xt, yt = x_np[:train_n], y_np[:train_n]
    xe, ye = x_np[train_n:], y_np[train_n:]
    def eval_acc():
        # plain forward outside record: BN runs on running stats
        correct = 0
        for i in range(0, eval_n, batch):
            out = net(nd.array(xe[i:i + batch], ctx=ctx))
            pred = out.asnumpy().argmax(axis=1)
            correct += int((pred == ye[i:i + batch]).sum())
        return correct / eval_n

    curve, acc_curve = [], []
    for ep in range(epochs):
        tot = 0.0
        nb = 0
        for i in range(0, train_n, batch):
            xb = nd.array(xt[i:i + batch], ctx=ctx)
            yb = nd.array(yt[i:i + batch], ctx=ctx)
            with ag.record():
                l = loss_fn(net(xb), yb)
                l.backward()
            trainer.step(batch)
            tot += float(l.mean().asnumpy())
            nb += 1
        curve.append(round(tot / nb, 4))
        acc_curve.append(round(eval_acc(), 4))
    return {"quality_resnet18_synth_eval_acc": acc_curve[-1],
            "quality_loss_curve": curve,
            "quality_acc_curve": acc_curve,
            "quality_epochs": epochs}


#: documented accuracy bound for the int8 serving path (absolute top-1
#: delta vs the f32 model on the quality-config dataset).  check_quant
#: imports it so the CI gate and the bench judge the same contract.
QUANT_ACC_DELTA_BOUND = 0.02


def backend_dtype_gemm_ratio(dtype="int8", n=1024, m=64, iters=8):
    """f32-wall / `dtype`-wall of a jitted GEMM on THIS backend —
    ≥ 1.0 means the backend has a native (profitable) low-precision
    matmul path (MXU int8/bf16), < 1.0 means it emulates (XLA-CPU
    upcasts int8 element-wise, ~10-50x slower).  The quant bench and
    tools/check_quant.py both use this probe to decide whether the
    int8/bf16 THROUGHPUT contracts are judgeable on this host — the
    accuracy/packing/zero-recompile contracts are judged regardless."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    rs = np.random.RandomState(0)
    af = jnp.asarray(rs.randn(m, n).astype(np.float32))
    bf = jnp.asarray(rs.randn(n, n).astype(np.float32))
    if dtype == "int8":
        a = jnp.asarray(rs.randint(-127, 127, (m, n), dtype=np.int8))
        b = jnp.asarray(rs.randint(-127, 127, (n, n), dtype=np.int8))
        f_lp = jax.jit(lambda x, w: lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32))
    else:
        a = af.astype(jnp.bfloat16)
        b = bf.astype(jnp.bfloat16)
        f_lp = jax.jit(lambda x, w: x @ w)
    f_f32 = jax.jit(lambda x, w: x @ w)

    def wall(f, x, w):
        import jax as _j
        _j.block_until_ready(f(x, w))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(x, w)
        _j.block_until_ready(out)
        return time.perf_counter() - t0

    return wall(f_f32, af, bf) / max(wall(f_lp, a, b), 1e-9)


def _quant_mlp(seed=1234, in_units=3072, hidden=256, classes=10):
    """The quant config's model: a Dense/GEMM classifier over the
    flattened quality-config images.  Dense (not conv) deliberately:
    the int8 serving path is the MXU int8-GEMM story, and on backends
    that EMULATE int8 (this CPU) an int8 conv net would burn the whole
    bench budget proving only that emulation is slow — the probe
    records that separately."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Flatten(),
            gluon.nn.Dense(hidden, activation="relu",
                           in_units=in_units),
            gluon.nn.Dense(classes, in_units=hidden))
    net.initialize(force_reinit=True)
    return net


def _measure_engine_serve(net, imgs, n, seed, ctx, max_batch=16,
                          capacity_s=1.5):
    """Warm an engine on `net` and report (a) closed-loop saturation
    throughput via the SHARED measure_serve_capacity (bounded
    outstanding work — a burst-submitted stream would instead measure
    the dispatcher's max_wait coalesce window on fast executables),
    (b) client-observed latency tails over the run_serve mixed-size
    request stream (per-request submit→done walls via done-callbacks,
    so two engines measured back-to-back never share a percentile
    ring), and (c) the post-warmup serve.traces delta — the
    zero-recompile contract."""
    import threading
    from incubator_mxnet_tpu.monitor import events
    rs = np.random.RandomState(seed)
    eng = net.inference_engine(ctx=ctx, max_batch=max_batch,
                               queue_cap=max(64, n))
    try:
        warm = eng.warmup(example_shape=imgs.shape[1:],
                          wire_dtype="float32")
        traces0 = events.get("serve.traces")
        capacity = measure_serve_capacity(eng, imgs, capacity_s,
                                          batch=8)
        lats, lock = [], threading.Lock()

        def track(t_sub):
            def cb(_f):
                dt = time.perf_counter() - t_sub
                with lock:
                    lats.append(dt)
            return cb

        futs = []
        t0 = time.perf_counter()
        i = 0
        while i < n:
            k = int(rs.choice((1, 1, 2, 3, 5, 8)))
            k = min(k, n - i)
            f = eng.submit(imgs[i]) if k == 1 else \
                eng.submit_batch(imgs[i:i + k])
            f.add_done_callback(track(time.perf_counter()))
            futs.append(f)
            i += k
        for f in futs:
            r = f.result(timeout=300)
            # a server RETURNS results: one-element D2H per request,
            # identical on both variants (symmetric comparison)
            float(r.reshape((-1,))[:1].asnumpy()[0])
        stream_rate = n / (time.perf_counter() - t0)
        traces_delta = events.get("serve.traces") - traces0
        # result() can return BEFORE the future's done-callbacks run
        # (set_result notifies waiters first): wait for every latency
        # sample to land before reading the list, or the sort below
        # races the last appends and p99 drops the slowest requests —
        # exactly the samples a tail metric exists for
        t_cb = time.monotonic() + 10.0
        while time.monotonic() < t_cb:
            with lock:
                if len(lats) >= len(futs):
                    break
            time.sleep(0.002)
    finally:
        eng.close()
    with lock:
        lats = sorted(lats)

    def pct(p):
        return lats[min(len(lats) - 1,
                        max(0, int(round(p * len(lats))) - 1))]

    return {"images_per_sec": round(capacity, 2),
            "stream_images_per_sec": round(stream_rate, 2),
            "p50_ms": round(pct(0.50) * 1e3, 3),
            "p99_ms": round(pct(0.99) * 1e3, 3),
            "traces_after_warmup_delta": int(traces_delta),
            "warmup_wall_s": warm["wall_s"]}


def run_quant(epochs=3, batch=256, train_n=2560, eval_n=512,
              serve_n=256, amp_steps=12, extra=None):
    """Quant config (ISSUE 15): int8 serving + bf16 AMP training as
    first-class paths, measured end to end.

    Four parts, merged into BENCH_serve.json:
    1. ACCURACY — train the quant MLP on the quality-config dataset,
       post-training-quantize a parameter-identical copy (naive
       calibration over train batches), report f32 vs int8 top-1 and
       the delta against QUANT_ACC_DELTA_BOUND.
    2. SERVING — the same mixed-size request stream run_serve uses,
       driven at an f32 engine and at the int8 engine: throughput,
       client-observed p50/p99, and the zero-recompile contract
       (serve.traces delta 0 after warmup) on BOTH.
    3. CAPACITY — one budgeted registry device, models admitted until
       AdmissionDenied for f32 vs int8: the packing multiplier the
       ~4x smaller int8 footprints buy (this is ledger math — judged
       on every host).
    4. AMP — ResilientTrainer guarded steps (the NaN-guard IS the
       overflow backstop) f32 vs amp='bfloat16': median step wall,
       loss trajectories bit-finite, guard trips on the clean run.

    Host honesty: backend_dtype_gemm_ratio probes whether THIS backend
    has native int8/bf16 matmul.  Where it does not (XLA-CPU emulates
    both), the throughput/step-time speedups are recorded but marked
    unjudgeable (quant_host_note) — the accuracy, packing and
    zero-recompile contracts gate quant_ok regardless."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.contrib import amp as amp_mod
    from incubator_mxnet_tpu.serving import (
        ModelRegistry, AdmissionDenied, project_footprint,
        quantize_for_serving)

    ctx = mx.gpu()
    out = {"quant_model": "mlp_3072_256_10_on_quality_data",
           "quant_acc_delta_bound": QUANT_ACC_DELTA_BOUND}

    # backend probes first: they decide which contracts are judgeable
    int8_ratio = backend_dtype_gemm_ratio("int8")
    bf16_ratio = backend_dtype_gemm_ratio("bfloat16")
    out["quant_backend_int8_gemm_ratio"] = round(int8_ratio, 3)
    out["quant_backend_bf16_gemm_ratio"] = round(bf16_ratio, 3)

    # ---- 1. accuracy on the quality-config dataset
    x_np, y_np = _quality_dataset(train_n + eval_n)
    xt, yt = x_np[:train_n], y_np[:train_n]
    xe, ye = x_np[train_n:], y_np[train_n:]
    net = _quant_mlp()
    net.hybridize(static_alloc=True, static_shape=True)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    for _ep in range(epochs):
        for i in range(0, train_n, batch):
            xb = nd.array(xt[i:i + batch], ctx=ctx)
            yb = nd.array(yt[i:i + batch], ctx=ctx)
            with ag.record():
                l = loss_fn(net(xb), yb)
                l.backward()
            trainer.step(batch)

    def eval_acc(model):
        correct = 0
        for i in range(0, eval_n, batch):
            o = model(nd.array(xe[i:i + batch], ctx=ctx))
            correct += int((o.asnumpy().argmax(axis=1)
                            == ye[i:i + batch]).sum())
        return correct / float(eval_n)

    acc_f32 = eval_acc(net)
    # parameter-identical copy → PTQ pipeline (calibrate → rewrite)
    import tempfile
    qnet = _quant_mlp()
    with tempfile.NamedTemporaryFile(suffix=".params") as tf:
        net.save_parameters(tf.name)
        qnet.load_parameters(tf.name, ctx=ctx)
    calib = [nd.array(xt[i:i + batch], ctx=ctx)
             for i in range(0, 4 * batch, batch)]
    _, qreport = quantize_for_serving(qnet, calib)
    acc_int8 = eval_acc(qnet)
    out.update({
        "quant_acc_f32": round(acc_f32, 4),
        "quant_acc_int8": round(acc_int8, 4),
        "quant_acc_delta": round(acc_f32 - acc_int8, 4),
        "quant_calib_mode": qreport["calib_mode"],
        "quant_quantized_layers": qreport["quantized_layers"],
        "quant_weight_bytes_f32":
            qreport["weight_bytes_total_before"],
        "quant_weight_bytes_int8":
            qreport["weight_bytes_total_after"],
    })

    # ---- 2. serving throughput/p99 + zero-recompile, f32 vs int8
    imgs = xe[:serve_n].astype(np.float32)
    f32_serve = _measure_engine_serve(net, imgs, serve_n, 0, ctx)
    int8_serve = _measure_engine_serve(qnet, imgs, serve_n, 0, ctx)
    for k, v in f32_serve.items():
        out["quant_f32_serve_" + k] = v
    for k, v in int8_serve.items():
        out["quant_int8_serve_" + k] = v
    out["quant_int8_speedup"] = round(
        int8_serve["images_per_sec"]
        / max(f32_serve["images_per_sec"], 1e-9), 3)
    out["quant_traces_after_warmup_delta"] = \
        int8_serve["traces_after_warmup_delta"]

    # ---- 3. capacity: models admitted per budgeted device
    fp_f32, _d = project_footprint(net, (1, 2, 4, 8, 16), (3, 32, 32),
                                   "float32")
    fp_int8, _d8 = project_footprint(qnet, (1, 2, 4, 8, 16),
                                     (3, 32, 32), "float32")
    budget = int(2.2 * fp_f32)

    def admitted(block):
        reg = ModelRegistry(devices=[ctx], hbm_budget=budget)
        n_adm = 0
        try:
            while n_adm < 32:
                reg.register("m%d" % n_adm, block,
                             example_shape=(3, 32, 32),
                             wire_dtype="float32", max_batch=16)
                n_adm += 1
        except AdmissionDenied:
            pass
        finally:
            reg.close()
        return n_adm

    n_f32 = admitted(net)
    n_int8 = admitted(qnet)
    out.update({
        "quant_footprint_f32_bytes": int(fp_f32),
        "quant_footprint_int8_bytes": int(fp_int8),
        "quant_hbm_budget_bytes": budget,
        "quant_models_admitted_f32": n_f32,
        "quant_models_admitted_int8": n_int8,
        "quant_packing_multiplier": round(n_int8 / max(n_f32, 1), 2),
    })

    # ---- 4. AMP bf16 guarded steps vs f32
    from incubator_mxnet_tpu.parallel.trainer import ShardedTrainer
    from incubator_mxnet_tpu.parallel.resilience import ResilientTrainer

    def amp_run(amp_dtype):
        # amp=False (not None) on BOTH layers of the baseline: None
        # means "fall back to MXNET_AMP_DTYPE", and an exported env
        # default would silently turn the f32 arm into a bf16-vs-bf16
        # comparison; the ResilientTrainer owns the policy for the
        # AMP arm
        t = ShardedTrainer(
            _quant_mlp(seed=4321, in_units=512, hidden=512),
            optimizer="sgd", lr=0.05, amp=False)
        res = ResilientTrainer(t, ckpt_dir=None,
                               amp=amp_dtype or False,
                               handle_sigterm=False)
        rs = np.random.RandomState(3)
        xa = rs.randn(batch, 512).astype(np.float32)
        ya = rs.randint(0, 10, batch).astype(np.int32)
        walls, losses, trips = [], [], 0
        for _ in range(amp_steps):
            t0 = time.perf_counter()
            loss, ok = res.step(xa, ya)
            walls.append(time.perf_counter() - t0)
            losses.append(loss)
            trips += 0 if ok else 1
        amp_mod.turn_off()
        walls = sorted(walls[2:])          # drop compile steps
        return walls[len(walls) // 2], losses, trips

    w_f32, l_f32, trips_f32 = amp_run(False)
    w_amp, l_amp, trips_amp = amp_run("bfloat16")
    amp_finite = bool(np.all(np.isfinite(l_amp))
                      and np.all(np.isfinite(l_f32)))
    out.update({
        "quant_amp_step_ms": round(w_amp * 1e3, 3),
        "quant_amp_f32_step_ms": round(w_f32 * 1e3, 3),
        "quant_amp_speedup": round(w_f32 / max(w_amp, 1e-9), 3),
        "quant_amp_losses_finite": amp_finite,
        "quant_amp_nan_guard_trips": int(trips_amp),
        "quant_amp_final_loss": round(float(l_amp[-1]), 4),
        "quant_amp_f32_final_loss": round(float(l_f32[-1]), 4),
    })

    # ---- verdict: host-independent contracts always gate; the
    # throughput contracts join only where the backend has the fast
    # path (the probe), mirroring check_feed's "ceiling too low =
    # neither pass nor fail" convention
    ok = (out["quant_traces_after_warmup_delta"] == 0
          and f32_serve["traces_after_warmup_delta"] == 0
          and out["quant_acc_delta"] <= QUANT_ACC_DELTA_BOUND
          and out["quant_packing_multiplier"] >= 2.0
          and amp_finite and trips_amp == 0)
    judged_speed = int8_ratio >= 1.0
    if judged_speed:
        ok = ok and out["quant_int8_speedup"] >= 2.0
    else:
        out["quant_host_note"] = (
            "backend emulates int8/bf16 GEMM (int8 ratio %.2f, bf16 "
            "%.2f): throughput/step-time speedups are recorded but "
            "not judged on this host; accuracy, packing and "
            "zero-recompile contracts gate quant_ok"
            % (int8_ratio, bf16_ratio))
    # the bf16 step-time contract joins only on a CLEARLY native bf16
    # backend (probe >= 1.1, not 1.0: XLA-CPU bf16 matmul lands near
    # f32 speed, and a 1.02-by-noise probe must not arm a >1.0 gate
    # that the 10-step median then fails by the same noise)
    if bf16_ratio >= 1.1:
        ok = ok and out["quant_amp_speedup"] > 1.0
    out["quant_int8_speedup_judged"] = bool(judged_speed)
    out["quant_ok"] = bool(ok)
    if extra is not None:
        extra.update(out)
    return out


def run_io(batch=128):
    """Input-pipeline-only throughput on the multi-process decode
    service (io/decode_service.py): sharded RecordIO readers → worker-
    process decode → shared-memory slab ring, uint8 slabs (the e2e
    wire format) — SURVEY §2.4 "must sustain v5e input rates".

    Sweeps worker counts (1 → min(4, cores)) and reports the decode
    parallelism ACTUALLY in effect as `io_host_cores` — the old code
    emitted os.cpu_count() regardless of what the pipeline used, which
    made r3-vs-r4 rounds incomparable (r3's 864.7 really ran multiple
    decode threads; r4's 399.9 ran one).  Hosts without shared memory
    fall back to the native C++ reader (`io_backend` says which)."""
    from incubator_mxnet_tpu import config as _cfg
    from incubator_mxnet_tpu.io.decode_service import (
        DecodeService, DecodeServiceUnavailable)
    path = _ensure_rec()
    cpu = os.cpu_count() or 1
    # the knob is authoritative when SET: 0 disables the service
    # (native fallback below), N joins the sweep so the configured
    # count is actually measured
    cfg_w = (int(_cfg.get("MXNET_IO_WORKERS"))
             if "MXNET_IO_WORKERS" in os.environ else None)
    try:
        if cfg_w is not None and cfg_w < 1:
            raise DecodeServiceUnavailable(
                "MXNET_IO_WORKERS=0: decode service disabled")
        counts = {1, min(2, cpu), min(4, cpu)}
        if cfg_w:
            counts.add(cfg_w)
        sweep = {}
        best_w, best_rates = 0, [0.0]
        for w in sorted(counts):
            svc = DecodeService(
                path, batch, (3, 224, 224), workers=w, resize=256,
                rand_crop=True, rand_mirror=True, shuffle=True,
                dtype="uint8")
            try:
                for _ in svc:       # warm epoch (page cache, workers)
                    pass
                # median of 3 one-epoch windows (the resnet headline's
                # variance discipline)
                rates = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    n = 0
                    for sb in svc:
                        n += sb.count
                    rates.append(n / (time.perf_counter() - t0))
                rates.sort()
                sweep[str(w)] = round(rates[1], 1)
                if rates[1] > best_rates[len(best_rates) // 2]:
                    best_w, best_rates = w, rates
            finally:
                svc.close()
        rate = best_rates[len(best_rates) // 2]
        out = {"io_pipeline_images_per_sec": round(rate, 1),
               "io_spread_pct": round(
                   100.0 * (best_rates[-1] - best_rates[0]) / rate, 2),
               # the decode worker count the headline number actually
               # used — NOT os.cpu_count()
               "io_host_cores": best_w,
               "io_worker_sweep": sweep,
               "io_backend": "decode_service"}
        if len(sweep) > 1:
            lo, hi = min(sweep, key=int), max(sweep, key=int)
            out["io_worker_scaling"] = round(
                sweep[hi] / max(sweep[lo], 1e-9), 2)
        return out
    except DecodeServiceUnavailable:
        pass
    # sandboxed host: native C++ threaded reader
    from incubator_mxnet_tpu.io import native
    if not native.available():
        raise RuntimeError("decode service and native io both "
                           "unavailable")
    nthreads = min(cpu, 16)
    r = native.NativeImageRecordReader(
        path, batch_size=batch, data_shape=(3, 224, 224), resize=256,
        rand_crop=True, rand_mirror=True, shuffle=True,
        num_threads=nthreads)
    for _ in r:     # warm epoch
        pass
    r.reset()
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        n = 0
        for data, _label in r:
            n += data.shape[0]
        r.reset()
        rates.append(n / (time.perf_counter() - t0))
    rates.sort()
    return {"io_pipeline_images_per_sec": round(rates[1], 1),
            "io_spread_pct": round(
                100.0 * (rates[-1] - rates[0]) / rates[1], 2),
            "io_host_cores": nthreads,      # decode threads in effect
            "io_backend": "native"}


def _free_device_memory():
    """Drop dead device buffers between retries inside one process:
    each config's net/trainer/pendings form reference cycles
    (Block↔Parameter↔pending) that only gc.collect() breaks."""
    import gc
    gc.collect()
    try:
        import jax
        jax.clear_caches()
    except Exception:
        pass


def _try_batches(fn, batches, **kw):
    err = None
    for b in batches:
        try:
            return fn(batch=b, **kw), b
        except Exception as e:      # OOM etc. — halve and retry
            err = e
            _free_device_memory()
    raise err


# ---------------------------------------------------------------------------
# driver: one SUBPROCESS per config.
#
# Observed on an earlier setup (not re-checked on this chip): a failed
# (OOM) allocation left the allocator unusable for the REST of the
# process.  Process exit recovers it, and a chip belongs to one process
# at a time anyway, so each config runs in its own python subprocess
# and reports one JSON dict on its last stdout line.  The parent must
# stay off JAX: a parent that has touched the backend holds the chip.
# ---------------------------------------------------------------------------

_CONFIGS = {
    "resnet": lambda b=None: _cfg_resnet(),
    # bert's batch fallback is driven by main() ACROSS subprocesses:
    # an OOM may wedge the allocator for the whole process (see the
    # driver comment above), so in-process retry at a smaller batch
    # cannot work — each batch attempt must be its own process
    "bert": lambda b=None: _cfg_simple(
        "bert_base_tokens_per_sec_per_chip", run_bert,
        (int(b),) if b else (16,),
        const={"bert_seq": 512}, batch_key="bert_batch"),
    "ssd512": lambda b=None: _cfg_simple(
        "ssd512_train_images_per_sec", run_ssd,
        (int(b),) if b else (8,), pass_extra=True),
    "rcnn": lambda b=None: _cfg_simple(
        "rcnn_train_images_per_sec", run_rcnn,
        (int(b),) if b else (2,), pass_extra=True),
    "gnmt": lambda b=None: _cfg_simple(
        "gnmt_train_tokens_per_sec", run_gnmt,
        (int(b),) if b else (128,), pass_extra=True),
    "transformer_nmt": lambda b=None: _cfg_simple(
        "transformer_nmt_train_tokens_per_sec", run_transformer_nmt,
        (int(b),) if b else (64,)),
    "wide_deep": lambda b=None: _cfg_wide_deep(b),
    "io": lambda b=None: _cfg_io(),
    "sharded": lambda b=None: _cfg_simple(
        "sharded_trainer_value", run_sharded, (256, 128, 64),
        batch_key="sharded_trainer_batch"),
    "int8": lambda b=None: _cfg_simple(
        "resnet50_int8_infer_images_per_sec", run_int8_infer, (64, 32)),
    "quant": lambda b=None: _cfg_quant(),
    "quality": lambda b=None: run_quality(),
    "serve": lambda b=None: _cfg_serve(),
    "generate": lambda b=None: _cfg_generate(),
    "elastic": lambda b=None: _cfg_elastic(),
    "integrity": lambda b=None: _cfg_integrity(),
    "controlplane": lambda b=None: _cfg_controlplane(),
    "multichip": lambda b=None: _cfg_multichip(),
}

# batch ladders main() walks one-subprocess-per-attempt (first success
# wins); configs not listed use their in-process ladders above
_SUBPROC_BATCHES = {"bert": (32, 16, 8),
                    # r5 seq 64: b256 wedges in compile (observed
                    # >560s); b128 = 134k tok/s
                    "transformer_nmt": (128, 64),
                    # r5: reference-geometry gnmt_large (179M params,
                    # seq 50) — tokens/s scales with batch (87k/104k/
                    # 118k at 128/256/512); b1024 OOMs
                    "gnmt": (512, 256, 128),
                    # fused-path throughput scales with batch (plateau
                    # ~1.8M samples/s near b128k, r4); b32768 is the
                    # largest defensible large-batch-recsys config
                    "wide_deep": (32768, 8192, 2048),
                    # r5: VGG16-reduced SSD — conv-bound, batch ladder
                    # down from 16
                    "ssd512": (16, 8, 4),
                    # per-image roi density held constant, so larger
                    # batches are honest throughput (b8 ~3x b2, r4);
                    # r5 resnet50@600x800 is ~10x the r4 stand-in's
                    # FLOPs, so the ladder starts at 4
                    "rcnn": (4, 2, 1)}


def _cfg_resnet():
    extra = {}
    imgs, batch = _try_batches(run_cachedop, (128, 64, 32), extra=extra)
    extra.update({"value": round(imgs, 2), "batch": batch})
    # feed./train. counter+tail snapshot of this config's process
    # (ISSUE 4): the e2e feed counters above are deltas, this is the
    # whole-ledger block teletop --file renders
    try:
        from incubator_mxnet_tpu import telemetry
        extra["telemetry"] = telemetry.snapshot_dict()
    except Exception:
        pass
    return extra


def _cfg_wide_deep(b=None):
    # batch comes from main()'s subprocess ladder (an in-process OOM
    # retry cannot work on this backend — see the driver comment)
    b = int(b) if b else 2048
    val = run_wide_deep(batch=b)
    out = {"wide_deep_train_samples_per_sec": round(val, 2),
           "wide_deep_train_samples_per_sec_batch": b}
    # secondary: the row_sparse lazy-update path (the r3 headline
    # semantics) at the
    # r3-comparable b2048, now jitted via BucketedSparseTrainer (r5)
    try:
        _free_device_memory()
        out["wide_deep_sparse_path_samples_per_sec"] = round(
            run_wide_deep(batch=2048, iters=40, sparse=True), 2)
    except Exception as e:
        out["wide_deep_sparse_path_error"] = str(e)[:120]
    return out


def _cfg_simple(key, fn, batches, const=None, batch_key=None,
                pass_extra=False):
    extra = {}
    kw = {"extra": extra} if pass_extra else {}
    val, b = _try_batches(fn, batches, **kw)
    out = {key: round(val, 2),
           (batch_key or key + "_batch"): b}
    out.update(extra)
    out.update(const or {})
    return out


def _cfg_io():
    # run_io reports io_host_cores as the decode worker count actually
    # in effect (not os.cpu_count() — ISSUE 6 satellite)
    return run_io()


def _cfg_serve():
    parsed = run_serve()
    try:
        # overload scenario (ISSUE 8) rides in the same record: lanes,
        # shedding and tail percentiles under 2x Poisson load
        parsed.update(run_serve_overload())
    except Exception as e:
        parsed["serve_overload_error"] = str(e)[:160]
    try:
        _write_bench_serve(parsed)      # trajectory file rides along
    except Exception:
        pass
    return parsed


def _cfg_generate():
    parsed = run_generate()
    try:
        _merge_bench_serve(parsed)      # generate_* keys ride in the
    except Exception:                   # serve trajectory file
        pass
    return parsed


def _cfg_quant():
    parsed = run_quant()
    try:
        _merge_bench_serve(parsed)      # quant_* keys ride in the
    except Exception:                   # serve trajectory file
        pass
    return parsed


def _cfg_elastic():
    parsed = run_elastic()
    try:
        _write_multichip_elastic(parsed)    # trajectory file rides along
    except Exception:
        pass
    return parsed


def _cfg_multichip():
    parsed = run_multichip()
    try:
        _write_multichip_scaling(parsed)    # trajectory file rides along
    except Exception:
        pass
    return parsed


def _run_config_subprocess(name, timeout_s, batch=None):
    import subprocess
    cmd = [sys.executable, os.path.abspath(__file__), "--config", name]
    if batch is not None:
        cmd.append(str(batch))
    try:
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout_s,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {name + "_error": "config timed out (%ds)" % timeout_s}
    for line in reversed(res.stdout.strip().splitlines() or [""]):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except Exception:
                break
    tail = (res.stderr or res.stdout or "").strip().splitlines()
    return {name + "_error": (tail[-1] if tail else
                              "rc=%d, no output" % res.returncode)[:160]}


def main():
    # hard wall-clock budget: the driver must always get the ONE JSON
    # line; the five BASELINE configs run first (each in its own
    # process, see above), extras are skipped once the budget is spent
    # (override with MXNET_BENCH_BUDGET_S)
    t_start = time.perf_counter()
    budget = float(os.environ.get("MXNET_BENCH_BUDGET_S", 720))
    _ensure_rec()       # build the shared corpus once, outside timings

    extra = {}
    times = {}
    required = ("resnet", "bert", "ssd512", "rcnn", "gnmt",
                "transformer_nmt", "wide_deep")
    optional = ("io", "serve", "generate", "sharded", "elastic",
                "multichip", "quality", "quant", "int8")

    # optional configs need this much budget left to be worth starting
    # (below it they'd time out AT the budget edge instead of skipping
    # cleanly — int8's quantization calibration alone needs ~4 min cold)
    optional_min = {"io": 30, "serve": 90, "generate": 60,
                    "sharded": 90, "elastic": 60, "multichip": 90,
                    "quality": 120, "quant": 150, "int8": 250}

    for name in required + optional:
        remaining = budget - (time.perf_counter() - t_start)
        if name not in required and remaining < optional_min[name]:
            # typed skip record (ISSUE 15 satellite): a machine-readable
            # reason in the standard schema, with the standalone escape
            # hatch named — any config runs budget-free via
            # `python bench.py <cfg>`.  String-valued on purpose:
            # bench_diff flattens numeric leaves and its 'skipped'
            # fragment judges them lower-better, so a numeric
            # remaining_s here would read budget-timing noise between
            # rounds as a regression
            extra[name + "_skipped"] = {
                "reason": "budget",
                "detail": "needed %ds, %.0fs remaining of %ds budget"
                          % (optional_min[name], remaining, budget),
                "standalone": "python bench.py %s" % name,
            }
            continue
        # required configs get a fair floor even if earlier ones ran
        # long; optionals never exceed the remaining budget; the
        # subprocess hard-timeout keeps the total bounded
        cap = max(remaining, 150) if name in required             else max(remaining - 5, 30)
        t0 = time.perf_counter()
        if name in _SUBPROC_BATCHES:
            # one subprocess per batch attempt (OOM wedges a process);
            # the cap is re-derived per attempt so a hung first rung
            # cannot multiply into N x cap of wall clock
            for i, b in enumerate(_SUBPROC_BATCHES[name]):
                if i > 0:
                    remaining = budget - (time.perf_counter() - t_start)
                    cap = max(remaining, 60)
                res = _run_config_subprocess(name, cap, batch=b)
                # retry on the config's OWN failure key only — a
                # secondary-metric error (e.g. wide_deep_sparse_path_
                # error) must not discard a successful headline
                if (name + "_error") not in res:
                    break
            extra.update(res)
        else:
            extra.update(_run_config_subprocess(name, cap))
        times[name] = round(time.perf_counter() - t0, 1)

    headline = extra.pop("value", 0.0)
    batch = extra.pop("batch", 0)
    extra["config_wall_s"] = times
    extra["bench_wall_s"] = round(time.perf_counter() - t_start, 1)
    # round-over-round guard (VERDICT r4 next #3): surface the previous
    # driver-recorded headline + delta so a regression is visible next
    # to the in-run spread field
    try:
        import re
        here = os.path.dirname(os.path.abspath(__file__))
        # numeric round sort (lexicographic breaks at r10 if a future
        # driver drops the zero padding)
        prev_files = sorted(
            (f for f in os.listdir(here)
             if re.fullmatch(r"BENCH_r(\d+)\.json", f)),
            key=lambda f: int(re.fullmatch(r"BENCH_r(\d+)\.json",
                                           f).group(1)))
        if prev_files and headline:
            with open(os.path.join(here, prev_files[-1])) as fh:
                prev = json.load(fh).get("parsed", {})
            pv = prev.get("value")
            if pv:
                extra["prior_round"] = {
                    "file": prev_files[-1], "value": pv,
                    "delta_pct": round(100.0 * (headline - pv) / pv, 2)}
    except Exception:
        pass
    print(json.dumps({
        "metric": "resnet50_v1b_train_images_per_sec_per_chip",
        "value": headline,
        "unit": "images/sec",
        "vs_baseline": round(headline / V100_IMAGES_PER_SEC, 4),
        "batch": batch,
        "path": "gluon hybridize->CachedOp->Trainer (north-star config 1)",
        **extra,
    }))
    return 0 if headline else 1     # headline failure -> non-zero exit


if __name__ == "__main__":
    from incubator_mxnet_tpu import compile_cache as _compile_cache
    _compile_cache.enable()
    # every dump path below (crashing configs, scenario children,
    # fault-injection runs) writes real black-box/quarantine files —
    # they belong in a scratch dir, never the repo checkout bench runs
    # from (ISSUE 9 satellite: the stray blackbox-*-verify.json)
    if "MXNET_BLACKBOX_DIR" not in os.environ:
        import tempfile as _tempfile
        os.environ["MXNET_BLACKBOX_DIR"] = _tempfile.gettempdir()
    if len(sys.argv) >= 2 and sys.argv[1] == "integrity":
        # standalone integrity chaos scenario (ISSUE 9): ONE JSON line
        # + BENCH_integrity.json; rc 1 when a corruption went
        # undetected/unrecovered
        try:
            parsed = run_integrity()
            rc = 0 if (parsed.get("integrity_clean_stream_bit_identical")
                       and parsed.get("integrity_ckpt_salvaged", 0)
                       and parsed.get("integrity_sdc_evicted", 0)
                       and parsed.get("integrity_losses_finite")) else 1
        except Exception as e:
            parsed, rc = {"integrity_error": str(e)[:160]}, 1
        try:
            _write_bench_integrity(parsed, rc=rc)
        except Exception:
            pass
        print(json.dumps(parsed))
        sys.exit(rc)
    if len(sys.argv) >= 2 and sys.argv[1] == "--integrity-child":
        _n, _s, _spe = (int(a) for a in sys.argv[2:5])
        _integrity_scenario(_n, _s, _spe)
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "controlplane":
        # standalone control-plane chaos scenario (ISSUE 16): ONE
        # JSON line + BENCH_controlplane.json; rc 1 only when the
        # scenario RAN (overloaded) and the fleet failed to recover
        # an injected incident on its own
        try:
            parsed = run_controlplane()
            rc = 0 if parsed.get("controlplane_ok") is not False \
                else 1
        except Exception as e:
            parsed, rc = {"controlplane_error": str(e)[:160]}, 1
        try:
            _write_bench_controlplane(parsed, rc=rc)
        except Exception:
            pass
        print(json.dumps(parsed))
        sys.exit(rc)
    if len(sys.argv) >= 2 and sys.argv[1] == "--controlplane-child":
        _n = int(sys.argv[2])
        _d, _c = float(sys.argv[3]), float(sys.argv[4])
        _controlplane_scenario(_n, _d, _c, int(sys.argv[5]))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "serve_overload":
        # standalone overload scenario (ISSUE 8): ONE JSON line; rc 1
        # only when the scenario RAN overloaded and the contract broke
        # (hi-lane p99 past deadline, or nothing shed)
        try:
            parsed = run_serve_overload()
            rc = 0 if parsed.get("serve_overload_ok") is not False \
                else 1
        except Exception as e:
            parsed, rc = {"serve_overload_error": str(e)[:160]}, 1
        print(json.dumps(parsed))
        sys.exit(rc)
    if len(sys.argv) >= 2 and sys.argv[1] == "generate":
        # standalone generation bench (ISSUE 14): ONE JSON line;
        # generate_* keys merged into BENCH_serve.json.  rc 1 only
        # when the scenario RAN overloaded and the contract broke
        # (drain beat continuous on TTFT p99, a recompile leaked into
        # steady state, or hi TTFT p99 blew its deadline)
        try:
            parsed = run_generate()
            rc = 0 if parsed.get("generate_ok") is not False else 1
        except Exception as e:
            parsed, rc = {"generate_error": str(e)[:160]}, 1
            try:
                from incubator_mxnet_tpu import telemetry
                parsed["generate_blackbox"] = telemetry.dump_blackbox(
                    reason="bench.generate", exc=e)
            except Exception:
                pass
        try:
            _merge_bench_serve(parsed, rc=rc)
        except Exception:
            pass
        print(json.dumps(parsed))
        sys.exit(rc)
    if len(sys.argv) >= 2 and sys.argv[1] == "serve":
        # standalone serving bench: ONE JSON line + BENCH_serve.json
        # (same {n, cmd, rc, tail, parsed} schema as BENCH_r*)
        try:
            parsed = run_serve()
            try:
                parsed.update(run_serve_overload())
            except Exception as e:
                parsed["serve_overload_error"] = str(e)[:160]
            rc = 0 if parsed.get("serve_speedup_vs_batch1", 0) and \
                parsed.get("serve_traces_after_warmup_delta", 1) == 0 \
                and parsed.get("serve_overload_ok") is not False \
                else 1
        except Exception as e:
            parsed, rc = {"serve_error": str(e)[:160],
                          "serve_failed": str(e)[:160]}, 1
            try:
                from incubator_mxnet_tpu import telemetry
                parsed["serve_blackbox"] = telemetry.dump_blackbox(
                    reason="bench.serve", exc=e)
            except Exception:
                pass
        print(_write_bench_serve(parsed, rc=rc))
        sys.exit(rc)
    if len(sys.argv) >= 2 and sys.argv[1] == "--elastic-child":
        # marked child of run_elastic: the n-device virtual CPU
        # platform is already forced in XLA_FLAGS by the parent
        _n, _k, _s, _spe = (int(a) for a in sys.argv[2:6])
        _elastic_scenario(_n, _k, _s, _spe)
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--multichip-child":
        # marked child of run_multichip (same virtual-platform recipe)
        _multichip_scenario(int(sys.argv[2]))
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "quant":
        # standalone quant bench (ISSUE 15): ONE JSON line; quant_*
        # keys merged into BENCH_serve.json.  rc 1 only when a
        # host-independent contract broke (steady-state recompile,
        # accuracy delta past the documented bound, packing < 2x, a
        # NaN-guard trip on the clean AMP run) or — on hosts whose
        # backend has native int8 — the 2x throughput contract
        try:
            parsed = run_quant()
            rc = 0 if parsed.get("quant_ok") is not False else 1
            try:
                # same cost-table totals every other standalone config
                # line carries (schema parity with `--config quant`)
                from incubator_mxnet_tpu.telemetry import costs as _costs
                t = _costs.totals()
                if t.get("executables"):
                    parsed["quant_costs"] = t
            except Exception:
                pass
        except Exception as e:
            parsed, rc = {"quant_error": str(e)[:160]}, 1
            try:
                from incubator_mxnet_tpu import telemetry
                parsed["quant_blackbox"] = telemetry.dump_blackbox(
                    reason="bench.quant", exc=e)
            except Exception:
                pass
        try:
            _merge_bench_serve(parsed, rc=rc)
        except Exception:
            pass
        print(json.dumps(parsed))
        sys.exit(rc)

    def _run_one_config(name, batch, rc_on_fail):
        """ONE config → one JSON line.  Shared by the driver's
        `--config` subprocess protocol (rc 0 even on failure — the
        driver reads <cfg>_error and walks its batch ladder) and the
        bare `bench.py <cfg>` standalone entry (rc 1 on failure —
        ISSUE 15 satellite: any config runs budget-free)."""
        try:
            out = _CONFIGS[name](batch)
            try:
                # cost-table totals (flops / bytes / hbm peak) ride in
                # every config's JSON line (ISSUE 5)
                from incubator_mxnet_tpu.telemetry import costs as _costs
                t = _costs.totals()
                if t.get("executables"):
                    out[name + "_costs"] = t
            except Exception:
                pass
            print(json.dumps(out))
            return 0
        except Exception as e:
            # a crashing config leaves its black box (ring + counters +
            # cost table) and reports <cfg>_failed instead of killing
            # the whole round (ISSUE 5); _error kept for the driver's
            # batch-retry ladder
            fail = {name + "_failed": str(e)[:160],
                    name + "_error": str(e)[:160]}
            try:
                from incubator_mxnet_tpu import telemetry
                fail[name + "_blackbox"] = telemetry.dump_blackbox(
                    reason="bench." + name, exc=e)
            except Exception:
                pass
            print(json.dumps(fail))
            return rc_on_fail

    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        sys.exit(_run_one_config(
            sys.argv[2], sys.argv[3] if len(sys.argv) >= 4 else None,
            rc_on_fail=0))
    if len(sys.argv) >= 2 and sys.argv[1] in _CONFIGS:
        # bare `bench.py <cfg>` (ISSUE 15 satellite): any config —
        # including ones the last full round skipped for budget — runs
        # standalone with no budget gate; rc reflects THIS config
        sys.exit(_run_one_config(
            sys.argv[1], sys.argv[2] if len(sys.argv) >= 3 else None,
            rc_on_fail=1))
    sys.exit(main())
