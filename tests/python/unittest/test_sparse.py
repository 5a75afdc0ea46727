"""Sparse NDArray + ops (ref: tests/python/unittest/test_sparse_ndarray.py,
test_sparse_operator.py)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.ndarray import sparse
from incubator_mxnet_tpu.test_utils import assert_almost_equal


def _rand_sparse(shape, density=0.3):
    a = np.random.randn(*shape).astype("float32")
    mask = np.random.rand(*shape) < density
    return a * mask


def test_csr_roundtrip():
    a = _rand_sparse((6, 8))
    csr = sparse.csr_matrix(a)
    assert csr.stype == "csr"
    assert csr.shape == (6, 8)
    assert_almost_equal(csr.asnumpy(), a)
    dense = csr.tostype("default")
    assert_almost_equal(dense, a)


def test_row_sparse_roundtrip():
    a = np.zeros((8, 4), "float32")
    a[1] = 1.0
    a[5] = 2.0
    rsp = sparse.row_sparse_array(a)
    assert rsp.stype == "row_sparse"
    assert list(rsp.indices.asnumpy()) == [1, 5]
    assert_almost_equal(rsp.asnumpy(), a)


def test_cast_storage():
    a = _rand_sparse((5, 5))
    dense = nd.array(a)
    csr = sparse.cast_storage(dense, "csr")
    back = sparse.cast_storage(csr, "default")
    assert_almost_equal(back, a)
    rsp = sparse.cast_storage(dense, "row_sparse")
    assert_almost_equal(rsp.asnumpy(), a)


def test_csr_dot():
    a = _rand_sparse((6, 10))
    w = np.random.randn(10, 3).astype("float32")
    csr = sparse.csr_matrix(a)
    out = sparse.dot(csr, nd.array(w))
    assert_almost_equal(out, a @ w, rtol=1e-4, atol=1e-4)


def test_csr_dot_transpose():
    a = _rand_sparse((6, 10))
    x = np.random.randn(6, 3).astype("float32")
    csr = sparse.csr_matrix(a)
    out = sparse.dot(csr, nd.array(x), transpose_a=True)
    assert_almost_equal(out, a.T @ x, rtol=1e-4, atol=1e-4)


def test_embedding_grad_row_sparse():
    idx = nd.array([2, 7, 2, 0], dtype="int32")
    og = nd.array(np.ones((4, 3), "float32"))
    g = sparse.embedding_grad(idx, og, vocab_size=10)
    assert list(g.indices.asnumpy()) == [0, 2, 7]
    vals = g.data.asnumpy()
    assert vals[1, 0] == 2.0       # row 2 hit twice


def test_sparse_sgd_lazy():
    w = nd.array(np.ones((6, 2), "float32"))
    g = sparse.RowSparseNDArray(np.array([1, 4]),
                                np.ones((2, 2), "float32"), (6, 2))
    sparse.sparse_sgd_update(w, g, lr=0.5)
    out = w.asnumpy()
    assert out[1, 0] == 0.5
    assert out[4, 0] == 0.5
    assert out[0, 0] == 1.0        # untouched


def test_sparse_adagrad_and_adam():
    w = nd.array(np.ones((6, 2), "float32"))
    h = nd.array(np.zeros((6, 2), "float32"))
    g = sparse.RowSparseNDArray(np.array([2]),
                                np.full((1, 2), 2.0, "float32"), (6, 2))
    sparse.sparse_adagrad_update(w, g, h, lr=1.0)
    assert h.asnumpy()[2, 0] == 4.0
    assert w.asnumpy()[2, 0] != 1.0
    assert w.asnumpy()[0, 0] == 1.0

    w2 = nd.array(np.ones((6, 2), "float32"))
    m = nd.array(np.zeros((6, 2), "float32"))
    v = nd.array(np.zeros((6, 2), "float32"))
    sparse.sparse_adam_update(w2, g, m, v, lr=0.1)
    assert m.asnumpy()[2, 0] != 0
    assert w2.asnumpy()[0, 0] == 1.0


def test_optimizer_dispatches_sparse():
    from incubator_mxnet_tpu import optimizer as opt
    w = nd.array(np.ones((6, 2), "float32"))
    g = sparse.RowSparseNDArray(np.array([3]),
                                np.ones((1, 2), "float32"), (6, 2))
    o = opt.SGD(learning_rate=1.0)
    o.update(0, w, g, o.create_state(0, w))
    assert w.asnumpy()[3, 0] == 0.0
    assert w.asnumpy()[0, 0] == 1.0
    o2 = opt.Adam()
    w2 = nd.array(np.ones((6, 2), "float32"))
    o2.update(0, w2, g, o2.create_state(0, w2))
    assert w2.asnumpy()[3, 0] != 1.0


def test_retain():
    rsp = sparse.RowSparseNDArray(np.array([1, 3, 5]),
                                  np.arange(6, dtype="float32")
                                  .reshape(3, 2), (8, 2))
    out = sparse.retain(rsp, np.array([3, 5, 7]))
    assert list(out.indices.asnumpy()) == [3, 5]


def test_rsp_add():
    a = sparse.RowSparseNDArray(np.array([0, 2]),
                                np.ones((2, 3), "float32"), (4, 3))
    b = sparse.RowSparseNDArray(np.array([2, 3]),
                                np.ones((2, 3), "float32") * 2, (4, 3))
    out = sparse.add(a, b)
    d = out.asnumpy()
    assert d[0, 0] == 1 and d[2, 0] == 3 and d[3, 0] == 2


def test_rand_ndarray_sparse():
    from incubator_mxnet_tpu.test_utils import rand_ndarray
    csr = rand_ndarray((10, 10), stype="csr", density=0.2)
    assert csr.stype == "csr"
    rsp = rand_ndarray((10, 4), stype="row_sparse", density=0.3)
    assert rsp.stype == "row_sparse"


# ---------------------------------------------------------------------------
# sparse autograd integration (ref: test_sparse_operator.py sparse
# Embedding grad + test_module.py sparse pull; VERDICT round-1 item 8)
# ---------------------------------------------------------------------------


def test_embedding_sparse_grad_flow():
    """Embedding(sparse_grad=True) backward yields a RowSparseNDArray on
    the weight — not a dense vocab-size scatter."""
    from incubator_mxnet_tpu import autograd as ag
    from incubator_mxnet_tpu import gluon
    vocab, dim = 50, 4
    emb = gluon.nn.Embedding(vocab, dim, sparse_grad=True)
    emb.initialize()
    idx = nd.array(np.array([[1, 3], [3, 7]], np.float32))
    with ag.record():
        out = emb(idx)
        loss = (out * out).sum()
    loss.backward()
    g = emb.weight.grad()
    assert isinstance(g, sparse.RowSparseNDArray), type(g)
    assert sorted(g.indices.asnumpy().tolist()) == [1, 3, 7]
    # values match the dense computation: dL/dW[r] = sum over uses of 2*W[r]
    w = emb.weight.data().asnumpy()
    dense_expect = np.zeros((vocab, dim), np.float32)
    for r in [1, 3, 3, 7]:
        dense_expect[r] += 2 * w[r]
    assert_almost_equal(g.asnumpy(), dense_expect)


def test_sparse_trainer_lazy_update():
    """Trainer.step with a row_sparse grad updates ONLY the touched rows
    (ref: sgd_update FComputeEx lazy_update)."""
    from incubator_mxnet_tpu import autograd as ag
    from incubator_mxnet_tpu import gluon
    vocab, dim = 30, 4
    emb = gluon.nn.Embedding(vocab, dim, sparse_grad=True)
    emb.initialize()
    w_before = emb.weight.data().asnumpy().copy()
    trainer = gluon.Trainer(emb.collect_params(), "sgd",
                            {"learning_rate": 1.0, "wd": 0.0})
    idx = nd.array(np.array([[2, 5]], np.float32))
    with ag.record():
        loss = emb(idx).sum()
        loss.backward()
    trainer.step(1)
    w_after = emb.weight.data().asnumpy()
    touched = [2, 5]
    untouched = [r for r in range(vocab) if r not in touched]
    assert np.allclose(w_after[untouched], w_before[untouched])
    assert_almost_equal(w_after[touched], w_before[touched] - 1.0)


def test_sparse_adam_trainer():
    from incubator_mxnet_tpu import autograd as ag
    from incubator_mxnet_tpu import gluon
    emb = gluon.nn.Embedding(20, 3, sparse_grad=True)
    emb.initialize()
    w_before = emb.weight.data().asnumpy().copy()
    trainer = gluon.Trainer(emb.collect_params(), "adam",
                            {"learning_rate": 0.1})
    idx = nd.array(np.array([[4]], np.float32))
    with ag.record():
        loss = (emb(idx) ** 2).sum()
        loss.backward()
    trainer.step(1)
    w_after = emb.weight.data().asnumpy()
    assert not np.allclose(w_after[4], w_before[4])
    untouched = [r for r in range(20) if r != 4]
    assert np.allclose(w_after[untouched], w_before[untouched])


def test_kvstore_sparse_push_and_row_sparse_pull():
    from incubator_mxnet_tpu import kvstore as kv
    store = kv.create("local")
    store.init("w", nd.zeros((6, 2)))
    rsp = sparse.RowSparseNDArray(
        np.array([1, 4], np.int64),
        np.array([[1.0, 2.0], [3.0, 4.0]], np.float32), (6, 2))
    store.push("w", rsp)
    out = nd.zeros((6, 2))
    store.row_sparse_pull("w", out=out,
                          row_ids=nd.array(np.array([1, 4], np.float32)))
    got = out.asnumpy()
    assert np.allclose(got[1], [1.0, 2.0])
    assert np.allclose(got[4], [3.0, 4.0])
    assert np.allclose(got[[0, 2, 3, 5]], 0)


def test_wide_deep_libsvm_convergence(tmp_path):
    """Config 5 end-to-end: LibSVMIter -> WideDeep -> sparse grads ->
    sparse optimizer; loss must halve on a learnable synthetic set."""
    from incubator_mxnet_tpu import autograd as ag
    from incubator_mxnet_tpu import gluon, io as mxio
    from incubator_mxnet_tpu.models.wide_deep import (WideDeep,
                                                      csr_to_fields)
    rs = np.random.RandomState(0)
    vocab, fields, B, N = 100, 4, 16, 64
    # synthetic: label = 1 iff any feature id < vocab//2
    lines = []
    for _ in range(N):
        ids = sorted(rs.choice(vocab, fields, replace=False))
        label = 1 if min(ids) < vocab // 2 else 0
        lines.append("%d %s" % (label,
                                " ".join("%d:%.3f" % (i, 1.0)
                                         for i in ids)))
    path = tmp_path / "train.libsvm"
    path.write_text("\n".join(lines))

    it = mxio.LibSVMIter(data_libsvm=str(path), data_shape=(vocab,),
                         batch_size=B)
    net = WideDeep(vocab, embed_dim=8, hidden=(16,), classes=2)
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.02})
    first = last = None
    for epoch in range(12):
        it.reset()
        for batch in it:
            csr = batch.data[0]
            idxs, vals = csr_to_fields(csr, fields)
            y = batch.label[0]
            with ag.record():
                logits = net(idxs, vals)
                l = loss_fn(logits, y)
                l.backward()
            trainer.step(B)
            last = float(l.asnumpy().mean())
            if first is None:
                first = last
    assert last < first * 0.5, (first, last)
    # the sparse path must actually be in use
    g = net.deep_embed.weight.grad()
    assert isinstance(g, sparse.RowSparseNDArray)


def test_bucketed_sparse_trainer_matches_eager_lazy_path():
    """r5 jitted sparse path: BucketedSparseTrainer (device-side
    unique buckets + sentinel-row lazy updates, one executable per
    bucket) must track the eager row_sparse path (Trainer + lazy
    sparse_adam_update) step for step on the same data."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, autograd as ag
    from incubator_mxnet_tpu.models.wide_deep import WideDeep
    from incubator_mxnet_tpu.contrib.sparse_jit import \
        BucketedSparseTrainer

    vocab, E, B, F = 600, 8, 16, 4
    rs = np.random.RandomState(5)

    net_e = WideDeep(vocab, embed_dim=E, hidden=(16,), classes=2,
                     sparse_grad=True)
    net_e.initialize()
    net_j = WideDeep(vocab, embed_dim=E, hidden=(16,), classes=2,
                     sparse_grad=True)
    net_j.initialize()
    # same init
    pe, pj = net_e.collect_params(), net_j.collect_params()
    touched = set()
    # trigger deferred init with one forward each
    i0 = nd.array(rs.randint(0, vocab, (B, F)), dtype="int32")
    v0 = nd.array(rs.rand(B, F).astype(np.float32))
    net_e(i0, v0)
    net_j(i0, v0)
    # in creation order, which both nets share: sorted by name, the pairs
    # cross where the global name counter passes a power of ten between
    # the two nets (embedding9_weight, embedding10_weight)
    for p_e, p_j in zip(pe.values(), pj.values()):
        p_j.set_data(nd.array(p_e.data().asnumpy()))

    trainer = gluon.Trainer(pe, "adam", {"learning_rate": 1e-2})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    jt = BucketedSparseTrainer(net_j, optimizer="adam", lr=1e-2)

    # batches with very different unique-row counts → several buckets
    for nuniq in (5, 40, 300, 12):
        pool = rs.choice(vocab, size=nuniq, replace=False)
        idx = rs.choice(pool, size=(B, F)).astype(np.int32)
        touched.update(idx.reshape(-1).tolist())
        vals = rs.rand(B, F).astype(np.float32)
        y = rs.randint(0, 2, B).astype(np.float32)

        with ag.record():
            out = net_e(nd.array(idx, dtype="int32"), nd.array(vals))
            l = sce(out, nd.array(y))
            l.backward()
        trainer.step(B)
        loss_j = jt.step(np.asarray(idx), vals, y)
        # eager loss is per-sample; jit loss is the mean
        np.testing.assert_allclose(float(loss_j.asnumpy()),
                                    float(l.mean().asnumpy()),
                                    rtol=1e-4, atol=1e-5)

    jt.sync_to_net()
    untouched = np.array(sorted(set(range(vocab)) - touched))
    assert len(untouched) > 0
    # the two nets carry different auto-prefixes; pair params by
    # sorted order (same construction order on both sides)
    for ke, kj in zip(sorted(pe), sorted(pj)):
        a = pe[ke].data().asnumpy()
        b = pj[kj].data().asnumpy()
        # atol bounds Adam's eps-zone chaos (a row whose summed grad
        # lands near eps has a summation-order-sensitive update in
        # BOTH paths); a semantic bug (wrong rows, missing wd, wrong
        # t) shows up at the ~3e-2 update scale
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-3,
                                   err_msg="%s vs %s" % (ke, kj))
        if ke.startswith("embedding"):
            # the lazy-semantics core: rows never touched by any batch
            # must be BIT-IDENTICAL across the two paths
            np.testing.assert_array_equal(a[untouched], b[untouched],
                                          err_msg=ke + " untouched")


def test_bucketed_sparse_trainer_bucket_rows_and_overflow():
    """Explicit bucket_rows: small-unique batches fit the bucket and
    update correctly; a batch whose unique count exceeds the bucket
    increments the device-side overflow counter (surfaced lazily —
    no per-step host sync)."""
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.models.wide_deep import WideDeep
    from incubator_mxnet_tpu.contrib.sparse_jit import \
        BucketedSparseTrainer

    vocab, E, B, F = 300, 4, 8, 4
    rs = np.random.RandomState(11)
    net = WideDeep(vocab, embed_dim=E, hidden=(8,), classes=2,
                   sparse_grad=True)
    net.initialize()
    net(nd.array(rs.randint(0, vocab, (B, F)), dtype="int32"),
        nd.array(rs.rand(B, F).astype(np.float32)))
    jt = BucketedSparseTrainer(net, optimizer="sgd", lr=1e-2,
                               bucket_rows=8)
    w0 = np.asarray(jt._state["tables"][jt._deep_name])[:-1].copy()

    # 4 unique rows < bucket 8: fits
    pool = rs.choice(vocab, size=4, replace=False)
    idx = rs.choice(pool, size=(B, F)).astype(np.int32)
    vals = rs.rand(B, F).astype(np.float32)
    y = rs.randint(0, 2, B).astype(np.float32)
    l1 = jt.step(idx, vals, y)
    assert jt.overflow_steps == 0
    w1 = np.asarray(jt._state["tables"][jt._deep_name])[:-1]
    changed = np.where(np.any(w1 != w0, axis=1))[0]
    assert set(changed) <= set(pool.tolist())
    assert len(changed) > 0

    # 20 unique rows > bucket 8: the step is SKIPPED — overflow
    # counted, state bit-identical (no poisoning); the returned loss
    # is the PREVIOUS finite loss (NaN-free contract on step()), so
    # naive per-step loss averaging stays finite
    before = {k: np.asarray(v).copy()
              for k, v in jt._state["tables"].items()}
    t_before = int(np.asarray(jt._state["t"]))
    idx2 = rs.choice(vocab, size=(B, F), replace=False).astype(np.int32)
    assert len(np.unique(idx2)) > 8
    l_ovf = jt.step(idx2, vals, y)
    assert jt.overflow_steps == 1
    assert not np.isnan(float(l_ovf.asnumpy()))
    assert float(l_ovf.asnumpy()) == float(l1.asnumpy())
    for k, v in jt._state["tables"].items():
        np.testing.assert_array_equal(np.asarray(v), before[k])
    assert int(np.asarray(jt._state["t"])) == t_before

    # training recovers: a following in-bucket step updates normally
    l_ok = jt.step(idx, vals, y)
    assert not np.isnan(float(l_ok.asnumpy()))
    assert jt.overflow_steps == 1
