"""Distributed/sharded path on the virtual 8-device CPU mesh
(ref test strategy: tests/nightly/dist_*_kvstore.py run multi-node as
multi-process localhost; here multi-chip as 8 virtual devices —
SURVEY §4 'carry into the TPU build' item 3)."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, parallel
from incubator_mxnet_tpu.test_utils import assert_almost_equal

import jax


requires_multidevice = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs multi-device (virtual) mesh")


def test_mesh_creation():
    mesh = parallel.make_mesh()
    assert mesh.devices.size == len(jax.devices())
    if len(jax.devices()) >= 8:
        mesh2 = parallel.make_mesh((4, 2), ("data", "model"))
        assert mesh2.axis_names == ("data", "model")


def test_functionalize_matches_imperative():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    x = nd.array(np.random.randn(4, 5).astype("float32"))
    ref = net(x).asnumpy()
    pure = parallel.functionalize(net)
    params = parallel.extract_params(net)
    out, states = pure(params, x._data)
    assert np.allclose(np.asarray(out), ref, atol=1e-5)
    assert states == {}


@requires_multidevice
def test_sharded_trainer_dp_step():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(nd.ones((2, 8)))     # materialise shapes
    trainer = parallel.ShardedTrainer(net, optimizer="sgd", lr=0.05)
    n_dev = len(jax.devices())
    batch = np.random.randn(4 * n_dev, 8).astype("float32")
    labels = np.random.randint(0, 4, 4 * n_dev)
    losses = []
    for _ in range(10):
        loss = trainer.step(batch, labels)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    trainer.sync_to_block()
    out = net(nd.array(batch[:4]))
    assert out.shape == (4, 4)


@requires_multidevice
def test_dp_matches_single_device_step():
    """One DP step on the mesh == one large-batch step on one device."""
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    params0 = {k: np.asarray(v) for k, v in
               parallel.extract_params(net).items()}
    batch = np.random.randn(8, 3).astype("float32")
    labels = np.random.randint(0, 2, 8)

    t_mesh = parallel.ShardedTrainer(net, optimizer="sgd", lr=0.1,
                                     momentum=0.0)
    t_mesh.step(batch, labels)
    mesh_params = {k: np.asarray(v) for k, v in t_mesh.params.items()}

    # single-device reference via imperative trainer
    net2 = gluon.nn.Dense(2, in_units=3)
    net2.initialize()
    for k, p in net2.collect_params().items():
        p.set_data(nd.array(params0[k.replace(net2.prefix,
                                              net.prefix)]
                            if k not in params0 else params0[k]))
    from incubator_mxnet_tpu import autograd as ag
    tr = gluon.Trainer(net2.collect_params(), "sgd",
                       {"learning_rate": 0.1})
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    with ag.record():
        out = net2(nd.array(batch))
        loss = lossfn(out, nd.array(labels.astype("float32"))).mean()
    loss.backward()
    tr.step(1)      # rescale 1: loss already mean ⇒ same as mesh step
    ref_params = {k: p.data().asnumpy()
                  for k, p in net2.collect_params().items()}
    for (km, vm), (kr, vr) in zip(sorted(mesh_params.items()),
                                  sorted(ref_params.items())):
        assert_almost_equal(vm, vr, rtol=1e-4, atol=1e-5)


@requires_multidevice
def test_psum_collective_semantics():
    """Exact-value allreduce invariant (ref: dist_sync_kvstore asserts:
    sum == num_workers × grad)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    mesh = parallel.make_mesh()
    n = mesh.devices.size
    x = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))

    @jax.jit
    def allreduce(v):
        return jnp.sum(v, axis=0, keepdims=True)
    out = np.asarray(allreduce(xs))
    assert np.allclose(out[0], x.sum(axis=0))


@requires_multidevice
def test_tensor_parallel_sharding_compiles():
    """dp×tp mesh: weight sharded on 'model' axis, batch on 'data'."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    ndev = len(jax.devices())
    if ndev % 2:
        pytest.skip("needs even device count")
    mesh = parallel.make_mesh((ndev // 2, 2), ("data", "model"))
    w = jax.device_put(np.random.randn(8, 16).astype("float32"),
                       NamedSharding(mesh, P(None, "model")))
    x = jax.device_put(np.random.randn(4, 8).astype("float32"),
                       NamedSharding(mesh, P("data", None)))

    @jax.jit
    def f(x, w):
        return jnp.tanh(x @ w)
    out = f(x, w)
    assert out.shape == (4, 16)


def test_split_and_load_multi_ctx():
    ctxs = [mx.cpu(0), mx.cpu(0)]
    data = nd.array(np.arange(8).reshape(4, 2))
    parts = gluon.split_and_load(data, ctxs)
    assert len(parts) == 2
    assert parts[0].shape == (2, 2)


def test_sharded_trainer_checkpoint_resume(tmp_path):
    """Pod-scale checkpoint/resume: save mid-training, restore into a
    FRESH trainer, and verify bit-identical continued training
    (ref: Trainer.save_states/load_states, sharded via orbax)."""
    import numpy as np
    import jax
    from incubator_mxnet_tpu import nd, parallel, gluon
    import incubator_mxnet_tpu as mx

    def build():
        # fixed prefixes: checkpoint portability across processes needs
        # stable param names (the reference's prefix= contract)
        mx.random.seed(11)
        net = gluon.nn.HybridSequential(prefix="ck_")
        net.add(gluon.nn.Dense(16, in_units=8, activation="relu",
                               prefix="ck_d1_"),
                gluon.nn.Dense(4, in_units=16, prefix="ck_d2_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, 8)))
        return parallel.ShardedTrainer(net, optimizer="adam", lr=1e-2)

    rs = np.random.RandomState(0)
    xs = [rs.randn(8, 8).astype(np.float32) for _ in range(6)]
    ys = [rs.randint(0, 4, 8) for _ in range(6)]

    t1 = build()
    for i in range(3):
        t1.step(xs[i], ys[i], rng_bits=jax.random.key_data(
            jax.random.PRNGKey(i)))
    ckpt = str(tmp_path / "ckpt")
    t1.save_checkpoint(ckpt)
    # continue original
    losses_a = [float(t1.step(xs[i], ys[i], rng_bits=jax.random.key_data(
        jax.random.PRNGKey(i)))) for i in range(3, 6)]

    # fresh trainer restores and continues identically
    t2 = build()
    t2.load_checkpoint(ckpt)
    assert t2._n_step == 3
    losses_b = [float(t2.step(xs[i], ys[i], rng_bits=jax.random.key_data(
        jax.random.PRNGKey(i)))) for i in range(3, 6)]
    assert np.allclose(losses_a, losses_b, rtol=1e-6), (losses_a,
                                                        losses_b)


def test_sharded_trainer_checkpoint_rejects_mismatch(tmp_path):
    import numpy as np
    import pytest
    from incubator_mxnet_tpu import nd, parallel, gluon

    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    net(nd.ones((1, 8)))
    t = parallel.ShardedTrainer(net, optimizer="sgd", lr=0.1)
    ckpt = str(tmp_path / "ck")
    t.save_checkpoint(ckpt)

    other = gluon.nn.Dense(6, in_units=3)
    other.initialize()
    other(nd.ones((1, 3)))
    t2 = parallel.ShardedTrainer(other, optimizer="sgd", lr=0.1)
    with pytest.raises(ValueError):
        t2.load_checkpoint(ckpt)


def test_sharded_trainer_checkpoint_shape_mismatch(tmp_path):
    """Same param NAMES but different shapes must be rejected, not
    silently loaded (wrong-architecture resume)."""
    import pytest
    from incubator_mxnet_tpu import nd, parallel, gluon

    def build(units):
        net = gluon.nn.Dense(units, in_units=8, prefix="shp_")
        net.initialize(force_reinit=True)
        net(nd.ones((1, 8)))
        return parallel.ShardedTrainer(net, optimizer="sgd", lr=0.1)

    t8 = build(8)
    ckpt = str(tmp_path / "ck8")
    t8.save_checkpoint(ckpt)
    t16 = build(16)
    with pytest.raises(ValueError):
        t16.load_checkpoint(ckpt)


@requires_multidevice
def test_zero1_sharded_opt_state_matches_replicated():
    """ZeRO-1: sharded optimizer state must train bit-for-bit like the
    replicated baseline, while each leaf's addressable shard is 1/ndev
    of the full tensor (the memory claim being purchased)."""
    ndev = len(jax.devices())
    net = gluon.nn.HybridSequential()
    # hidden sized divisible by ndev so every weight has a ZeRO axis
    net.add(gluon.nn.Dense(8 * ndev, in_units=8, activation="relu"),
            gluon.nn.Dense(4, in_units=8 * ndev))
    net.initialize()
    net(nd.ones((2, 8)))
    params0 = {k: np.asarray(v)
               for k, v in parallel.extract_params(net).items()}

    batch = np.random.randn(2 * ndev, 8).astype("float32")
    labels = np.random.randint(0, 4, 2 * ndev)

    t_zero = parallel.ShardedTrainer(net, optimizer="adam", lr=1e-2,
                                     zero=1)
    t_base = parallel.ShardedTrainer(net, optimizer="adam", lr=1e-2)
    # identical starting points
    t_zero.params = {k: jax.device_put(params0[k],
                                       t_zero._param_shardings[k])
                     for k in params0}
    t_base.params = {k: jax.device_put(params0[k],
                                       t_base._param_shardings[k])
                     for k in params0}

    for _ in range(4):
        lz = t_zero.step(batch, labels)
        lb = t_base.step(batch, labels)
    assert_almost_equal(float(lz), float(lb), rtol=1e-5, atol=1e-6)
    for k in params0:
        assert_almost_equal(np.asarray(t_zero.params[k]),
                            np.asarray(t_base.params[k]),
                            rtol=1e-5, atol=1e-6)

    # the memory claim: every ZeRO-eligible moment leaf is sharded
    sharded = 0
    for k, v in t_zero.opt_state["m"].items():
        shard_elems = v.addressable_shards[0].data.size
        if any(d % ndev == 0 and d >= ndev for d in v.shape):
            assert shard_elems == v.size // ndev, \
                "%s not sharded: %d vs %d" % (k, shard_elems, v.size)
            sharded += 1
    assert sharded >= 2


@requires_multidevice
def test_zero1_checkpoint_roundtrip(tmp_path):
    ndev = len(jax.devices())

    def build():
        # fixed prefix: stable param names across fresh nets; a fresh
        # net is required because the donated step consumes the first
        # net's block buffers
        net = gluon.nn.Dense(4 * ndev, in_units=6, prefix="zck_d_")
        net.initialize(force_reinit=True)
        net(nd.ones((2, 6)))
        return parallel.ShardedTrainer(net, optimizer="adam", lr=1e-2,
                                       zero=1)

    tr = build()
    batch = np.random.randn(ndev, 6).astype("float32")
    labels = np.random.randint(0, 4 * ndev, ndev)
    tr.step(batch, labels)
    params_after = {k: np.asarray(v) for k, v in tr.params.items()}
    tr.save_checkpoint(str(tmp_path / "zck"))

    tr2 = build()
    tr2.load_checkpoint(str(tmp_path / "zck"))
    m = next(iter(tr2.opt_state["m"].values()))
    assert m.addressable_shards[0].data.size == m.size // ndev
    for k in params_after:
        assert_almost_equal(np.asarray(tr2.params[k]),
                            params_after[k], rtol=1e-6, atol=1e-7)
    # training continues from the restored sharded state
    tr2.step(batch, labels)


def _cpu_multiprocess_collectives_supported():
    """Whether this jax can run cross-process collectives on the CPU
    backend.  Compiling a multi-process computation there needs a CPU
    collectives transport (gloo/mpi), which jax only wires up where
    the `jax_cpu_collectives_implementation` config exists (0.5.x+);
    without it the compile fails with 'Multiprocess computations
    aren't implemented on the CPU backend' — a missing CAPABILITY, not
    a regression, so the multicontroller test skips instead of
    staining tier-1 (ISSUE 8 satellite)."""
    return hasattr(jax.config, "jax_cpu_collectives_implementation")


def test_multicontroller_sharded_trainer_matches_single_process(tmp_path):
    """REAL multi-controller training: 2 localhost processes x 4 virtual
    devices form one 8-device global mesh via jax.distributed; each
    process feeds its slice of the global batch.  The result must match
    a single-process 8-device run of the identical schedule (the
    reference's multi-node == single-node-big-batch invariant, here for
    the pjit/ICI path rather than the kvstore path)."""
    import json
    import os
    import socket
    import subprocess
    import sys

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices for the reference run")
    if jax.default_backend() == "cpu" and \
            not _cpu_multiprocess_collectives_supported():
        pytest.skip("CPU backend lacks multiprocess collectives on "
                    "this jax (no jax_cpu_collectives_implementation "
                    "config) — the worker compile fails with "
                    "'Multiprocess computations aren't implemented on "
                    "the CPU backend'")

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "..", "..", "nightly",
                          "dist_sharded_trainer.py")
    repo = os.path.abspath(os.path.join(os.path.dirname(worker),
                                        "..", ".."))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    out_json = str(tmp_path / "dst.json")
    ref_json = str(tmp_path / "ref.json")
    base_env = {
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH",
                                                         ""),
    }
    procs = []
    try:
        for rank in range(2):
            env = dict(os.environ)
            env.update(base_env)
            env.update({
                "XLA_FLAGS":
                    "--xla_force_host_platform_device_count=4",
                "DMLC_NUM_WORKER": "2",
                "DMLC_WORKER_ID": str(rank),
                "DMLC_PS_ROOT_URI": "127.0.0.1",
                "DMLC_PS_ROOT_PORT": str(port),
            })
            procs.append(subprocess.Popen(
                [sys.executable, worker, out_json] if rank == 0 else
                [sys.executable, worker],
                env=env, cwd=repo, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, "worker failed:\n%s" % out[-3000:]
    with open(out_json) as f:
        got = json.load(f)
    assert got["n_devices"] == 8 and got["n_processes"] == 2

    # single-process 8-device reference: the SAME worker script run as
    # one process (hermetic — no jax config mutation in this process,
    # same forced-CPU backend as the workers)
    env = dict(os.environ)
    env.update(base_env)
    env.pop("DMLC_NUM_WORKER", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run([sys.executable, worker, ref_json], env=env,
                         cwd=repo, capture_output=True, text=True,
                         timeout=420)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-1000:]
    with open(ref_json) as f:
        ref = json.load(f)
    assert ref["n_devices"] == 8 and ref["n_processes"] == 1
    assert abs(got["loss"] - ref["loss"]) < 1e-5, (got, ref)
    assert abs(got["checksum"] - ref["checksum"]) < 1e-4, (got, ref)
