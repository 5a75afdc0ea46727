"""The one way an executable is compiled: `telemetry.costs.metered_jit`
(the rows it files, what it names the executable, what it keeps alive)
and `compile_cache.enable()` (the only cache of compiled code).
CPU-only: values, counts and names, never a time."""
import gc
import importlib.util
import os
import re
import threading
import weakref

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.telemetry import costs, spans

PKG = os.path.dirname(os.path.abspath(mx.__file__))


@pytest.fixture
def log():
    spans._LOG.clear()
    yield spans
    spans._LOG.clear()


def _rows(label):
    return [r for r in costs.table() if r["label"].startswith(label + "[")]


def _calls(label):
    return [r for r in spans.phase_log(prefix="compile.call")
            if r[3] == label]


def _fwd(a, b):
    return jax.vjp(lambda x, y: (x * y).sum(), a, b)


# -- metered_jit ---------------------------------------------------------

def test_value_and_vjp_equal_plain_jit():
    x, y = jnp.ones((8, 8)), jnp.full((8, 8), 2.0)
    out, vjp = costs.metered_jit(_fwd, label="t.vjp", kind="test")(x, y)
    ref, ref_vjp = jax.jit(_fwd)(x, y)
    assert float(out) == float(ref) == 128.0
    for g, r in zip(vjp(jnp.ones(())), ref_vjp(jnp.ones(()))):
        onp.testing.assert_array_equal(onp.asarray(g), onp.asarray(r))


def test_weak_type_traces_a_signature_of_its_own(log):
    mj = costs.metered_jit(lambda a: a * 2, label="t.weak", kind="test")
    strong, weak = jnp.asarray(onp.float32(3.0)), jnp.asarray(3.0)
    assert not strong.weak_type and weak.weak_type
    assert float(mj(strong)) == float(mj(weak)) == 6.0
    assert sorted(r["label"] for r in _rows("t.weak")) == \
        ["t.weak[0]", "t.weak[1]"]
    assert len(_calls("t.weak")) == 2


def test_a_second_device_runs_there_on_the_signature_already_traced(log):
    """jit keys its trace on the avals and compiles again for the other
    device: the registry's unit is the traced signature, so one row and
    one `compile.call` row, both devices' calls counted on it."""
    d0, d1 = jax.devices()[:2]
    mj = costs.metered_jit(lambda a: a * 2.0, label="t.dev", kind="test")
    out0 = mj(jax.device_put(jnp.ones((4,)), d0))
    out1 = mj(jax.device_put(jnp.ones((4,)), d1))
    assert out0.devices() == {d0} and out1.devices() == {d1}
    onp.testing.assert_array_equal(onp.asarray(out1), onp.full((4,), 2.0))
    (row,) = _rows("t.dev")
    assert row["invocations"] == 2 and len(_calls("t.dev")) == 1


def test_a_donated_argument_is_consumed_and_aliased():
    mj = costs.metered_jit(lambda a: a + 1.0, donate_argnums=(0,),
                           label="t.donate", kind="test",
                           expect_donated=(0,))
    x = jnp.ones((1024,))
    before = x.unsafe_buffer_pointer()
    out = mj(x)
    assert x.is_deleted()
    assert out.unsafe_buffer_pointer() == before
    assert float(out[0]) == 2.0


def test_a_trace_that_raises_leaves_no_row_behind(log):
    def fn(a):
        if a.shape[0] == 3:
            raise ValueError("no threes")
        return a * 3.0

    mj = costs.metered_jit(fn, label="t.raise", kind="test")
    with pytest.raises(ValueError, match="no threes"):
        mj(jnp.ones((3,)))
    assert not mj._pending
    assert _rows("t.raise") == [] and _calls("t.raise") == []
    mj(jnp.ones((4,)))                  # the next good signature: its own row
    assert [r["label"] for r in _rows("t.raise")] == ["t.raise[0]"]
    assert len(_calls("t.raise")) == 1


def test_a_cache_hit_adds_no_row_and_counts_once(log):
    mj = costs.metered_jit(lambda a: a - 1.0, label="t.hit", kind="test")
    x = jnp.ones((5,))
    mj(x)
    (row,) = _rows("t.hit")
    assert row["invocations"] == 1
    mj(x)
    (row,) = _rows("t.hit")
    assert row["invocations"] == 2 and len(_calls("t.hit")) == 1


def test_dropping_the_wrapper_frees_it():
    mj = costs.metered_jit(lambda a: a * 5.0, label="t.free", kind="test")
    mj(jnp.ones((2,)))
    costs.table()                       # resolved rows hold no strong ref
    dead, dead_jit = weakref.ref(mj), weakref.ref(mj._jit)
    del mj
    gc.collect()
    assert dead() is None and dead_jit() is None


def test_lower_names_the_module_by_label_or_role():
    mj = costs.metered_jit(lambda a: a, label="t.some/label:1", kind="test")
    by_role = costs.metered_jit(lambda a: a, label="t.other", kind="test",
                                role="t_role")
    for f, want in ((mj, "jit__traced_t_some_label_1"),
                    (by_role, "jit__traced_t_role")):
        text = f.lower(jnp.ones((2,))).as_text()
        assert text.split("module @", 1)[1].split(" ", 1)[0] == want


def test_resolving_a_row_registers_nothing(log):
    """`table()` lowers against the stored avals; they carry no weak
    type, so this lowering traces the function again — under the
    thread-local `resolving` flag, which keeps the hook quiet."""
    traces = []

    def fn(a):
        traces.append(a.weak_type)
        return a * 2

    mj = costs.metered_jit(fn, label="t.resolve", kind="test")
    mj(jnp.asarray(3.0))
    (row,) = _rows("t.resolve")         # resolves the pending row
    assert row["analyzed"] and traces == [True, False]
    assert not mj._pending
    mj(jnp.asarray(3.0))
    (row,) = _rows("t.resolve")
    assert row["invocations"] == 2 and len(_calls("t.resolve")) == 1


def test_two_threads_tracing_at_once_leave_two_rows():
    mj = costs.metered_jit(lambda a: a.sum(), label="t.threads",
                           kind="test")
    gate = threading.Barrier(2)
    out = {}

    def run(n):
        gate.wait(timeout=30)
        out[n] = float(mj(jnp.ones((n,))))

    threads = [threading.Thread(target=run, args=(n,)) for n in (3, 4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert out == {3: 3.0, 4: 4.0}
    rows = _rows("t.threads")
    assert len(rows) == 2 and sum(r["invocations"] for r in rows) == 2


def test_lowerings_gives_one_lowered_a_signature_while_the_wrapper_lives():
    mj = costs.metered_jit(lambda a: a * 7.0, label="t.lowerings",
                           kind="test")
    mj(jnp.ones((2,)))
    mj(jnp.ones((3,)))
    lows = costs.lowerings("t.lowerings")
    assert len(lows) == 2
    assert all("jit__traced_t_lowerings" in low.as_text() for low in lows)
    assert costs.lowerings("t.lowering") == []      # exact up to the '['
    del mj, lows
    gc.collect()
    assert costs.lowerings("t.lowerings") == []


# -- every labelled call site of the package ------------------------------

def _hybrid_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    return net


def _xy():
    return (nd.array(onp.random.randn(4, 5).astype(onp.float32)),
            nd.array(onp.array([0, 1, 2, 1], onp.int32)))


def _site_cachedop_fwd():
    net = _hybrid_net()
    net(_xy()[0]).asnumpy()
    return net.name + ".fwd", None


def _site_cachedop_fwd_vjp():
    net = _hybrid_net()
    with autograd.record():
        out = net(_xy()[0])
    out.asnumpy()
    return net.name + ".fwd_vjp", None


def _site_fused_fwd_vjp():
    net, (x, y) = _hybrid_net(), _xy()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    for _ in range(3):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.asnumpy()      # read before backward: the deferred forward
        loss.backward()     # runs as an executable of its own
        trainer.step(4)
    return "gluon.fused_fwd_vjp", None


def _site_serve_infer():
    from incubator_mxnet_tpu.serving import InferenceEngine
    net = _hybrid_net()
    net(_xy()[0])
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          cost_label="serve.infer:m/1")
    try:
        eng.warmup(example_shape=(5,), wire_dtype="float32")
    finally:
        eng.close()
    return "serve.infer:m/1", "serve_infer"


def _site_sharded_zstep():
    from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net(nd.array(onp.zeros((2, 5), onp.float32)))
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    tr = ShardedTrainer(net, optimizer="sgd", lr=0.1, mesh=mesh, zero=2)
    try:
        jax.block_until_ready(tr.step(
            onp.random.randn(4, 5).astype(onp.float32),
            onp.array([0, 1, 2, 1], onp.int32)))
    finally:
        tr.release()
    return "sharded.zstep", None


@pytest.mark.parametrize("site", [
    _site_cachedop_fwd, _site_cachedop_fwd_vjp, _site_fused_fwd_vjp,
    _site_serve_infer, _site_sharded_zstep],
    ids=lambda f: f.__name__[len("_site_"):])
def test_call_site_builds_a_metered_jit_named_by_its_role(
        site, monkeypatch, log):
    """The generation engine's three, the fused Gluon step and
    `sharded.step` are in test_phase_log.py; these are the other five."""
    built = {}
    orig = costs.metered_jit

    def spy(fn, **kw):
        out = orig(fn, **kw)
        built.setdefault(kw.get("label"), []).append(out)
        return out

    monkeypatch.setattr(costs, "metered_jit", spy)
    label, role = site()
    (exe,) = built[label]
    assert isinstance(exe, costs.MeteredJit)
    slug = re.sub(r"[^0-9A-Za-z]+", "_", role or label).strip("_")
    assert exe._jit.__wrapped__.__name__ == "_traced_" + slug
    assert len(_calls(label)) == 1
    assert [r["label"] for r in _rows(label)] == [label + "[0]"]


def test_warmup_without_a_signature_names_the_label():
    from incubator_mxnet_tpu.serving import InferenceEngine
    net = _hybrid_net()
    net(_xy()[0])
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          cost_label="serve.infer:nameless")
    try:
        with pytest.raises(ValueError, match="serve.infer:nameless") as e:
            eng.warmup()
        assert "example_shape" in str(e.value)
    finally:
        eng.close()


# -- one cache of compiled code --------------------------------------------

def test_the_disk_cache_is_gone_and_enable_alone_sets_a_cache_dir():
    from incubator_mxnet_tpu import config
    assert not [name for name in config.list_vars()
                if "AOT" in name or "PREWARM" in name]
    for gone in ("incubator_mxnet_tpu.aot_cache",
                 "incubator_mxnet_tpu.compile.prewarm"):
        assert importlib.util.find_spec(gone) is None, gone
    setters = []
    for dirpath, _dirs, files in os.walk(PKG):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as fh:
                if re.search(r"config\.update\(\s*[\"']"
                             r"jax_compilation_cache_dir", fh.read()):
                    setters.append(os.path.relpath(path, PKG))
    assert setters == ["compile_cache.py"]
