"""The always-on phase log (telemetry/spans.py, PR 27): the primitive, the
rows the generation engine, the trainers and the compile path leave in it,
the same intervals in a `jax.profiler` session, and the executables' names.
CPU-only: counts, nesting and names, never a time."""
import glob
import os
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, nd
from incubator_mxnet_tpu.models.transformer import transformer_nmt_small
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.serving import GenerationEngine
from incubator_mxnet_tpu.telemetry import spans

V, BOS, EOS = 23, 1, 2


@pytest.fixture
def log():
    spans._LOG.clear()
    yield spans
    spans._LOG.clear()


# -- the primitive -------------------------------------------------------

def test_nesting_ident_parent_and_count(log):
    with log.phase("t.outer", 7) as outer:
        with log.phase("t.inner", "req-1", outer.ident, n=2) as inner:
            inner.n += 1
        log.phase_at("t.given", 10.0, 12.5, 3, outer.ident, 4)
    rows = log.phase_log()
    assert [r[0] for r in rows] == ["t.inner", "t.given", "t.outer"]
    inner_row, given, outer_row = rows
    assert inner_row[3:] == ("req-1", 7, 3)
    assert given == ("t.given", 10.0, 12.5, 3, 7, 4)
    assert outer_row[3:] == (7, None, 0)
    # a child lies inside its parent, on one clock
    assert outer_row[1] <= inner_row[1] <= inner_row[2] <= outer_row[2]
    assert outer.seconds == outer_row[2] - outer_row[1] >= inner.seconds


def test_rows_are_written_without_a_knob_and_without_formatting(log, monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    assert not spans.enabled()              # spans are off; the log is not
    ident, parent = object(), object()
    with log.phase("t.raw", ident, parent):
        pass
    (row,) = log.phase_log()
    assert type(row) is tuple and len(row) == 6
    assert row[3] is ident and row[4] is parent     # stored, never formatted


def test_window_and_prefix_filters_and_totals(log):
    for k in range(5):
        log.phase_at("a.x", 100.0 + k, 100.5 + k, k, None, 2)
    log.phase_at("a.y", 101.25, 109.0)
    log.phase_at("b.x", 102.0, 102.1)
    # by START time, both ends included
    assert [r[3] for r in log.phase_log(since=101.0, until=103.0,
                                        prefix="a.x")] == [1, 2, 3]
    assert [r[0] for r in log.phase_log(since=101.1, until=102.0)] == \
        ["a.x", "a.y", "b.x"]
    assert len(log.phase_log(prefix="a.")) == 6
    tot = log.phase_totals("a.")
    assert set(tot) == {"a.x", "a.y"}
    assert tot["a.x"][0] == 5 and tot["a.x"][2] == 10
    assert tot["a.x"][1] == pytest.approx(2.5)


def test_ring_is_bounded_by_a_constant(log):
    n = spans._LOG.maxlen
    assert n == 1 << 18
    for k in range(n + 10):
        log.phase_at("t.fill", float(k), float(k))
    rows = log.phase_log()
    assert len(rows) == n and rows[0][1] == 10.0        # oldest dropped


def test_a_span_is_a_phase_too(log):
    prev = spans.enable(True)
    try:
        with spans.span("t.span") as outer:
            with spans.span("t.child"):
                pass
    finally:
        spans.enable(prev)
    child, parent = log.phase_log(prefix="t.")
    assert (child[0], parent[0]) == ("t.child", "t.span")
    assert parent[3] == outer.ctx.span_id and child[4] == outer.ctx.span_id
    assert parent[1] <= child[1] <= child[2] <= parent[2]


# -- the generation engine -----------------------------------------------

def _engine(slots=3):
    mx.random.seed(0)
    net = transformer_nmt_small(V, V, dropout=0.0)
    net.initialize(force_reinit=True)
    return GenerationEngine(net, bos=BOS, eos=EOS, slots=slots, max_len=16,
                            prompt_buckets=(4, 8))


def _serve(eng, n=7):
    rng = onp.random.RandomState(5)
    streams = [eng.submit(rng.randint(3, V, size=rng.randint(2, 8)), 6)
               for _ in range(n)]
    for s in streams:
        s.result(timeout=120)
    return streams


@pytest.fixture(scope="module")
def served():
    """One tiny engine run (7 requests over 3 slots): its rows, and the
    counters' change over it."""
    spans._LOG.clear()
    eng = _engine()
    eng.warmup()
    names = ("gen.tokens", "gen.joins", "gen.steps")
    before = {k: events.get(k) for k in names}
    t0 = time.monotonic()
    _serve(eng)
    stats = eng.stats()
    eng.close()
    rows = spans.phase_log(since=t0, prefix="gen.")
    return rows, {k: events.get(k) - before[k] for k in names}, stats


def test_engine_rows_nest_inside_their_tick(served):
    rows, delta, _ = served
    ticks = {r[3]: r for r in rows if r[0] == "gen.tick"}
    assert ticks and len(ticks) == len([r for r in rows if r[0] == "gen.tick"])
    children = [r for r in rows if r[0] in (
        "gen.admit", "gen.prefill", "gen.join", "gen.decode", "gen.sync",
        "gen.emit")]
    assert {r[0] for r in children} == {
        "gen.admit", "gen.prefill", "gen.join", "gen.decode", "gen.sync",
        "gen.emit"}
    for r in children:
        tick = ticks[r[4]]
        assert tick[1] <= r[1] <= r[2] <= tick[2], (r, tick)
    # one sync and one emit per step, in that order at the end of the tick;
    # the step's decode was dispatched before its sync: in this tick, or
    # early, in the tick before (a full engine with no stream about to end)
    by_tick = {}
    for r in rows:
        if r[0] in ("gen.decode", "gen.sync", "gen.emit"):
            by_tick.setdefault(r[4], []).append(r)
    assert len(by_tick) == delta["gen.steps"]
    for steps in by_tick.values():
        names = [r[0] for r in sorted(steps, key=lambda r: r[1])]
        assert names[-2:] == ["gen.sync", "gen.emit"], names
        assert names[:-2] in ([], ["gen.decode"], ["gen.decode"] * 2), names
    dispatched = {}
    for r in rows:
        if r[0] == "gen.decode":
            dispatched.setdefault(r[3], []).append(r)
    for r in rows:
        if r[0] == "gen.sync":
            assert any(d[2] <= r[1] and ticks[r[4]][3] - ticks[d[4]][3]
                       in (0, 1) for d in dispatched[r[3]]), r
    # a tick's n is its live slots, as its decode's
    for r in rows:
        if r[0] == "gen.decode":
            assert r[5] == ticks[r[4]][5] > 0


def test_engine_counts_agree_with_the_counters(served):
    rows, delta, stats = served
    assert sum(r[5] for r in rows if r[0] == "gen.emit") == delta["gen.tokens"]
    assert sum(r[5] for r in rows if r[0] == "gen.admit") == delta["gen.joins"] == 7
    assert len([r for r in rows if r[0] == "gen.prefill"]) == 7
    assert len([r for r in rows if r[0] == "gen.join"]) == 7
    tot = stats["phases"]
    assert tot["gen.join"][0] >= 7 and tot["gen.emit"][2] >= delta["gen.tokens"]


def test_request_phases_add_up_to_the_first_token(served):
    rows, _, _ = served
    per = {}
    for r in rows:
        if r[0].startswith("gen.req."):
            per.setdefault(r[3], {})[r[0]] = r
    assert len(per) == 7
    ticks = {r[3] for r in rows if r[0] == "gen.tick"}
    prefill = {r[3]: r for r in rows if r[0] == "gen.prefill"}
    for rid, ph in per.items():
        q, a, f = (ph["gen.req.queue"], ph["gen.req.admit"],
                   ph["gen.req.first"])
        assert q[2] == a[1] and a[2] == f[1]        # one ladder, no gap
        total = sum(r[2] - r[1] for r in (q, a, f))
        assert total == pytest.approx(f[2] - q[1], abs=1e-9)
        assert q[4] == a[4] == f[4] and q[4] in ticks   # the admitting tick
        assert prefill[rid][4] == q[4]
        assert a[1] <= prefill[rid][1] and prefill[rid][2] <= a[2]


def test_the_journal_tells_the_same_story(log):
    eng = _engine(slots=2)
    eng.warmup()
    _serve(eng, n=3)
    recs = list(eng._journal._ring)
    eng.close()
    first = {r[3]: r for r in log.phase_log(prefix="gen.req.first")}
    queue = {r[3]: r for r in log.phase_log(prefix="gen.req.queue")}
    assert len(recs) == 3
    for rec in recs:
        assert rec.t_first == first[rec.rid][2]
        assert rec.t_exec == first[rec.rid][1]
        assert (rec.t_enq, rec.t_collect) == queue[rec.rid][1:3]
        assert rec.t_enq <= rec.t_collect <= rec.t_exec <= rec.t_first <= rec.t_fin
    from incubator_mxnet_tpu.telemetry import reqtrace
    summ = reqtrace.record_summary(recs[0], "gen")
    assert list(summ["phases"]) == ["queue", "prefill", "first", "decode",
                                    "resolve"]


def test_an_idle_engine_writes_one_idle_row_and_no_ticks(log):
    eng = _engine(slots=2)
    eng.warmup()
    _serve(eng, n=1)
    time.sleep(0.3)                         # six polls of the idle loop
    before = len(log.phase_log(prefix="gen.tick"))
    _serve(eng, n=1)
    eng.close()
    # one row for the whole wait, not one for each poll of it (a wait that
    # a submit already signalled, or close() ends, is a row of its own)
    idle = log.phase_log(prefix="gen.idle")
    assert len(idle) <= 3
    assert len([r for r in idle if r[2] - r[1] >= 0.25]) == 1
    ticks = log.phase_log(prefix="gen.tick")
    assert len(ticks) > before and all(r[5] > 0 for r in ticks)


def test_a_profiler_session_holds_the_engine_phases(log, tmp_path):
    """The same intervals on the profiler's clock: `/host:CPU` events named
    after the phases, entered on the engine's own thread."""
    import jax
    from jax.profiler import ProfileData
    eng = _engine()
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, n=4)
    finally:
        jax.profiler.stop_trace()
        eng.close()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    names = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    names[ev.name] = names.get(ev.name, 0) + 1
    for want in ("gen.tick", "gen.admit", "gen.prefill", "gen.join",
                 "gen.decode", "gen.sync", "gen.emit"):
        assert names.get(want, 0) >= 1, (want, sorted(names)[:40])
    steps = len(log.phase_log(prefix="gen.emit"))
    assert steps - 1 <= names["gen.emit"] <= steps
    assert names["gen.sync"] == names["gen.emit"]


# -- executables named by role -------------------------------------------

def _module_name(jitted, *args):
    text = jitted.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


def test_engine_executables_are_named_by_role():
    import jax
    eng = _engine(slots=2)
    from incubator_mxnet_tpu.telemetry import costs
    assert all(isinstance(f, costs.MeteredJit)
               for f in (eng._prefill, eng._join, eng._decode))
    eng._init_cache_arrays()
    dev = eng._ctx.jax_device
    src = jax.device_put(onp.full((1, 4), BOS, onp.int32), dev)
    vl = jax.device_put(onp.full((1,), 4, onp.int32), dev)
    assert _module_name(eng._prefill, eng._params, src, vl) == \
        "jit__traced_gen_prefill"
    row = eng._prefill(eng._params, src, vl)
    assert _module_name(eng._join, eng._cache, row,
                        jax.device_put(onp.array([0, 1], onp.int32),
                                       dev)) == \
        "jit__traced_gen_join"
    assert _module_name(eng._decode, eng._params, eng._cache) == \
        "jit__traced_gen_decode"
    eng.close()


def test_a_cost_label_does_not_rename_the_engine_executables(log):
    from incubator_mxnet_tpu.telemetry import costs
    mx.random.seed(0)
    net = transformer_nmt_small(V, V, dropout=0.0)
    net.initialize(force_reinit=True)
    eng = GenerationEngine(net, bos=BOS, eos=EOS, slots=2, max_len=16,
                           prompt_buckets=(4,),
                           cost_label="serve.gen:my-model/v2")
    eng.warmup()
    assert eng._join._jit.__wrapped__.__name__ == "_traced_gen_join"
    labels = {r["label"] for r in costs.table()}
    assert "serve.gen:my-model/v2:join[0]" in labels    # the registry's row
    # which executable compiled, by label: one compile.call row each
    called = [r[3] for r in log.phase_log(prefix="compile.call")]
    assert sorted(called) == ["serve.gen:my-model/v2:decode_step",
                              "serve.gen:my-model/v2:join",
                              "serve.gen:my-model/v2:prefill"]
    eng.close()


def test_label_slugs():
    from incubator_mxnet_tpu.telemetry.costs import traced_as
    f = lambda x: x      # noqa: E731
    assert traced_as(f, "gluon.train_step").__name__ == "_traced_gluon_train_step"
    assert traced_as(f, "sharded.zstep").__name__ == "_traced_sharded_zstep"
    assert traced_as(f, "serve.infer:m/1", "serve_infer").__name__ == \
        "_traced_serve_infer"
    assert traced_as(f, "a" * 80).__name__ == "_traced_" + "a" * 40
    assert traced_as(f, "x")(3) == 3


def test_fused_gluon_step_is_named_and_leaves_step_rows(monkeypatch, log):
    from incubator_mxnet_tpu.telemetry import costs
    seen = []
    orig = costs.metered_jit        # looked up where the step is built

    def spy(fn, **kw):
        out = orig(fn, **kw)
        if kw.get("label") == "gluon.train_step":
            seen.append(out)
        return out

    monkeypatch.setattr(costs, "metered_jit", spy)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = nd.array(onp.random.randn(4, 5).astype(onp.float32))
    y = nd.array(onp.array([0, 1, 2, 1], onp.int32))
    for _ in range(4):
        with autograd.record():
            loss = loss_fn(net(x), y)
            loss.backward()
        trainer.step(4)
    loss.asnumpy()
    steps = log.phase_log(prefix="gluon.step")
    assert [r[3] for r in steps] == [0, 1, 2, 3]
    assert seen, "the fused train step was never built"
    assert seen[-1]._jit.__wrapped__.__name__ == "_traced_gluon_train_step"
    assert "gluon.train_step" in {
        r[3] for r in log.phase_log(prefix="compile.call")}


def test_sharded_step_rows(log):
    import jax
    from incubator_mxnet_tpu.parallel import ShardedTrainer, make_mesh
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net(nd.array(onp.zeros((2, 5), onp.float32)))
    mesh = make_mesh((2,), ("data",), devices=jax.devices()[:2])
    tr = ShardedTrainer(net, optimizer="sgd", lr=0.1, mesh=mesh)
    x = onp.random.randn(4, 5).astype(onp.float32)
    y = onp.array([0, 1, 2, 1], onp.int32)
    for _ in range(3):
        loss = tr.step(x, y)
    jax.block_until_ready(loss)
    assert [r[3] for r in log.phase_log(prefix="sharded.step")] == [0, 1, 2]
    assert tr._step._jit.__wrapped__.__name__ == "_traced_sharded_step"
    assert [r[3] for r in log.phase_log(prefix="compile.call")] == \
        ["sharded.step"]
    tr.release()


# -- compile time ----------------------------------------------------------

def test_enable_puts_jax_compile_events_into_the_log(log, tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    from jax._src import monitoring
    before = len(monitoring.get_event_duration_listeners())
    compile_cache.enable()
    compile_cache.enable()                  # one listener, however often
    assert len(monitoring.get_event_duration_listeners()) - before <= 1

    def fresh_program(x):
        for k in range(40):                 # a trace of well over a millisecond
            x = jnp.tanh(x * 3.25 + 0.125 * k) @ x.T @ x
        return x.sum()

    t0 = time.monotonic()
    jax.jit(fresh_program)(jnp.ones((7, 3))).block_until_ready()
    rows = log.phase_log(since=t0 - 1.0, prefix="compile.jax.")
    kinds = {r[0] for r in rows}
    assert {"compile.jax.jaxpr_trace_duration",
            "compile.jax.jaxpr_to_mlir_module_duration",
            "compile.jax.backend_compile_duration"} <= kinds
    assert "compile.jax.compile_time_saved_sec" not in kinds
    # counts and membership only: how many nested traces cross the
    # millisecond is the loaded host's business
    assert "fresh_program" in {r[3] for r in rows}


def test_start_jax_trace_keeps_the_python_tracer_off(tmp_path, monkeypatch):
    import jax
    from incubator_mxnet_tpu import profiler
    seen = {}

    def fake(logdir, profiler_options=None, **kw):
        seen["dir"], seen["level"] = logdir, profiler_options.python_tracer_level

    monkeypatch.setattr(jax.profiler, "start_trace", fake)
    profiler.start_jax_trace(str(tmp_path))
    assert seen == {"dir": str(tmp_path), "level": 0}


def test_exporter_carries_the_totals(log):
    from incubator_mxnet_tpu import telemetry
    log.phase_at("t.export", 1.0, 1.5, None, None, 3)
    log.phase_at("t.export", 2.0, 2.25, None, None, 1)
    block = telemetry.MetricsExporter().json_dict()["phases"]
    assert block["t.export"] == [2, 0.75, 4]
