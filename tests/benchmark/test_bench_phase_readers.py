"""The readers of the program's phase log (PR 27), each by hand on rows
written at made-up times and a made-up record; the cases in which a reader
finds nothing to read; a device's idle gap named by an engine phase; and the
rows that a rehearsed cell leaves behind for them."""
import json
import os
import time

import pytest

from benchpaths import BENCH, DATA, load

from incubator_mxnet_tpu.telemetry import spans

ROOT = os.path.join(DATA, "root")


@pytest.fixture
def log():
    spans._LOG.clear()
    yield spans
    spans._LOG.clear()


def _spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


def _read(metric, record, result=None):
    spec = _spec(metric)
    return load(spec["reader"], "readers").read(spec, record, result)


def _ticks(log):
    """Three ticks of 100 ms: the device is waited for during 60, 80 and
    90 ms of them.  The first starts before the window, and its sync with it."""
    for k, (t0, wait) in enumerate([(999.95, 0.06), (1000.0, 0.08),
                                    (1000.2, 0.09)]):
        log.phase_at("gen.tick", t0, t0 + 0.1, k, None, 4)
        log.phase_at("gen.sync", t0 + 0.01, t0 + 0.01 + wait, 7, k)
        log.phase_at("gen.emit", t0 + 0.095, t0 + 0.1, 7, k, 4)


@pytest.mark.parametrize("cell", ["online", "backlog"])
def test_host_busy_share_by_hand(log, cell):
    _ticks(log)
    record = {"t_open": 1000.0, "t_close": 1051.0}
    # ticks 1 and 2: 200 ms, of which 170 ms waiting for the device
    assert _read("engine.host_busy_share." + cell, record) == pytest.approx(15.0)
    assert _read("engine.host_busy_share." + cell,
                 {"t_open": 2000.0, "t_close": 2051.0}) is None
    assert _read("engine.host_busy_share." + cell, {"kind": "train"}) is None


def test_queue_wait_quantile_by_hand(log):
    for k, wait in enumerate([0.010, 0.020, 0.030, 0.500]):
        log.phase_at("gen.req.queue", 1001.0 + k, 1001.0 + k + wait, k, 1)
    log.phase_at("gen.req.queue", 990.0, 999.0, 9, 1)       # the lead-in's
    log.phase_at("gen.req.queue.other", 1002.0, 1009.0)
    record = {"t_open": 1000.0, "t_close": 1051.0}
    assert _read("engine.queue_wait_p50_ms.online", record) == pytest.approx(25.0)
    assert _read("engine.queue_wait_p50_ms.online",
                 {"t_open": 1100.0, "t_close": 1151.0}) is None
    assert _read("engine.queue_wait_p50_ms.online", {}) is None


def test_step_host_ms_takes_the_newest_steps(log):
    for k, ms in enumerate([900.0, 800.0, 3.0, 5.0, 4.0]):   # two compile
        log.phase_at("gluon.step", 10.0 + k, 10.0 + k + ms * 1e-3, k)
    assert _read("modelstep.step_host_ms", {"steps": 3}) == pytest.approx(4.0)
    assert _read("modelstep.step_host_ms", {"steps": 0}) is None
    assert _read("modelstep.step_host_ms", {"kind": "serve"}) is None
    log._LOG.clear()
    assert _read("modelstep.step_host_ms", {"steps": 3}) is None


def test_setup_compile_seconds_is_a_union_before_the_window(log):
    log.phase_at("compile.jax.jaxpr_trace_duration", 10.0, 11.0, "f")
    log.phase_at("compile.jax.jaxpr_trace_duration", 10.2, 10.4, "inner")
    log.phase_at("compile.jax.backend_compile_duration", 12.0, 15.0, "f")
    log.phase_at("compile.jax.cache_retrieval_time_sec", 12.5, 14.5)
    log.phase_at("compile.call", 10.0, 16.0, "gluon.train_step")
    log.phase_at("compile.jax.backend_compile_duration", 70.0, 72.0, "ref")
    # a serving run: the driver records when the window opened
    assert _read("compile.setup_compile_s",
                 {"t_open": 20.0, "t_close": 71.0}) == pytest.approx(4.0)
    # a compile that straddles the opening is not set-up's
    assert _read("compile.setup_compile_s", {"t_open": 14.0}) == pytest.approx(1.0)
    # a training run: the window opens with the oldest of its steps
    for k in range(5):
        log.phase_at("gluon.step", 16.0 + k, 16.1 + k, k)
    assert _read("compile.setup_compile_s", {"steps": 3}) == pytest.approx(4.0)
    assert _read("compile.setup_compile_s", {"steps": 5}) == pytest.approx(4.0)
    assert _read("compile.setup_compile_s", {}) is None
    assert _read("compile.setup_compile_s", {"t_open": 5.0}) is None


def test_readers_find_nothing_in_a_program_without_the_log(log, monkeypatch):
    """The parent of PR 27 has no `phase_log`: the metric is left out."""
    _ticks(log)
    log.phase_at("compile.jax.backend_compile_duration", 1.0, 2.0)
    log.phase_at("gluon.step", 3.0, 4.0, 0)
    monkeypatch.delattr(spans, "phase_log")
    record = {"t_open": 1000.0, "t_close": 1051.0, "steps": 1}
    for metric in ("engine.host_busy_share.online",
                   "engine.queue_wait_p50_ms.online",
                   "modelstep.step_host_ms", "compile.setup_compile_s"):
        assert _read(metric, record) is None, metric


def _planes(join="jit__traced_gen_join(11)", prefill="jit__traced_gen_prefill(12)",
            decode="jit__traced_gen_decode(13)"):
    ms = 1e6        # ns
    dev = {"XLA Modules": [(prefill, 0 * ms, 2 * ms), (join, 2 * ms, 6 * ms),
                           (decode, 10 * ms, 10 * ms), (join, 30 * ms, 10 * ms)],
           "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 0 * ms, 2 * ms),
                       ("%select.2 = f32[8] select(...)", 2 * ms, 6 * ms),
                       ("%fusion.3 = f32[8] fusion(...)", 10 * ms, 10 * ms),
                       ("%select.2 = f32[8] select(...)", 30 * ms, 10 * ms)]}
    host = {"GenDecodeLoop": [("gen.tick", 1 * ms, 38 * ms),
                              ("gen.sync", 9 * ms, 11.5 * ms),
                              ("gen.emit", 20.5 * ms, 9 * ms),
                              ("np.asarray(jax.Array)", 9.5 * ms, 10 * ms)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_an_idle_gap_is_named_by_the_engine_phase_over_it():
    tr = load("trace_reduce")
    red = tr.reduce_planes(_planes())
    # the middle of idle [8,10] lies in gen.sync and in the longer gen.tick,
    # that of idle [20,30] in gen.emit, np.asarray and gen.tick: each gap is
    # named by the innermost event
    assert dict(map(tuple, red["idle_gaps"])) == pytest.approx(
        {"gen.emit": 0.010, "gen.sync": 0.002})
    assert red["device_ops"][0][0] == "module jit__traced_gen_join(11)"


@pytest.mark.parametrize("cell", ["online", "backlog"])
def test_admit_device_share_finds_modules_by_name(cell):
    tr = load("trace_reduce")
    red = tr.reduce_planes(_planes())
    # prefill 2 + join 16 of 28 ms busy
    got = _read("engine.admit_device_share." + cell, {"trace": red})
    assert got == pytest.approx(100.0 * 18 / 28)
    share = load("module_share", "readers")
    join = share.read({"role": "join"}, {"trace": red, "roles": {
        "join": "jit__traced_gen_join(11)"}}, None)
    assert got >= join == pytest.approx(100.0 * 16 / 28)
    # a parent's names say nothing: nothing to read, and no trace, neither
    old = tr.reduce_planes(_planes("jit__traced(11)", "jit__traced(12)",
                                   "jit__traced(13)"))
    assert _read("engine.admit_device_share." + cell, {"trace": old}) is None
    assert _read("engine.admit_device_share." + cell, {"trace": None}) is None


def test_new_metrics_are_declared_with_the_layer_of_their_prefix(bench_json):
    new = {"engine.host_busy_share.online", "engine.host_busy_share.backlog",
           "engine.queue_wait_p50_ms.online", "engine.admit_device_share.online",
           "engine.admit_device_share.backlog", "modelstep.step_host_ms",
           "compile.setup_compile_s"}
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    assert new <= set(entries)
    assert [m["name"] for m in bench_json["per_layer"]][-len(new):] == [
        "engine.host_busy_share.online", "engine.host_busy_share.backlog",
        "engine.queue_wait_p50_ms.online", "engine.admit_device_share.online",
        "engine.admit_device_share.backlog", "modelstep.step_host_ms",
        "compile.setup_compile_s"]
    for name in new:
        m = entries[name]
        assert m["source"] == ("device_trace" if "device_share" in name
                               else "program_span")
        assert m["better"] == "lower"
    assert entries["compile.setup_compile_s"]["workloads"] == [
        w["name"] for w in bench_json["workloads"]]


def test_a_rehearsed_serving_cell_leaves_rows_for_every_reader(run_cell, log):
    t_open = time.monotonic()
    line, _ = run_cell(ROOT, "nmt_tiny.online_tiny", "--trace", "0")
    t_close = time.monotonic()
    assert line["correct"] is True
    names = {r[0] for r in log.phase_log(since=t_open)}
    assert {"gen.tick", "gen.admit", "gen.prefill", "gen.join", "gen.decode",
            "gen.sync", "gen.emit", "gen.req.queue", "gen.req.admit",
            "gen.req.first"} <= names
    assert any(n.startswith("compile.jax.") for n in names)
    record = {"t_open": t_open, "t_close": t_close}
    assert 0.0 < _read("engine.host_busy_share.online", record) < 100.0
    assert _read("engine.queue_wait_p50_ms.online", record) >= 0.0
    # everything compiled before `t_close` is set-up to a window opening there
    assert _read("compile.setup_compile_s", {"t_open": t_close}) > 0.0


def test_a_rehearsed_training_cell_leaves_step_rows(run_cell, log):
    line, _ = run_cell(ROOT, "bert_tiny.pretrain_tiny", "--trace", "0")
    assert line["correct"] is True
    steps = [r for r in log.phase_log(prefix="gluon.step")]
    assert len(steps) >= 6 and [r[3] for r in steps] == list(range(len(steps)))
    assert _read("modelstep.step_host_ms", {"steps": 3}) > 0.0
    assert _read("compile.setup_compile_s", {"steps": 3}) > 0.0
