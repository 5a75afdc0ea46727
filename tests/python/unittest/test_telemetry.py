"""Unified telemetry layer (ISSUE 4): spans with cross-thread parent
propagation, the Prometheus/JSON export surface, per-step training
telemetry, compile observability, and the teletop renderer — all on
CPU, no network beyond loopback."""
import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, parallel, profiler, telemetry
from incubator_mxnet_tpu.monitor import EventCounters, events
from incubator_mxnet_tpu.telemetry import MetricsExporter, StepTelemetry

pytestmark = pytest.mark.telemetry


@pytest.fixture
def tele_on(tmp_path):
    """Telemetry enabled + profiler collecting into a tmp trace file;
    both restored afterwards (span recording needs both switches)."""
    prev = telemetry.enable(True)
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.set_state("run")
    yield
    profiler.set_state("stop")
    profiler.dumps(reset=True)
    telemetry.enable(prev)


def _dumped_spans(name_prefix=""):
    path = profiler.dump()
    with open(path) as f:
        evs = json.load(f)["traceEvents"]
    return [e for e in evs if e.get("cat") == "span"
            and e["name"].startswith(name_prefix)]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_disabled_is_noop():
    assert not telemetry.enabled()
    s = telemetry.span("never.recorded")
    with s:
        assert telemetry.current() is None
    # the disabled path hands back one shared object — no allocation
    assert telemetry.span("x") is telemetry.span("y")


def test_span_requires_profiler_too(tmp_path):
    """Enabled telemetry without a collecting profiler must not grow
    the (unbounded) chrome sink — but the span itself is real now
    (ISSUE 5): its completion lands in the bounded flight-recorder
    ring instead, so black-box dumps see spans on untraced runs."""
    from incubator_mxnet_tpu.telemetry import flightrec
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    prev = telemetry.enable(True)
    prev_bb = flightrec.enable(True)
    flightrec.clear()
    try:
        assert not telemetry.recording()    # chrome-sink gate closed
        with telemetry.span("tele.ringonly"):
            assert telemetry.current() is not None
        assert not _dumped_spans("tele.ringonly")   # sink untouched
        assert any(e["kind"] == "span" and e["name"] == "tele.ringonly"
                   for e in flightrec.ring_snapshot())
    finally:
        telemetry.enable(prev)
        flightrec.enable(prev_bb)
        flightrec.clear()


def test_span_parent_propagation_across_thread(tele_on):
    """The tentpole contract: a worker thread's span joins the
    submitting thread's trace via an explicitly handed SpanContext."""
    captured = {}

    def worker(parent_ctx):
        with telemetry.span("test.child", parent=parent_ctx):
            pass

    with telemetry.span("test.parent"):
        ctx = telemetry.current()
        captured["trace"], captured["span"] = ctx.trace_id, ctx.span_id
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
        # nesting on ONE thread parents implicitly
        with telemetry.span("test.inline"):
            pass

    spans = {e["name"]: e for e in _dumped_spans("test.")}
    assert set(spans) == {"test.parent", "test.child", "test.inline"}
    parent = spans["test.parent"]["args"]
    child = spans["test.child"]["args"]
    inline = spans["test.inline"]["args"]
    assert parent["trace_id"] == captured["trace"]
    assert "parent_id" not in parent            # trace root
    # cross-thread child: same trace, parented on the captured span
    assert child["trace_id"] == captured["trace"]
    assert child["parent_id"] == captured["span"]
    # same-thread nesting: implicit parent, same trace
    assert inline["trace_id"] == captured["trace"]
    assert inline["parent_id"] == captured["span"]
    # worker ran on a different thread id in the trace
    assert spans["test.child"]["tid"] != spans["test.parent"]["tid"]


def test_device_feed_spans_join_consumer_trace(tele_on):
    """DeviceFeed's worker read/transfer spans parent onto the
    consumer-side span open at feed start."""
    from incubator_mxnet_tpu.io.device_feed import DeviceFeed
    batches = [np.ones((2, 3), np.float32) for _ in range(3)]
    with telemetry.span("test.epoch"):
        ctx = telemetry.current()
        feed = DeviceFeed(lambda: iter(batches), ctx=mx.cpu())
        got = sum(1 for _ in feed)
    assert got == 3
    spans = _dumped_spans("feed.")
    reads = [e for e in spans if e["name"] == "feed.read"]
    xfers = [e for e in spans if e["name"] == "feed.transfer"]
    # 3 batch reads (+1 for the read that discovers end-of-epoch)
    assert len(xfers) == 3 and len(reads) >= 3
    for e in reads + xfers:
        assert e["args"]["trace_id"] == ctx.trace_id
        assert e["args"]["parent_id"] == ctx.span_id


def test_serving_dispatch_spans_join_submit_trace(tele_on):
    """submit→dispatch→infer crosses three threads; the dispatch and
    infer spans must share the submitter's trace id."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=8))
    net.initialize()
    net(nd.ones((1, 8)))
    eng = net.inference_engine(ctx=mx.cpu(), max_batch=4)
    try:
        with telemetry.span("test.submit"):
            ctx = telemetry.current()
            fut = eng.submit(np.ones(8, np.float32))
        fut.result(timeout=60)
    finally:
        eng.close()
    dispatch = [e for e in _dumped_spans("serve.dispatch")]
    infer = [e for e in _dumped_spans("serve.infer")]
    assert dispatch and infer
    assert dispatch[0]["args"]["trace_id"] == ctx.trace_id
    assert dispatch[0]["args"]["parent_id"] == ctx.span_id
    # serve.infer nests under serve.dispatch on the dispatcher thread
    assert infer[0]["args"]["trace_id"] == ctx.trace_id
    assert infer[0]["args"]["parent_id"] == \
        dispatch[0]["args"]["span_id"]


# ---------------------------------------------------------------------------
# EventCounters (satellites + race)
# ---------------------------------------------------------------------------

def test_event_counters_multithread_race():
    """N threads hammering incr/observe concurrently must lose no
    update (the ledger is the single source every exporter reads)."""
    c = EventCounters()
    n_threads, per = 8, 500

    def work(i):
        for k in range(per):
            c.incr("race.count")
            c.observe("race.lat_us", float(i * per + k))
            c.add_time("race.wall_us", 1e-6)

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.get("race.count") == n_threads * per
    assert c.get("race.lat_us.n") == n_threads * per
    assert c.get("race.wall_us") == n_threads * per
    p = c.percentiles("race.lat_us")
    assert p["n"] == min(EventCounters.MAX_SAMPLES, n_threads * per)


def test_log_nonzero_includes_percentiles(caplog):
    import logging
    c = EventCounters()
    c.incr("x.count", 7)
    for v in (100.0, 200.0, 300.0):
        c.observe("x.lat_us", v)
    with caplog.at_level(logging.INFO):
        c.log_nonzero(logging.getLogger("tele-test"))
    text = caplog.text
    assert "x.count" in text and "7" in text
    assert "p50=200" in text and "p99=300" in text and "n=3" in text


# ---------------------------------------------------------------------------
# export: Prometheus text / JSON / file / HTTP
# ---------------------------------------------------------------------------

def test_prometheus_text_golden():
    c = EventCounters()
    c.incr("serve.requests", 5)
    c.observe_time("serve.e2e_us", 100e-6)
    c.observe_time("serve.e2e_us", 200e-6)
    exp = MetricsExporter(c)
    assert exp.prometheus_text() == (
        '# TYPE mxnet_serve_e2e_us summary\n'
        'mxnet_serve_e2e_us{quantile="0.5"} 100\n'
        'mxnet_serve_e2e_us{quantile="0.9"} 200\n'
        'mxnet_serve_e2e_us{quantile="0.99"} 200\n'
        'mxnet_serve_e2e_us_sum 300\n'
        'mxnet_serve_e2e_us_count 2\n'
        '# TYPE mxnet_serve_requests counter\n'
        'mxnet_serve_requests 5\n')


def test_prometheus_renders_every_family():
    """The acceptance contract: every nonzero serve./feed./train./
    resilience./mesh. counter appears, and every observed _us series
    gets quantile lines."""
    c = EventCounters()
    names = ("serve.batches", "feed.batches", "train.steps",
             "resilience.checkpoint_written", "mesh.straggler")
    for n in names:
        c.incr(n, 3)
    for n in ("serve.e2e_us", "feed.transfer_us", "train.step_us",
              "mesh.rebuild_us"):
        c.observe_time(n, 1e-3)
    text = MetricsExporter(c).prometheus_text()
    for n in names:
        assert "mxnet_%s 3" % n.replace(".", "_") in text
    for n in ("serve_e2e_us", "feed_transfer_us", "train_step_us",
              "mesh_rebuild_us"):
        assert '# TYPE mxnet_%s summary' % n in text
        assert 'mxnet_%s{quantile="0.5"}' % n in text
        assert 'mxnet_%s{quantile="0.99"}' % n in text
        assert 'mxnet_%s_count 1' % n in text
    # sample-ring companion counters fold into the summary, never
    # leak as bare counters
    assert "_us_n " not in text and ".n" not in text


def test_observe_only_series_has_no_sum():
    """observe() without observe_time (e.g. train.loss) has no total
    counter — the summary renders quantiles + count, no _sum."""
    c = EventCounters()
    c.observe("train.loss", 2.5)
    text = MetricsExporter(c).prometheus_text()
    assert 'mxnet_train_loss{quantile="0.5"} 2.5' in text
    assert "mxnet_train_loss_count 1" in text
    assert "mxnet_train_loss_sum" not in text


def test_exporter_file_roundtrip(tmp_path):
    c = EventCounters()
    c.incr("serve.requests", 9)
    c.observe_time("serve.e2e_us", 5e-4)
    exp = MetricsExporter(c)
    # JSON round trip
    jpath = str(tmp_path / "snap.json")
    exp.export_file(jpath)
    snap = json.load(open(jpath))
    assert snap["counters"]["serve.requests"] == 9
    assert snap["percentiles"]["serve.e2e_us"]["p50"] == 500
    # .prom suffix → text format
    ppath = str(tmp_path / "snap.prom")
    exp.export_file(ppath)
    assert "mxnet_serve_requests 9" in open(ppath).read()


def test_exporter_periodic_file(tmp_path):
    c = EventCounters()
    c.incr("feed.batches", 2)
    path = str(tmp_path / "periodic.json")
    exp = MetricsExporter(c).start(path=path, period_s=0.05)
    import time as _time
    deadline = _time.monotonic() + 5.0
    import os
    while not os.path.exists(path) and _time.monotonic() < deadline:
        _time.sleep(0.02)
    exp.close()
    snap = json.load(open(path))        # close() writes a final one
    assert snap["counters"]["feed.batches"] == 2


def test_exporter_restart_after_close(tmp_path):
    """close() retires the periodic worker via a stop Event; a later
    start() must get a fresh one — not a dead thread that never
    exports."""
    import os
    import time as _time
    c = EventCounters()
    c.incr("feed.batches")
    path = str(tmp_path / "restart.json")
    exp = MetricsExporter(c)
    exp.start(path=path, period_s=0.05)
    exp.close()
    os.remove(path)                     # drop close()'s final snapshot
    c.incr("feed.batches")
    exp.start(path=path, period_s=0.05)
    deadline = _time.monotonic() + 5.0
    while not os.path.exists(path) and _time.monotonic() < deadline:
        _time.sleep(0.02)
    exp.close()
    assert json.load(open(path))["counters"]["feed.batches"] == 2


def test_prometheus_empty_percentile_dict_is_safe():
    """A reset() racing a scrape can yield an empty percentile dict for
    a name; the render must fall back to the plain counter, not 500."""
    c = EventCounters()
    c.incr("x.lat_us", 300)             # counter exists...
    exp = MetricsExporter(c)
    orig = c.latency_snapshot
    c.latency_snapshot = lambda **kw: {"x.lat_us": {}}   # ...samples gone
    try:
        text = exp.prometheus_text()
    finally:
        c.latency_snapshot = orig
    assert "# TYPE mxnet_x_lat_us counter" in text
    assert "mxnet_x_lat_us 300" in text


def test_metrics_endpoint_smoke():
    c = EventCounters()
    c.incr("serve.requests", 4)
    c.observe_time("serve.e2e_us", 1e-4)
    exp = MetricsExporter(c)
    port = exp.serve_http(port=0)
    base = "http://127.0.0.1:%d" % port
    r = urllib.request.urlopen(base + "/metrics", timeout=10)
    body = r.read().decode()
    assert r.status == 200
    assert r.headers["Content-Type"].startswith("text/plain")
    assert "mxnet_serve_requests 4" in body
    assert 'mxnet_serve_e2e_us{quantile="0.99"}' in body
    h = json.loads(urllib.request.urlopen(
        base + "/healthz", timeout=10).read().decode())
    assert h["status"] == "ok"
    j = json.loads(urllib.request.urlopen(
        base + "/metrics.json", timeout=10).read().decode())
    assert j["counters"]["serve.requests"] == 4
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(base + "/nope", timeout=10)
    exp.close()
    with pytest.raises(Exception):
        urllib.request.urlopen(base + "/healthz", timeout=2)


def test_module_start_stop(tmp_path):
    prev = telemetry.enable(False)
    try:
        exp = telemetry.start(port=0)
        assert telemetry.enabled()      # start() switches the flag on
        assert telemetry.get_exporter() is exp
        port = exp.http_port
        assert urllib.request.urlopen(
            "http://127.0.0.1:%d/healthz" % port, timeout=10).status \
            == 200
        telemetry.stop()
        assert telemetry.get_exporter() is None
    finally:
        telemetry.enable(prev)
        telemetry.stop()


# ---------------------------------------------------------------------------
# per-step training telemetry
# ---------------------------------------------------------------------------

def _small_trainer(seed=11):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential(prefix="tz_")
    net.add(gluon.nn.Dense(16, in_units=8, activation="relu",
                           prefix="tz_d1_"),
            gluon.nn.Dense(4, in_units=16, prefix="tz_d2_"))
    net.initialize(force_reinit=True)
    net(nd.ones((2, 8)))
    return parallel.ShardedTrainer(net, optimizer="sgd", lr=1e-2)


def test_step_telemetry_resilient_trainer(tmp_path):
    prev = telemetry.enable(True)
    try:
        rt = parallel.ResilientTrainer(
            _small_trainer(), ckpt_dir=str(tmp_path / "ck"),
            ckpt_interval=0, seed=5, handle_sigterm=False)
        rs = np.random.RandomState(0)
        before = events.snapshot("train.")
        for _ in range(3):
            rt.step(rs.randn(8, 8).astype(np.float32),
                    rs.randint(0, 4, 8))
        after = events.snapshot("train.")
        d = lambda k: after.get(k, 0) - before.get(k, 0)
        assert d("train.steps") == 3
        assert d("train.step_us") > 0
        assert d("train.data_wait_us") >= 0
        assert d("train.compute_us") > 0
        assert d("train.loss.n") == 3
        assert events.percentiles("train.step_us")["n"] >= 3
        assert events.percentiles("train.loss")["n"] >= 3
        # the guarded step traced at least once under this wiring
        assert events.get("train.traces") >= 1
        # checkpoint duration lands as a train.* sample
        ck0 = events.get("train.checkpoint_us.n")
        rt.checkpoint()
        assert events.get("train.checkpoint_us.n") == ck0 + 1
    finally:
        telemetry.enable(prev)


def test_step_telemetry_sharded_trainer_async():
    prev = telemetry.enable(True)
    try:
        t = _small_trainer(seed=12)
        rs = np.random.RandomState(1)
        before = events.snapshot("train.")
        for _ in range(2):
            t.step(rs.randn(8, 8).astype(np.float32),
                   rs.randint(0, 4, 8))
        after = events.snapshot("train.")
        d = lambda k: after.get(k, 0) - before.get(k, 0)
        assert d("train.steps") == 2
        assert d("train.dispatch_us") > 0
        # async contract: no host sync, so no compute/loss samples
        assert d("train.compute_us") == 0
        assert d("train.loss.n") == 0
        # first step traced the executable → counted as compiling
        assert d("train.steps_compiling") >= 1
    finally:
        telemetry.enable(prev)


def test_step_telemetry_disabled_records_nothing():
    assert not telemetry.enabled()
    t = _small_trainer(seed=13)
    before = events.get("train.steps")
    rs = np.random.RandomState(2)
    t.step(rs.randn(8, 8).astype(np.float32), rs.randint(0, 4, 8))
    assert events.get("train.steps") == before
    assert t._tele is None


# ---------------------------------------------------------------------------
# the condensed snapshot
# ---------------------------------------------------------------------------

def test_snapshot_keeps_its_families_and_none_of_the_disk_cache():
    """`snapshot_dict()` (what bench.py embeds) carries the serve./
    feed./train. families and no `aot.*` key, whatever else counted."""
    events.incr("serve.batches", 2)
    events.incr("feed.batches", 3)
    events.incr("train.steps", 4)
    events.incr("aot.hit")              # a name nothing counts any more
    events.observe_time("serve.e2e_us", 1e-3)
    snap = telemetry.snapshot_dict()
    for fam in ("serve.", "feed.", "train."):
        assert any(k.startswith(fam) for k in snap["counters"]), fam
    assert "serve.e2e_us" in snap["percentiles"]
    keys = list(snap["counters"]) + list(snap["percentiles"])
    assert not [k for k in keys if k.startswith("aot")]
    assert not [p for p in telemetry.SNAPSHOT_PREFIXES
                if p.startswith("aot")]


# ---------------------------------------------------------------------------
# teletop
# ---------------------------------------------------------------------------

def test_teletop_render_and_file(tmp_path, capsys):
    from incubator_mxnet_tpu.tools import teletop
    c = EventCounters()
    c.incr("serve.batch_fill", 30)
    c.incr("serve.pad_waste", 10)
    c.incr("feed.stall_us", 3)
    c.incr("feed.step_us", 1)
    c.observe_time("serve.e2e_us", 2e-3)
    snap = MetricsExporter(c).json_dict()
    out = teletop.render(snap)
    assert "serve.batch_fill" in out and "30" in out
    assert "serve.e2e_us" in out and "p99" in out
    assert "serve batch fill" in out and "75.0%" in out
    assert "feed stall fraction" in out and "aot cache" not in out
    # --prefix filters the tables
    assert "feed.stall_us" not in teletop.render(snap, prefix="serve.")
    # file mode end-to-end through main()
    path = str(tmp_path / "snap.json")
    MetricsExporter(c).export_file(path)
    assert teletop.main(["--file", path]) == 0
    assert "serve.batch_fill" in capsys.readouterr().out


def test_teletop_reads_bench_telemetry_block(tmp_path, capsys):
    """BENCH_r*/BENCH_serve blobs double as teletop fixtures via their
    nested `telemetry` block."""
    from incubator_mxnet_tpu.tools import teletop
    blob = {"n": 6, "cmd": "python bench.py serve", "rc": 0,
            "parsed": {"telemetry": {
                "counters": {"serve.requests": 12},
                "percentiles": {"serve.e2e_us":
                                {"n": 12, "p50": 90.0, "p99": 400.0}}}}}
    path = str(tmp_path / "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(blob, f)
    assert teletop.main(["--file", path]) == 0
    out = capsys.readouterr().out
    assert "serve.requests" in out and "serve.e2e_us" in out
