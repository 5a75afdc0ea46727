"""Fleet observability (ISSUE 11): cross-process trace propagation
(TraceContext, global-step stamping, foreign spans, kvstore op spans),
kvstore-aggregated per-replica telemetry (FleetReporter/FleetView),
telemetry-driven straggler detection feeding ElasticTrainer's
slow-(observed) state, the blackbox fleet block + merge CLI, and the
ISSUE 11 satellites (bench_diff, gate reports).
All CPU, tier-1 fast."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, parallel, telemetry
from incubator_mxnet_tpu import config as mxcfg
from incubator_mxnet_tpu.kvstore import create as kv_create
from incubator_mxnet_tpu.monitor import EventCounters, events
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.telemetry import (FleetTelemetry, FleetView,
                                           StragglerDetector, fleet,
                                           flightrec)

pytestmark = pytest.mark.fleet


@pytest.fixture
def tele_ring():
    """Telemetry + a fresh flight-recorder ring, both restored."""
    prev = telemetry.enable(True)
    prev_bb = flightrec.enable(True)
    flightrec.configure(1024)
    flightrec.clear()
    telemetry.set_global_step(None)
    yield
    telemetry.set_global_step(None)
    telemetry.enable(prev)
    flightrec.enable(prev_bb)
    flightrec.clear()


def _ring_spans(name=None):
    return [e for e in flightrec.ring_snapshot()
            if e["kind"] == "span"
            and (name is None or e["name"] == name)]


# ---------------------------------------------------------------------------
# trace propagation
# ---------------------------------------------------------------------------

def test_trace_context_wire_roundtrip(tele_ring):
    telemetry.set_global_step(17)
    with telemetry.span("outer"):
        tc = telemetry.propagate()
        assert tc is not None and tc.step == 17
        wire = tc.to_wire()
    # the wire form is primitives only — queue/JSON-safe
    assert json.loads(json.dumps(wire)) == list(wire)
    tc2 = telemetry.TraceContext.from_wire(wire)
    assert (tc2.trace_id, tc2.span_id, tc2.step) == \
        (tc.trace_id, tc.span_id, 17)
    # a rebuilt context is a valid cross-process parent
    with telemetry.span("far.side", parent=tc2):
        pass
    child = _ring_spans("far.side")[-1]
    assert child["trace"] == tc.trace_id
    assert child["parent"] == tc.span_id
    assert telemetry.TraceContext.from_wire(None) is None


def test_propagate_without_open_span_carries_step(tele_ring):
    telemetry.set_global_step(9)
    tc = telemetry.propagate()
    assert tc is not None and tc.step == 9
    telemetry.set_global_step(None)
    assert telemetry.propagate() is None


def test_span_tags_and_global_step_stamp(tele_ring):
    telemetry.set_global_step(123)
    with telemetry.span("kv.test", gen=4, rank=2):
        pass
    ev = _ring_spans("kv.test")[-1]
    assert ev["gen"] == 4 and ev["rank"] == 2 and ev["step"] == 123
    telemetry.set_global_step(None)
    with telemetry.span("kv.test2"):
        pass
    assert "step" not in _ring_spans("kv.test2")[-1]


def test_emit_foreign_pid_parent_and_chrome_row(tele_ring, tmp_path):
    telemetry.set_global_step(55)
    with telemetry.span("consumer") as _:
        parent = telemetry.current()
        ctx = telemetry.emit_foreign("io.decode", time.time() - 0.005,
                                     0.005, pid=424242, wid=1)
    assert ctx is not None
    ev = _ring_spans("io.decode")[-1]
    assert ev["pid"] == 424242 and ev["step"] == 55
    assert ev["parent"] == parent.span_id
    assert ev["trace"] == parent.trace_id
    # the dump's chrome view renders the foreign span in the FOREIGN
    # process's row
    dump = flightrec.dump_blackbox(path=str(tmp_path / "d.json"),
                                   reason="test")
    with open(dump) as f:
        doc = json.load(f)
    rows = [e for e in doc["trace"]["traceEvents"]
            if e["name"] == "span:io.decode"]
    assert rows and rows[-1]["pid"] == 424242
    own = [e for e in doc["trace"]["traceEvents"]
           if e["name"] == "span:consumer"]
    assert own and own[-1]["pid"] == os.getpid()


def test_emit_foreign_disabled_is_none():
    prev = telemetry.enable(False)
    try:
        assert telemetry.emit_foreign("x", time.time(), 0.1) is None
    finally:
        telemetry.enable(prev)


def test_kvstore_ops_spans_tagged_gen_rank(tele_ring):
    kv = kv_create("local")
    kv.init("w", NDArray(np.zeros(4, np.float32)))
    kv.push("w", NDArray(np.ones(4, np.float32)))
    out = NDArray(np.zeros(4, np.float32))
    kv.pull("w", out=out)
    kv._barrier()
    kv.advance_generation("test")
    kv.push("w", NDArray(np.ones(4, np.float32)))
    names = {e["name"] for e in _ring_spans()}
    assert {"kv.push", "kv.pull", "kv.barrier"} <= names
    pushes = _ring_spans("kv.push")
    assert pushes[0]["gen"] == 0 and pushes[0]["rank"] == 0
    assert pushes[-1]["gen"] == 1    # post-advance push carries new gen


# ---------------------------------------------------------------------------
# fleet aggregation
# ---------------------------------------------------------------------------

def test_fleet_reporter_view_roundtrip():
    kv = kv_create("local")
    view = FleetView(kv)
    for rid in range(3):
        rep = fleet.FleetReporter(kv, rid)
        rep.publish({"step": 7, "step_us": 1000.0 * (rid + 1),
                     "dispatch_us": 10 * rid, "steps_skipped": rid})
    merged = view.refresh(range(4))     # rid 3 never published
    assert sorted(merged) == [0, 1, 2]
    assert merged[1]["step_us"] == 2000.0
    assert merged[2]["steps_skipped"] == 2
    assert merged[0]["step"] == 7
    # re-publish replaces (the kvstore push-replace contract)
    fleet.FleetReporter(kv, 1).publish({"step": 8, "step_us": 5.0})
    assert view.refresh([1])[1]["step_us"] == 5.0


def test_published_row_carries_its_families_and_no_disk_cache_field():
    """The wire schema after the serialized-executable cache went: the
    step, train and feed fields, and nothing named `aot*`."""
    kv = kv_create("local")
    sample = dict(fleet._counter_sample(), step=3, step_us=1500.0)
    fleet.FleetReporter(kv, 0).publish(sample)
    (row,) = FleetView(kv).refresh([0]).values()
    assert set(sample) <= set(row)
    assert not [k for k in row if k.startswith("aot")]
    for field in ("step", "step_us", "dispatch_us", "collective_us",
                  "data_wait_us", "hbm_peak_bytes", "steps_skipped",
                  "feed_stall_us", "decode_batches"):
        assert field in row
    assert row["step"] == 3.0 and row["step_us"] == 1500.0


def test_straggler_detector_flags_and_recovers(tele_ring):
    det = StragglerDetector(window=3, sigma=4.0)
    base = events.get("mesh.straggler")
    # warm: uniform fleet — MAD 0, the +50% floor keeps it quiet
    for s in range(3):
        assert det.observe(s, {r: 1000.0 for r in range(4)}) == []
    # replica 2 goes 4x slow
    flagged = []
    for s in range(3, 8):
        per = {r: (4000.0 if r == 2 else 1000.0) for r in range(4)}
        flagged = det.observe(s, per)
    assert flagged == [2]
    assert events.get("mesh.straggler") == base + 1   # transition once
    evs = [e for e in flightrec.ring_snapshot()
           if e["kind"] == "mesh" and e["name"] == "straggler"]
    assert evs and evs[-1]["replica"] == 2
    assert evs[-1]["step_us"] > evs[-1]["fleet_median_us"]
    # recovery: back to fleet speed -> recovered transition, unflagged
    for s in range(8, 14):
        flagged = det.observe(s, {r: 1000.0 for r in range(4)})
    assert flagged == []
    assert any(e["kind"] == "mesh"
               and e["name"] == "straggler_recovered"
               and e["replica"] == 2
               for e in flightrec.ring_snapshot())
    # labeled counter split names the replica
    labeled = events.labeled_snapshot().get("mesh.straggler", [])
    assert any(r["labels"].get("replica") == "2" for r in labeled)


def test_straggler_needs_a_fleet():
    det = StragglerDetector(window=2, sigma=4.0)
    # one replica: no fleet to compare against, never flags
    for s in range(6):
        assert det.observe(s, {0: 1000.0 * (s + 1)}) == []


def test_fleet_telemetry_update_and_block(tele_ring):
    kv = kv_create("local")
    ft = FleetTelemetry(kv, 4, window=2, sigma=4.0, publish_steps=1)
    strag = []
    for s in range(6):
        per = {r: (8000.0 if (r == 3 and s >= 2) else 2000.0)
               for r in range(4)}
        strag = ft.update(s, per)
    assert strag == [3]
    block = ft.block()
    assert block["stragglers"] == [3]
    assert set(block["replicas"]) == {"0", "1", "2", "3"}
    row = block["replicas"]["3"]
    for field in ("step", "step_us", "dispatch_us", "collective_us",
                  "hbm_peak_bytes", "steps_skipped"):
        assert field in row
    # the dump embeds the same block through the provider hook
    assert flightrec.fleet_block()["stragglers"] == [3]
    # replica-labeled Prometheus children exist for fleet.step_us
    text = telemetry.MetricsExporter().prometheus_text()
    assert 'mxnet_fleet_step_us{replica="3"' in text


def test_fleet_publish_cadence_and_disable():
    kv = kv_create("local")
    ft = FleetTelemetry(kv, 2, window=2, publish_steps=0)
    assert ft.update(0, {0: 1.0, 1: 1.0}) == []
    assert ft.view.last == {}           # publishing disabled: no push
    ft2 = FleetTelemetry(kv, 2, window=2, publish_steps=3)
    ft2.update(1, {0: 1.0, 1: 1.0})     # off-cadence: no publish
    assert ft2.view.last == {}
    ft2.update(3, {0: 1.0, 1: 1.0})     # on-cadence
    assert sorted(ft2.view.last) == [0, 1]


# ---------------------------------------------------------------------------
# straggler -> elastic slow-(observed) state
# ---------------------------------------------------------------------------

def test_observed_slow_feeds_replica_health(tele_ring):
    kv = kv_create("local")
    health = parallel.elastic.ReplicaHealth(kv, 3, stale_steps=50,
                                            down_steps=100)
    for rid in range(3):
        health.beat(rid, 5)
    base = events.get("mesh.replica_slow")
    health.note_observed_slow(1, 5)
    assert events.get("mesh.replica_slow") == base + 1
    # beats are FRESH, yet the verdict is slow — and sticky
    verdict = health.poll(6, [0, 1, 2])
    assert verdict == {0: "healthy", 1: "slow", 2: "healthy"}
    health.note_observed_slow(1, 7)     # re-noting: no double count
    assert events.get("mesh.replica_slow") == base + 1
    health.clear_observed_slow(1)
    for rid in range(3):
        health.beat(rid, 8)
    assert health.poll(8, [0, 1, 2])[1] == "healthy"
    ev = [e for e in flightrec.ring_snapshot()
          if e["kind"] == "mesh" and e["name"] == "replica_slow"]
    assert ev and ev[-1]["replica"] == 1
    assert ev[-1]["source"] == "straggler"


def test_elastic_trainer_detects_alive_but_slow(tele_ring, tmp_path):
    """End-to-end: mesh.replica_slow injected -> the victim's PUBLISHED
    step times skew -> mesh.straggler names it and the health state
    goes slow (observed) — all while its heartbeats would still pass
    staleness, and without any shrink."""
    import jax
    from incubator_mxnet_tpu import fault
    devices = jax.devices()[:2]
    in_dim, classes, batch = 16, 4, 8

    def build(mesh, lr_factor):
        mx.random.seed(3)
        net = gluon.nn.HybridSequential(prefix="tf_")
        net.add(gluon.nn.Dense(16, in_units=in_dim, activation="relu",
                               prefix="tf_d1_"),
                gluon.nn.Dense(classes, in_units=16, prefix="tf_d2_"))
        net.initialize(force_reinit=True)
        net(nd.ones((2, in_dim)))
        return parallel.ShardedTrainer(net, optimizer="sgd",
                                       lr=1e-2 * lr_factor, mesh=mesh)

    def data_fn(step, n_replicas):
        rs = np.random.RandomState(100 + step)
        return (rs.randn(batch, in_dim).astype(np.float32),
                rs.randint(0, classes, batch))

    mxcfg.set("MXNET_STRAGGLER_WINDOW", "2")
    mxcfg.set("MXNET_FAULT_PLAN", "mesh.replica_slow@2")
    fault.reset_from_config()
    base = events.get("mesh.straggler")
    try:
        et = parallel.ElasticTrainer(
            build, ckpt_dir=str(tmp_path / "ck"), devices=devices,
            ckpt_interval=3, seed=5, handle_sigterm=False,
            stale_steps=5, down_steps=100)
        assert et.fleet is not None
        et.run(data_fn, 6)
    finally:
        fault.clear()
        mxcfg.unset("MXNET_FAULT_PLAN")
        mxcfg.unset("MXNET_STRAGGLER_WINDOW")
    assert events.get("mesh.straggler") > base
    strag = [e for e in flightrec.ring_snapshot()
             if e["kind"] == "mesh" and e["name"] == "straggler"]
    assert strag and strag[0]["replica"] == 1   # victim = max active
    # detected from telemetry BEFORE heartbeat staleness (inject@2 +
    # stale 5 = step 7; the run is only 6 steps long)
    assert strag[0]["step"] < 7
    # the mesh never shrank — the replica is alive, just slow
    assert et.n_replicas == 2 and not et.down
    assert et.health._state.get(1) == "slow"
    # the fleet block names it too
    assert 1 in [int(r) for r in et.fleet.block()["stragglers"]]


# ---------------------------------------------------------------------------
# dump / merge / teletop surfaces
# ---------------------------------------------------------------------------

def test_dump_fleet_block_and_straggler_cause(tele_ring, tmp_path):
    from incubator_mxnet_tpu.tools import blackbox as bb
    flightrec.set_fleet_provider(lambda: {
        "replicas": {"0": {"step_us": 1000}, "3": {"step_us": 9000}},
        "stragglers": [3]})
    try:
        flightrec.record_mesh("straggler", replica=3, step=11,
                              step_us=9000, fleet_median_us=1000)
        path = flightrec.dump_blackbox(path=str(tmp_path / "f.json"),
                                       reason="test")
    finally:
        flightrec.set_fleet_provider(None)
    doc = bb.load_dump(path)
    assert doc["fleet"]["stragglers"] == [3]
    # the dump embeds the PROCESS-GLOBAL counter ledger, so under a
    # full-suite run earlier tests' counters (quarantines, skipped
    # steps) would hit higher-ranked cause branches first — replace it
    # with exactly the contest this test is about: a feed stall that
    # the straggler family must outrank
    doc["counters"] = {"feed.stall_us": 10 ** 7, "feed.step_us": 1,
                       "mesh.straggler": 1}
    cause = bb.suspected_cause(doc)
    assert "replica 3" in cause and "straggler" in cause
    text = bb.render(doc)
    assert "fleet (per replica" in text and "*SLOW*" in text


def test_teletop_fleet_columns():
    from incubator_mxnet_tpu.tools import teletop
    snap = {"counters": {"mesh.straggler": 1}, "percentiles": {},
            "fleet": {"replicas": {
                "0": {"step": 5, "step_us": 1000, "dispatch_us": 10,
                      "collective_us": 2, "hbm_peak_bytes": 1 << 20},
                "1": {"step": 5, "step_us": 8000, "dispatch_us": 10,
                      "collective_us": 2, "hbm_peak_bytes": 1 << 20}},
                "stragglers": [1], "straggler_window": 8,
                "straggler_sigma": 4.0}}
    out = teletop.render(snap)
    assert "fleet (per replica" in out
    lines = [ln for ln in out.splitlines() if ln.startswith("1 ")]
    assert lines and "*SLOW*" in lines[0]
    assert "fleet stragglers" in out


def test_merge_traces_joins_processes(tmp_path):
    from incubator_mxnet_tpu.tools.blackbox import main, merge_traces
    a = tmp_path / "a.trace.json"
    b = tmp_path / "b.trace.json"
    a.write_text(json.dumps({"traceEvents": [
        {"name": "span:train.step", "ph": "X", "ts": 10, "dur": 5,
         "pid": 100, "tid": 1,
         "args": {"trace_id": "tX", "step": 42}}]}))
    b.write_text(json.dumps({"traceEvents": [
        {"name": "span:io.decode", "ph": "X", "ts": 11, "dur": 2,
         "pid": 200, "tid": 1,
         "args": {"trace": "tX", "step": 42}}]}))
    out = tmp_path / "merged.json"
    summary = merge_traces([str(a), str(b)], out_path=str(out))
    assert summary["processes"] == [100, 200]
    assert summary["cross_process_traces"] == ["tX"]
    assert summary["cross_process_steps"] == [42]
    merged = json.loads(out.read_text())
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert {100, 200} <= pids
    # CLI round trip
    rc = main(["merge", "--out", str(tmp_path / "m2.json"),
               str(a), str(b)])
    assert rc == 0 and (tmp_path / "m2.json").exists()


# ---------------------------------------------------------------------------
# decode-service cross-process propagation
# ---------------------------------------------------------------------------

def _make_rec(tmp_path, n=48):
    from incubator_mxnet_tpu.io import recordio
    path = str(tmp_path / "fleet48.rec")
    rs = np.random.RandomState(0)
    rec = recordio.MXRecordIO(path, "w")
    for i in range(n):
        img = rs.randint(0, 255, (80, 100, 3), dtype=np.uint8)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, quality=85))
    rec.close()
    return path


@pytest.mark.io
def test_decode_service_spans_reparent_under_consumer(tele_ring,
                                                      tmp_path):
    from incubator_mxnet_tpu.io.decode_service import (
        DecodeService, DecodeServiceUnavailable)
    path = _make_rec(tmp_path)
    try:
        svc = DecodeService(path, 8, (3, 64, 64), workers=1,
                            resize=72, dtype="uint8")
    except DecodeServiceUnavailable:
        pytest.skip("no shared memory / process spawn on this host")
    try:
        telemetry.set_global_step(77)
        it = iter(svc)
        with telemetry.span("consumer.step") as _:
            parent = telemetry.current()
            sb = next(it)
        assert sb.trace is not None
        assert sb.trace.step == 77
        spans = _ring_spans("io.decode")
        assert spans, "no io.decode span re-parented"
        ev = spans[-1]
        assert ev["parent"] == parent.span_id
        assert ev["trace"] == parent.trace_id
        assert ev["step"] == 77
        assert ev["pid"] != os.getpid()     # the WORKER's process row
        assert ev["wid"] == sb.wid
    finally:
        telemetry.set_global_step(None)
        svc.close()


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_bench_diff_regression_and_direction(tmp_path):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
        "tools"))
    try:
        import bench_diff
    finally:
        sys.path.pop(0)
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps({
        "serve_p99_us": 1000, "imgs_per_s": 500.0, "ok": True,
        "telemetry": {"counters": {"serve.shed": 0}}, "note": "x"}))
    new.write_text(json.dumps({
        "serve_p99_us": 1500, "imgs_per_s": 505.0, "ok": True,
        "telemetry": {"counters": {"serve.shed": 4}}, "note": "y"}))
    rc = bench_diff.main([str(old), str(new), "--threshold", "10"])
    assert rc == 1                      # p99 +50% = regression
    rc = bench_diff.main([str(old), str(new), "--threshold", "10",
                          "--keys", "imgs"])
    assert rc == 0                      # rate moved +1%: fine
    # direction heuristics
    assert bench_diff.direction_of("serve_p99_us") == "lower"
    assert bench_diff.direction_of("imgs_per_s") == "higher"
    assert bench_diff.direction_of(
        "io.decode.records_corrupt") == "lower"
    assert bench_diff.direction_of("weak_eff") == "higher"
    assert bench_diff.direction_of("zero_level") is None
    # bool flip true->false is always a regression
    old.write_text(json.dumps({"ok": True}))
    new.write_text(json.dumps({"ok": False}))
    assert bench_diff.main([str(old), str(new)]) == 1


def test_gate_report_artifact(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
        "tools"))
    try:
        import gate_report
    finally:
        sys.path.pop(0)
    # unset dir: no-op
    monkeypatch.delenv("MXNET_GATE_REPORT_DIR", raising=False)
    assert gate_report.write_report("check_x", "pass", []) is None
    monkeypatch.setenv("MXNET_GATE_REPORT_DIR", str(tmp_path))
    path = gate_report.write_report(
        "check_overhead", "fail",
        [{"trial": 0, "overhead_pct": 5.2, "verdict": "fail"}],
        rc=1, params={"threshold_pct": 2.0})
    assert path and os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema"].startswith("mxtpu-gate-report")
    assert doc["gate"] == "check_overhead"
    assert doc["verdict"] == "fail" and doc["rc"] == 1
    assert doc["trials"][0]["verdict"] == "fail"
    assert doc["params"]["threshold_pct"] == 2.0
    # a second run accumulates (timestamp+pid naming), not clobbers
    time.sleep(1.05)
    path2 = gate_report.write_report("check_overhead", "pass", [],
                                     rc=0)
    assert path2 != path and os.path.exists(path2)


def test_exporter_labeled_children_under_churn():
    """ISSUE 11 satellite: the labeled-children render path
    (Prometheus + JSON) must survive concurrent incr/observe(labels=)
    churn past MAX_LABELSETS — no exception, parseable output, the
    overflow fold present, and no duplicate series lines."""
    c = EventCounters()
    exp = telemetry.MetricsExporter(counters=c)
    stop = threading.Event()
    errors = []

    def hammer(tid):
        i = 0
        try:
            while not stop.is_set():
                labels = {"tenant": "t%d" % ((tid * 97 + i) % 200),
                          "lane": ("hi", "lo")[i % 2]}
                c.incr("churn.requests", labels=labels)
                c.observe("churn.e2e_us", float(i % 1000),
                          labels=labels)
                c.incr("churn.requests")
                c.observe("churn.e2e_us", float(i % 1000))
                i += 1
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    renders = []
    try:
        deadline = time.time() + 1.5
        while time.time() < deadline:
            renders.append(exp.prometheus_text())
            json.loads(exp.json_text())     # JSON path stays valid
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errors, "writer thread raised: %r" % errors
    text = exp.prometheus_text()
    assert not [e for e in errors]
    # cardinality bound held: distinct labelsets folded to overflow
    assert 'overflow="true"' in text
    labeled = c.labeled_snapshot()["churn.requests"]
    assert len(labeled) <= EventCounters.MAX_LABELSETS + 1
    # every series line unique (duplicates invalidate a whole scrape)
    for render in renders[-1:]:
        series = [ln.split(" ")[0] for ln in render.splitlines()
                  if ln and not ln.startswith("#")]
        assert len(series) == len(set(series))
    # and the unlabeled aggregate still renders alongside the children
    assert "mxnet_churn_requests " in text
    assert 'mxnet_churn_requests{lane="' in text
