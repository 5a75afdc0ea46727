"""Durable telemetry tests (ISSUE 12 tentpole): on-disk metrics
history (rotation/compaction bound, cross-run query), SLO rules
(threshold / multi-window burn-rate / MAD anomaly vs history
baselines), the alert lifecycle (slo.* counters, ring event,
mxnet_alert_active gauge, PROACTIVE black-box dump naming the rule),
the default serving rules derived from the PR 8 lane knobs, and the
cross-run trend tooling (`blackbox history`, `tools/gate_trend.py`).
CPU-only, fast."""
import json
import os
import sys
import subprocess
import threading
import time

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon
from incubator_mxnet_tpu import config as cfg
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu import telemetry
from incubator_mxnet_tpu.telemetry import history, slo
from incubator_mxnet_tpu.telemetry import flightrec as _bb
from incubator_mxnet_tpu.telemetry.history import HistoryWriter
from incubator_mxnet_tpu.tools import blackbox as bb_cli
from incubator_mxnet_tpu.tools import teletop

pytestmark = pytest.mark.slo

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", "..", ".."))


@pytest.fixture
def hist_dir(tmp_path, monkeypatch):
    """A private MXNET_HISTORY_DIR + a fresh process writer + a clean
    rule registry for every test (and after it — no rule may leak
    into the exporter ticks of later tests)."""
    d = tmp_path / "hist"
    monkeypatch.setenv("MXNET_HISTORY_DIR", str(d))
    history.reset()
    slo.clear_rules()
    yield str(d)
    slo.clear_rules()
    history.reset()


# ---------------------------------------------------------------------------
# history: write / rotate / compact / query
# ---------------------------------------------------------------------------

def test_history_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_HISTORY_DIR", "")
    history.reset()
    assert history.record("counter", "x", 1.0) == 0
    assert history.tick() == 0
    assert history.query("x") == []
    history.reset()


def test_history_append_and_query(hist_dir):
    w = history.get_writer()
    w.append("counter", "t12.a", 3.0, labels={"lane": "hi"}, total=3)
    w.append("counter", "t12.a", 2.0, labels={"lane": "lo"}, total=2)
    w.append("pct", "t12.lat_us", 99.0, p50=50, p90=90, p99=99, n=7)
    rows = history.query("t12.a")
    assert [r["v"] for r in rows] == [3.0, 2.0]
    # label subset match
    rows = history.query("t12.a", labels={"lane": "hi"})
    assert len(rows) == 1 and rows[0]["total"] == 3
    # kind + prefix match
    rows = history.query("t12.", kind="pct")
    assert len(rows) == 1 and rows[0]["p90"] == 90
    # since filter
    assert history.query("t12.a", since=time.time() + 60) == []


def test_history_tick_writes_counter_pct_and_cost_rows(hist_dir):
    from incubator_mxnet_tpu.telemetry import costs

    class _FakeCompiled:
        def cost_analysis(self):
            return {"flops": 2.5e9, "bytes accessed": 1e6}
    key = costs.note_executable("serve", "serve.infer:t12hist[0]",
                                compiled=_FakeCompiled(),
                                compile_s=0.25)
    costs.invoke(key, 3)
    events.incr("t12.tick_counter", 5)
    events.observe("t12.tick_us", 123.0)
    events.observe("t12.tick_us", 456.0, labels={"lane": "hi"})
    assert history.tick() > 0
    assert history.query("t12.tick_counter",
                         kind="counter")[0]["v"] == 5.0
    pcts = history.query("t12.tick_us", kind="pct")
    assert any(not r.get("labels") for r in pcts)
    assert any(r.get("labels") == {"lane": "hi"} for r in pcts)
    cost = history.query("serve.infer:t12hist", kind="cost")
    assert cost and cost[-1]["flops"] == 2.5e9 \
        and cost[-1]["invocations"] == 3
    # a second tick with no movement writes NO new cost row for it
    n0 = len(history.query("serve.infer:t12hist", kind="cost"))
    history.tick()
    assert len(history.query("serve.infer:t12hist",
                             kind="cost")) == n0
    # ... and an invoke moves it again
    costs.invoke(key, 1)
    history.tick()
    assert len(history.query("serve.infer:t12hist",
                             kind="cost")) == n0 + 1


def test_tick_excludes_history_self_counters(hist_dir):
    # tick N moves the history.* bookkeeping counters; tick N+1 must
    # NOT write them back as rows (the writer would never quiesce)
    history.tick()
    history.tick()
    assert history.query("history.", kind="counter") == []


def test_concurrent_ticks_count_each_delta_once(hist_dir):
    events.incr("t12.conc", 7)
    threads = [threading.Thread(target=history.tick)
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = history.query("t12.conc", kind="counter")
    assert sum(r["v"] for r in rows) == 7.0


def test_tick_quiesces_when_idle(hist_dir):
    events.observe("t12.idle_us", 5.0)
    events.observe("t12.idle_us", 7.0, labels={"lane": "x"})
    history.tick()
    n1 = len(history.query("t12.idle_us", kind="pct"))
    assert n1 == 2                  # plain + labeled
    # no new samples -> no new pct rows (identical windows must not
    # be appended forever, nor flood anomaly baselines)
    history.tick()
    assert len(history.query("t12.idle_us", kind="pct")) == n1
    events.observe("t12.idle_us", 9.0)
    history.tick()
    assert len(history.query("t12.idle_us", kind="pct")) == n1 + 1


def test_default_quota_ladder_matches_engine(hist_dir, monkeypatch):
    # slo.py re-derives the engine's auto lane-quota ladder without
    # importing it (jax); this parity test pins the two together
    from incubator_mxnet_tpu.serving.engine import _parse_lane_quotas
    monkeypatch.setenv("MXNET_SERVE_LANES", "a,b,c,d,e")
    for spec in ("", "1.0,0.4"):
        monkeypatch.setenv("MXNET_SERVE_LANE_QUOTAS", spec)
        lanes, quotas = slo._lanes_and_quotas()
        cap = 1000
        caps = _parse_lane_quotas(spec, tuple(lanes), cap)
        for lane in lanes:
            if caps[lane] is None:
                assert quotas[lane] >= 1.0
            else:
                assert max(1, int(quotas[lane] * cap)) == caps[lane]


def test_history_rotation_bound_under_concurrent_writers(hist_dir):
    cap_kb = 8
    w = HistoryWriter(directory=hist_dir, run="rotat-p1",
                      shard_kb=cap_kb)
    down0 = events.get("history.rows_downsampled")

    def writer(tid):
        for i in range(300):
            w.append("counter", "t12.rot.%d" % tid, float(i),
                     total=i, labels={"thread": str(tid)})
    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    w.append("marker", "t12.rot.final", 1.0)
    size = os.path.getsize(w.path)
    # the shard stays bounded (compaction headroom is 3/4 cap; one
    # uncompacted trailing batch may sit on top)
    assert size <= cap_kb * 1024 * 1.25, size
    assert events.get("history.rows_downsampled") > down0
    # every surviving line is valid JSON, and the NEWEST row survived
    with open(w.path) as f:
        rows = [json.loads(ln) for ln in f.read().splitlines() if ln]
    assert rows[-1]["name"] == "t12.rot.final"
    assert all(r["run"] == "rotat-p1" for r in rows)


def test_history_query_across_runs(hist_dir):
    a = HistoryWriter(directory=hist_dir, run="20260801T000000-p11")
    b = HistoryWriter(directory=hist_dir, run="20260802T000000-p22")
    a.append("counter", "t12.x", 1.0, ts=100.0)
    b.append("counter", "t12.x", 2.0, ts=200.0)
    assert history.runs(hist_dir) == ["20260801T000000-p11",
                                      "20260802T000000-p22"]
    rows = history.query("t12.x", directory=hist_dir)
    assert [(r["run"], r["v"]) for r in rows] == \
        [("20260801T000000-p11", 1.0), ("20260802T000000-p22", 2.0)]
    only_b = history.query("t12.x", directory=hist_dir,
                           run="20260802T000000-p22")
    assert [r["v"] for r in only_b] == [2.0]
    # a torn tail line (a run killed mid-write) is skipped, not raised
    with open(a.path, "a") as f:
        f.write('{"ts": 300.0, "run": "20260801T000')
    assert len(history.query("t12.x", directory=hist_dir)) == 2


# ---------------------------------------------------------------------------
# slo rules: threshold / burn-rate / anomaly
# ---------------------------------------------------------------------------

def test_threshold_rule_fires_and_clears(hist_dir):
    events.incr("t12.thr.count", 10)
    r = slo.ThresholdRule("t12-thr", metric="t12.thr.count", bound=15)
    slo.register_rule(r)
    assert slo.evaluate() == []
    events.incr("t12.thr.count", 10)        # 20 > 15
    fired0 = events.get("slo.fired")
    assert slo.evaluate() == ["t12-thr"]
    assert "t12-thr" in slo.active_alerts()
    assert events.get("slo.fired") == fired0 + 1
    # steady-state firing does not re-count the transition
    assert slo.evaluate() == ["t12-thr"]
    assert events.get("slo.fired") == fired0 + 1


def test_threshold_rule_on_labeled_percentile(hist_dir):
    for v in (100, 200, 50000):
        events.observe("t12.lab_us", v, labels={"lane": "gold"})
    r = slo.ThresholdRule("t12-lab", metric="t12.lab_us", pct="p99",
                          labels={"lane": "gold"}, bound=10000)
    assert r.check(time.time())[0] is True
    r2 = slo.ThresholdRule("t12-lab2", metric="t12.lab_us", pct="p99",
                           labels={"lane": "absent"}, bound=10000)
    assert r2.check(time.time())[0] is None     # never observed


def test_burn_rate_fires_and_clears_with_proactive_dump(hist_dir):
    _bb.clear()                     # reset the per-reason dump throttle
    events.incr("t12.burn.total", 1000)
    rule = slo.BurnRateRule(
        "t12-burn", bad="t12.burn.bad",
        total=["t12.burn.total", "t12.burn.bad"],
        budget=0.02, fast_s=1.0, slow_s=2.0)
    slo.register_rule(rule)
    t0 = time.time()
    assert slo.evaluate(now=t0) == []           # cold: one sample
    events.incr("t12.burn.bad", 100)            # ~9% >> 2% budget
    fired0 = events.get("slo.fired")
    lab0 = {tuple(sorted(r["labels"].items())): r["value"]
            for r in events.labeled_snapshot().get("slo.fired", ())}
    assert slo.evaluate(now=t0 + 0.5) == ["t12-burn"]
    info = slo.active_alerts()["t12-burn"]
    assert info["burn_fast"] >= 1.0 and info["burn_slow"] >= 1.0
    # the typed surfaces: counter, labeled counter, ring event, gauge,
    # PROACTIVE dump whose reason (and filename) name the rule
    assert events.get("slo.fired") == fired0 + 1
    lab = {tuple(sorted(r["labels"].items())): r["value"]
           for r in events.labeled_snapshot().get("slo.fired", ())}
    key = (("rule", "t12-burn"),)
    assert lab.get(key, 0) == lab0.get(key, 0) + 1
    ring = [e for e in _bb.ring_snapshot() if e["kind"] == "slo"]
    assert any(e["name"] == "fired" and e.get("rule") == "t12-burn"
               for e in ring)
    txt = telemetry.MetricsExporter().prometheus_text()
    assert 'mxnet_alert_active{rule="t12-burn"} 1' in txt
    dump = _bb.last_dump_path()
    assert dump and "slo-t12-burn" in os.path.basename(dump)
    doc = json.load(open(dump))
    assert doc["reason"] == "slo:t12-burn"
    assert "t12-burn" in doc["slo"]["active"]
    # recovery: a clean fast window clears the alert and the gauge
    events.incr("t12.burn.total", 100000)
    cleared0 = events.get("slo.cleared")
    assert slo.evaluate(now=t0 + 3.5) == []
    assert "t12-burn" not in slo.active_alerts()
    assert events.get("slo.cleared") == cleared0 + 1
    txt = telemetry.MetricsExporter().prometheus_text()
    assert 'mxnet_alert_active{rule="t12-burn"} 0' in txt
    # the alert transition is itself durable history
    srows = history.query("t12-burn", kind="slo")
    assert [r["event"] for r in srows] == ["fired", "cleared"]


def test_anomaly_rule_vs_history_baseline(hist_dir):
    w = history.get_writer()
    now = time.time()
    rows = [{"ts": now - 100 + i, "run": "base", "kind": "pct",
             "name": "t12.anom_us", "v": 100.0 + i, "p99": 100.0 + i}
            for i in range(10)]
    w.append_rows(rows)
    for _ in range(8):
        events.observe("t12.anom_us", 1000.0)   # ~6x the baseline
    r = slo.AnomalyRule("t12-anom", series="t12.anom_us", sigma=4.0,
                        baseline_s=3600.0, min_baseline=8)
    firing, info = r.check(now)
    assert firing is True and info["baseline_n"] == 10
    assert info["value"] == 1000.0 and info["threshold"] < 1000.0
    # too little baseline -> not judgeable, never a false page
    r2 = slo.AnomalyRule("t12-anom2", series="t12.anom_us",
                         min_baseline=99)
    assert r2.check(now)[0] is None


def test_anomaly_rule_label_scoped_and_self_excluded(hist_dir):
    w = history.get_writer()
    now = time.time()
    me = w.run
    rows = []
    for i in range(10):
        # another run's baselines: fast lane ~100µs, slow lane ~10ms
        rows.append({"ts": now - 50 + i, "run": "other", "kind": "pct",
                     "name": "t12.lane_us", "v": 100.0, "p99": 100.0,
                     "labels": {"lane": "fast"}})
        rows.append({"ts": now - 50 + i, "run": "other", "kind": "pct",
                     "name": "t12.lane_us", "v": 1e4, "p99": 1e4,
                     "labels": {"lane": "slow"}})
        # THIS run's own rows for another series
        rows.append({"ts": now - 50 + i, "run": me, "kind": "pct",
                     "name": "t12.self_us", "v": 100.0, "p99": 100.0})
    w.append_rows(rows)
    for _ in range(8):
        events.observe("t12.lane_us", 1000.0, labels={"lane": "fast"})
        events.observe("t12.self_us", 1000.0)
    # a labeled rule judges the lane against ITS OWN history — the
    # slow lane's 10ms rows must not inflate the fast lane's baseline
    r = slo.AnomalyRule("t12-lane", series="t12.lane_us",
                        labels={"lane": "fast"}, min_baseline=8)
    firing, info = r.check(now)
    assert firing is True and info["baseline_n"] == 10
    # only THIS run's rows exist for t12.self_us: self-excluded by
    # default (a degrading run must not normalize its own baseline)
    r2 = slo.AnomalyRule("t12-self", series="t12.self_us",
                         min_baseline=8)
    assert r2.check(now)[0] is None
    r3 = slo.AnomalyRule("t12-self2", series="t12.self_us",
                         min_baseline=8, include_self=True)
    assert r3.check(now)[0] is True


def test_unjudgeable_rule_clears_active_alert(hist_dir):
    state = {"v": True}

    class _R(slo.Rule):
        def check(self, now):
            return state["v"], {"value": 1}
    slo.register_rule(_R("t12-unj"))
    slo.evaluate()
    assert "t12-unj" in slo.active_alerts()
    # ONE unjudgeable round is a warm-up blip (a rule replaced
    # mid-incident): the alert must stay active, no flap...
    c0 = events.get("slo.cleared")
    state["v"] = None
    slo.evaluate()
    assert "t12-unj" in slo.active_alerts()
    assert events.get("slo.cleared") == c0
    # ...but PERSISTENT unjudgeability (evidence evaporated) clears
    # with a paired transition instead of latching active forever
    slo.evaluate()
    assert "t12-unj" not in slo.active_alerts()
    assert events.get("slo.cleared") == c0 + 1
    # a judgeable round in between resets the debounce
    state["v"] = True
    slo.evaluate()
    state["v"] = None
    slo.evaluate()
    assert "t12-unj" in slo.active_alerts()


def test_record_fleet_rows_keep_merge_step(hist_dir):
    n = history.record_fleet(
        {0: {"step": 5, "step_us": 111.0},
         1: {"step": 50, "step_us": 999.0}},
        step=50, stragglers=[1])
    assert n == 2
    rows = history.query("replica", kind="fleet")
    # the row's step is the rank-0 MERGE round (joinable across
    # replicas); the replica's own lagging step rides as replica_step
    assert all(r["step"] == 50 for r in rows)
    by = {r["labels"]["replica"]: r for r in rows}
    assert by["0"]["replica_step"] == 5 and by["0"]["v"] == 111.0
    assert by["1"]["straggler"] is True and not by["0"]["straggler"]


def test_broken_rule_is_counted_not_raised(hist_dir):
    class _Bad(slo.Rule):
        def check(self, now):
            raise RuntimeError("boom")
    slo.register_rule(_Bad("t12-bad"))
    e0 = events.get("slo.rule_errors")
    assert slo.evaluate() == []
    assert events.get("slo.rule_errors") == e0 + 1


def test_action_hook_runs_on_transitions(hist_dir):
    calls = []
    slo.register_action(lambda name, firing, info:
                        calls.append((name, firing)))
    events.incr("t12.act.count", 100)
    slo.register_rule(slo.ThresholdRule("t12-act",
                                        metric="t12.act.count",
                                        bound=10))
    slo.evaluate()
    # replacing a FIRING rule keeps the alert active; the next
    # evaluation under the new bound emits the paired cleared
    # transition (fired/cleared rows must always pair up)
    slo.register_rule(slo.ThresholdRule("t12-act",
                                        metric="t12.act.count",
                                        bound=1000))
    slo.evaluate()
    assert calls == [("t12-act", True), ("t12-act", False)]
    # a raising hook is counted, never propagated
    slo.register_action(lambda *a: 1 / 0)
    a0 = events.get("slo.action_errors")
    events.incr("t12.act.count", 10000)
    slo.evaluate()
    assert events.get("slo.action_errors") == a0 + 1


def test_burn_rate_latch_clears_on_fast_window_only(hist_dir):
    from collections import deque as _dq
    now = time.time()
    events.incr("t12.lt.bad", 100)
    events.incr("t12.lt.total", 101100)

    def mk(latched):
        r = slo.BurnRateRule("t12-latch", bad="t12.lt.bad",
                             total="t12.lt.total", budget=0.02,
                             fast_s=1.0, slow_s=10.0)
        # crafted windows: the fast window burns 4x while the slow
        # window — diluted by a clean flood — reads ~0.05x
        r._samples = _dq([(now - 10.5, 0.0, 0.0),
                          (now - 1.01, 50.0, 100500.0)])
        r._latched = latched
        return r
    # latched: the incident stays open while the fast window burns,
    # even though the diluted slow window dipped under 1x (no flap)
    firing, info = mk(True).check(now)
    assert firing is True
    assert info["burn_fast"] >= 1.0 and info["burn_slow"] < 1.0
    # not latched: the same windows do NOT open a NEW incident (the
    # slow window is the de-flaking gate for fresh alerts)
    assert mk(False).check(now)[0] is False
    # ... and a latched alert DOES clear once the fast window is clean
    r = mk(True)
    r._samples = _dq([(now - 10.5, 0.0, 0.0),
                      (now - 1.01, 100.0, 100000.0)])
    assert r.check(now)[0] is False and r._latched is False


# ---------------------------------------------------------------------------
# default serving rules from the PR 8 lane knobs
# ---------------------------------------------------------------------------

def test_default_serving_rules_derive_from_lane_knobs(hist_dir,
                                                      monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_LANES", "gold,silver,bronze")
    monkeypatch.setenv("MXNET_SERVE_LANE_QUOTAS", "")
    rules = slo.default_serving_rules(targets={"gold": 0.05})
    by_name = {r.name: r for r in rules}
    # one shed-burn rule per lane, budgets following the quota ladder
    # (top lane: the base budget; lower lanes: 1 - quota)
    assert by_name["serve-shed-gold"].budget == pytest.approx(
        float(cfg.get("MXNET_SLO_SHED_BUDGET")))
    assert by_name["serve-shed-silver"].budget == pytest.approx(0.25)
    assert by_name["serve-shed-bronze"].budget == pytest.approx(0.5)
    for lane in ("gold", "silver", "bronze"):
        r = by_name["serve-shed-%s" % lane]
        assert r.labels == {"lane": lane}
        assert r.bad == ["serve.shed"]
        assert r.total == ["serve.requests", "serve.shed"]
    # p99-vs-deadline only for the lane with an observed target
    assert by_name["serve-p99-gold"].bound == pytest.approx(5e4)
    assert "serve-p99-silver" not in by_name
    # explicit quota spec wins over the auto ladder
    monkeypatch.setenv("MXNET_SERVE_LANE_QUOTAS", "1.0,0.4")
    rules = slo.default_serving_rules()
    by_name = {r.name: r for r in rules}
    assert by_name["serve-shed-silver"].budget == pytest.approx(0.6)
    assert by_name["serve-shed-bronze"].budget == pytest.approx(0.6)
    # programmatic quotas (a live engine's actual enforcement)
    # override the env knobs entirely — lanes included
    rules = slo.default_serving_rules(quotas={"a": 1.0, "b": 0.9})
    by_name = {r.name: r for r in rules}
    assert set(by_name) == {"serve-shed-a", "serve-shed-b"}
    assert by_name["serve-shed-b"].budget == pytest.approx(0.1)


def test_engine_and_registry_slo_targets(hist_dir):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(4))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    net(nd.array(onp.zeros((1, 8), onp.float32), ctx=mx.cpu()))
    from incubator_mxnet_tpu.serving import ModelRegistry
    reg = ModelRegistry(devices=[mx.cpu()])
    try:
        reg.register("t12m", net, example_shape=(8,),
                     wire_dtype="float32", max_batch=4)
        data = onp.zeros((2, 8), onp.float32)
        # deadlines generous enough to absorb the first-call compile
        # (the engine tracks the tightest RELATIVE deadline per lane)
        futs = [reg.submit_batch("t12m", data, deadline=30.0),
                reg.submit_batch("t12m", data, deadline=20.0,
                                 lane="normal"),
                reg.submit_batch("t12m", data, deadline=10.0)]
        for f in futs:
            f.result(timeout=60)
        # the tightest observed relative deadline per lane
        targets = reg.slo_targets()
        assert targets["high"] == pytest.approx(10.0)
        assert targets["normal"] == pytest.approx(20.0)
        names = reg.install_slo_rules(fast_s=1.0, slow_s=2.0)
        assert "serve-p99-high" in names \
            and "serve-shed-high" in names
        rules = slo.rules()
        assert rules["serve-p99-high"].bound == pytest.approx(1e7)
    finally:
        reg.close()


# ---------------------------------------------------------------------------
# exporter integration: the periodic tick drives history + slo
# ---------------------------------------------------------------------------

def test_exporter_tick_drives_history_and_slo(hist_dir, tmp_path):
    events.incr("t12.exp.count", 100)
    slo.register_rule(slo.ThresholdRule("t12-exp",
                                        metric="t12.exp.count",
                                        bound=10))
    exp = telemetry.MetricsExporter()
    exp.start(path=str(tmp_path / "snap.json"), period_s=0.05)
    try:
        deadline = time.time() + 10
        while time.time() < deadline:
            if "t12-exp" in slo.active_alerts() and \
                    history.query("t12.exp.count", kind="counter"):
                break
            time.sleep(0.05)
    finally:
        exp.close()
    assert "t12-exp" in slo.active_alerts()
    assert history.query("t12.exp.count", kind="counter")
    # the snapshot surfaces carry the slo block for teletop
    snap = exp.json_dict()
    assert "t12-exp" in snap["slo"]["active"]
    out = teletop.render(snap)
    assert "ALERT  t12-exp" in out
    assert "slo (" in out


# ---------------------------------------------------------------------------
# trend tooling: blackbox history CLI + gate_trend
# ---------------------------------------------------------------------------

def _two_run_dir(hist_dir):
    a = HistoryWriter(directory=hist_dir, run="20260801T000000-p11")
    b = HistoryWriter(directory=hist_dir, run="20260802T000000-p22")
    for i, v in enumerate((100.0, 110.0, 120.0)):
        a.append("pct", "t12.cli_us", v, ts=100.0 + i, p99=v)
    for i, v in enumerate((100.0, 200.0, 300.0)):
        b.append("pct", "t12.cli_us", v, ts=200.0 + i, p99=v)
    a.append("counter", "t12.cli.hit", 10.0, ts=103.0)
    b.append("counter", "t12.cli.hit", 12.0, ts=203.0)
    # counters whose last per-tick DELTA inverts the cumulative story:
    # run A shed 500 total (last delta 1), run B shed 5 total
    a.append("counter", "t12.cli.shed", 5.0, ts=103.5, total=499)
    a.append("counter", "t12.cli.shed", 1.0, ts=104.0, total=500)
    b.append("counter", "t12.cli.shed", 5.0, ts=204.0, total=5)
    a.append("pct", "t12.gone_us", 5.0, ts=105.0, p99=5.0)
    return a, b


def test_blackbox_history_cli_golden(hist_dir, capsys):
    _two_run_dir(hist_dir)
    # runs summary
    assert bb_cli.main(["history", "--dir", hist_dir]) == 0
    out = capsys.readouterr().out
    assert "20260801T000000-p11" in out and "pct:3" in out
    # trend table with sparkline + delta vs the previous run
    assert bb_cli.main(["history", "--dir", hist_dir,
                        "--name", "t12.cli_us"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if "t12.cli_us" in ln]
    assert len(lines) == 2
    assert "+150.0" in lines[1]         # 120 -> 300 last-value delta
    assert any(c in lines[1] for c in "▁▂▃▄▅▆▇█")
    # --diff: the _us series regressed 120 -> 300 (lower-better)
    rc = bb_cli.main(["history", "--dir", hist_dir, "--diff"])
    out = capsys.readouterr()
    assert rc == 1
    assert "REGRESSION" in out.out and "t12.cli_us" in out.err
    # a series present only in run A must be surfaced, not silently
    # dropped from the comparison
    assert "VANISHED" in out.out and "t12.gone_us" in out.out
    # higher-better key improving does not gate
    rc = bb_cli.main(["history", "--dir", hist_dir, "--diff",
                      "--name", "t12.cli.hit"])
    out = capsys.readouterr()
    assert rc == 0 and "improved" in out.out
    # counters diff by CUMULATIVE total: run B shed 100x LESS even
    # though its last per-tick delta is larger — must read improved
    rc = bb_cli.main(["history", "--dir", hist_dir, "--diff",
                      "--name", "t12.cli.shed"])
    out = capsys.readouterr()
    assert rc == 0 and "improved" in out.out
    # a typo'd run id is a loud usage error, never a silent OK
    rc = bb_cli.main(["history", "--dir", hist_dir, "--diff",
                      "20260801T000000-p11", "nope"])
    assert rc == 2 and "nope" in capsys.readouterr().err
    # empty dir is a usage error, not a crash
    assert bb_cli.main(["history", "--dir",
                        os.path.join(hist_dir, "nope")]) == 2


def _gate_trend_mod():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import gate_trend
    finally:
        sys.path.pop(0)
    return gate_trend


def test_gate_trend_table_and_allfail_rc(tmp_path, capsys):
    gt = _gate_trend_mod()
    d = str(tmp_path / "gates")
    os.makedirs(d)

    def art(gate, ts, verdict, trials=()):
        doc = {"schema": "mxtpu-gate-report/1", "gate": gate,
               "ts": ts, "pid": 1, "verdict": verdict,
               "trials": list(trials)}
        with open(os.path.join(d, "%s-%d.json" % (gate, ts)),
                  "w") as f:
            json.dump(doc, f)
    art("check_overhead", 1, "pass")
    art("check_overhead", 2, "fail",
        [{"verdict": "inconclusive"}])
    art("check_overhead", 3, "pass")
    art("check_feed", 1, "skip")
    art("check_feed", 2, "fail")
    art("check_feed", 3, "fail")
    art("check_feed", 4, "fail")
    # a non-report json must be ignored
    with open(os.path.join(d, "other.json"), "w") as f:
        json.dump({"schema": "something-else"}, f)
    rc = gt.main([d, "--window", "3"])
    out = capsys.readouterr()
    assert rc == 1
    assert "check_feed" in out.err          # all-fail window
    rows = {r["gate"]: r for r in gt.trend(gt.load_reports(d),
                                           window=3)}
    assert rows["check_overhead"]["flake_pct"] == pytest.approx(33.3)
    assert rows["check_overhead"]["recent"] == "PFP"
    assert rows["check_overhead"]["inconclusive_trials"] == 1
    assert not rows["check_overhead"]["all_fail_window"]
    assert rows["check_feed"]["recent"] == "FFF"
    assert rows["check_feed"]["all_fail_window"]
    # skips don't count into the flake rate
    assert rows["check_feed"]["flake_pct"] == pytest.approx(100.0)
    # window not yet full -> never judged all-fail
    rows5 = {r["gate"]: r for r in gt.trend(gt.load_reports(d),
                                            window=5)}
    assert not rows5["check_feed"]["all_fail_window"]


# ---------------------------------------------------------------------------
# the acceptance scenario: two processes + a synthetic overload
# ---------------------------------------------------------------------------

_RUN1 = r"""
import os, sys
os.environ["MXNET_HISTORY_DIR"] = sys.argv[1]
os.environ["JAX_PLATFORMS"] = "cpu"
from incubator_mxnet_tpu.telemetry import history, costs
from incubator_mxnet_tpu.monitor import events

class _FakeCompiled:
    def cost_analysis(self):
        return {"flops": 2.5e9, "bytes accessed": 1.5e6}

key = costs.note_executable("serve", "serve.infer:demo[0]",
                            compiled=_FakeCompiled(), compile_s=0.5)
costs.invoke(key, 7)
events.incr("serve.shed", 7)
assert history.tick() > 0
print("RUN1_ID=%s" % history.get_writer().run)
"""


def test_two_process_proof(hist_dir, monkeypatch):
    """Acceptance: run 1 (a separate process) writes history shards;
    run 2 (this process) queries run 1's cost rows by label, then a
    synthetic serving overload trips a burn-rate rule — gauge set,
    slo.fired labeled counter incremented, proactive dump naming the
    rule."""
    env = dict(os.environ)
    env.pop("MXNET_HISTORY_DIR", None)
    res = subprocess.run(
        [sys.executable, "-c", _RUN1, hist_dir], cwd=_ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    run1 = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RUN1_ID=")][0].split("=", 1)[1]

    # -- run 2: query run 1's cost rows by label across processes
    me = history.get_writer().run
    assert me != run1
    rows = history.query("serve.infer:demo", kind="cost",
                         labels={"kind": "serve"})
    assert rows, "run 1's cost rows not visible to run 2"
    assert rows[-1]["run"] == run1
    assert rows[-1]["flops"] == 2.5e9 and rows[-1]["invocations"] == 7
    # the counters rode along in the same shard
    assert history.query("serve.shed", kind="counter",
                         run=run1)[0]["v"] == 7.0

    # -- synthetic overload against the DEFAULT serving rules
    _bb.clear()
    names = slo.install_default_serving_rules(
        targets={"high": 0.25}, fast_s=1.0, slow_s=2.0)
    assert "serve-shed-high" in names
    t0 = time.time()
    events.incr("serve.requests", 50, labels={"lane": "high"})
    slo.evaluate(now=t0)
    # 2x offered load: half the lane's traffic sheds (>> 2% budget)
    events.incr("serve.shed", 50,
                labels={"lane": "high", "reason": "lane_quota"})
    events.incr("serve.requests", 50, labels={"lane": "high"})
    fired0 = {tuple(sorted(r["labels"].items())): r["value"]
              for r in events.labeled_snapshot().get("slo.fired", ())}
    firing = slo.evaluate(now=t0 + 0.5)
    assert "serve-shed-high" in firing
    # gauge
    txt = telemetry.MetricsExporter().prometheus_text()
    assert 'mxnet_alert_active{rule="serve-shed-high"} 1' in txt
    # labeled counter
    fired = {tuple(sorted(r["labels"].items())): r["value"]
             for r in events.labeled_snapshot().get("slo.fired", ())}
    key = (("rule", "serve-shed-high"),)
    assert fired.get(key, 0) == fired0.get(key, 0) + 1
    # proactive dump naming the rule
    dump = _bb.last_dump_path()
    assert dump and "slo-serve-shed-high" in os.path.basename(dump)
    doc = json.load(open(dump))
    assert doc["reason"] == "slo:serve-shed-high"
    assert "serve-shed-high" in doc["slo"]["active"]
