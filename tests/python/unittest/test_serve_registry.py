"""Overload-hardened multi-tenant serving tests (ISSUE 8 tentpole):
priority lanes (strict priority + EDF), lane/tenant quota shedding
with the typed Shed error, the exactly-once drain contract under a
shed storm, labeled tenant/lane counter splits in /metrics and
black-box dumps, ModelRegistry HBM admission control (refusal = a
flight-recorder event naming the model), and the per-model circuit
breaker.  CPU-only, fast (the check_serve overload gate is
slow-marked)."""
import json
import queue as _queue
import threading
import time
from concurrent.futures import Future

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, fault
from incubator_mxnet_tpu import config as cfg
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.serving import (InferenceEngine, QueueFull,
                                         DeadlineExceeded, Shed,
                                         ModelRegistry, AdmissionDenied,
                                         CircuitOpen, UnknownModel,
                                         project_footprint)
from incubator_mxnet_tpu.serving.engine import _LaneQueue, _OverQuota
from incubator_mxnet_tpu.telemetry import flightrec as _bb

pytestmark = pytest.mark.serve


def _dense_net(units=4, in_units=8, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, activation="relu"))
        net.add(gluon.nn.Dense(units))
    net.initialize(ctx=mx.cpu())
    net.hybridize()
    net(nd.array(onp.zeros((1, in_units), onp.float32), ctx=mx.cpu()))
    return net


def _data(n, in_units=8, seed=1):
    return onp.random.RandomState(seed).rand(n, in_units).astype(
        onp.float32)


def _req(lane, deadline=None, tenant=None):
    r = type("R", (), {})()
    r.lane, r.tenant = lane, tenant
    r.deadline = deadline
    r.future = Future()
    return r


# ---------------------------------------------------------------------------
# the lane queue: strict priority across lanes, EDF within one
# ---------------------------------------------------------------------------

def test_lane_queue_priority_and_edf():
    q = _LaneQueue(8, ("hi", "lo"), {"hi": None, "lo": 4})
    q.put_nowait(_req("lo", deadline=50.0))
    q.put_nowait(_req("lo", deadline=10.0))     # earlier: pops first
    q.put_nowait(_req("lo"))                    # no deadline: pops last
    q.put_nowait(_req("hi", deadline=99.0))
    q.put_nowait(_req("hi"))
    # hi drains entirely before lo, EDF inside each lane, undeadlined
    # after every deadlined one (FIFO among themselves)
    lanes = [q.get_nowait().lane for _ in range(5)]
    assert lanes == ["hi", "hi", "lo", "lo", "lo"]
    # rebuild to check EDF order of the deadlines themselves
    q2 = _LaneQueue(8, ("lo",), {"lo": None})
    a, b, c = _req("lo", 50.0), _req("lo", 10.0), _req("lo")
    for r in (a, b, c):
        q2.put_nowait(r)
    assert q2.get_nowait() is b and q2.get_nowait() is a \
        and q2.get_nowait() is c
    # lane quota: 5th lo raises _OverQuota, global cap raises Full
    q3 = _LaneQueue(6, ("hi", "lo"), {"hi": None, "lo": 2})
    for _ in range(2):
        q3.put_nowait(_req("lo"))
    with pytest.raises(_OverQuota):
        q3.put_nowait(_req("lo"))
    for _ in range(4):
        q3.put_nowait(_req("hi"))
    with pytest.raises(_queue.Full):
        q3.put_nowait(_req("hi"))
    assert q3.qsize() == 6 and q3.lane_depths() == {"hi": 4, "lo": 2}


def test_lane_priority_under_stall():
    """Requests queued while the dispatcher is busy come out highest
    lane first, EDF within the lane — end to end through the engine."""
    net = _dense_net(seed=41)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          max_wait_us=100, queue_cap=16,
                          lanes=("hi", "lo"))
    done_order = []

    def track(tag):
        def cb(f):
            if f.exception() is None:
                done_order.append(tag)
        return cb

    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        x = _data(4)
        # first request holds the dispatcher in a 0.3s stalled call
        fault.install("serve.infer", at_calls=[2], times=1,
                      seconds=0.3)
        f0 = eng.submit(x[0], lane="lo")
        time.sleep(0.1)                 # dispatcher inside the stall
        fl = eng.submit(x[1], lane="lo", deadline=60.0)
        fl2 = eng.submit(x[2], lane="lo", deadline=30.0)  # earlier
        fh = eng.submit(x[3], lane="hi")
        for tag, f in (("f0", f0), ("lo_d60", fl), ("lo_d30", fl2),
                       ("hi", fh)):
            f.add_done_callback(track(tag))
        for f in (f0, fl, fl2, fh):
            f.result(timeout=30)
        assert done_order == ["f0", "hi", "lo_d30", "lo_d60"], done_order
    finally:
        fault.clear()
        eng.close()


# ---------------------------------------------------------------------------
# shedding: lane quota, tenant quota, born-expired
# ---------------------------------------------------------------------------

def test_lane_quota_shed_typed_and_counted():
    net = _dense_net(seed=43)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          max_wait_us=100, queue_cap=8,
                          lanes=("hi", "lo"), lane_quotas=(1.0, 0.5))
    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        s0 = events.get("serve.shed")
        fault.install("serve.infer", at_calls=[2], times=1,
                      seconds=0.4)
        x = _data(8)
        futs = [eng.submit(x[0], lane="lo")]    # dispatcher stalls
        time.sleep(0.1)
        for i in range(4):                      # lo quota = 4
            futs.append(eng.submit(x[i], lane="lo"))
        with pytest.raises(Shed):
            eng.submit(x[5], lane="lo")
        assert events.get("serve.shed") == s0 + 1
        lab = events.labeled_snapshot("serve.shed")["serve.shed"]
        assert any(r["labels"] == {"lane": "lo", "reason": "lane_quota"}
                   and r["value"] >= 1 for r in lab)
        # the hi lane still has headroom while lo sheds
        futs.append(eng.submit(x[6], lane="hi"))
        for f in futs:
            assert f.result(timeout=30) is not None
    finally:
        fault.clear()
        eng.close()


def test_tenant_quota_shed_and_no_leaked_counts():
    net = _dense_net(seed=45)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          max_wait_us=100, queue_cap=16,
                          lanes=("hi",), tenant_quota=2)
    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        fault.install("serve.infer", at_calls=[2], times=1,
                      seconds=0.4)
        x = _data(8)
        futs = [eng.submit(x[0], tenant="a")]   # dispatcher stalls
        time.sleep(0.1)
        futs += [eng.submit(x[i], tenant="a") for i in (1, 2)]
        with pytest.raises(Shed):               # 3rd queued for "a"
            eng.submit(x[3], tenant="a")
        lab = events.labeled_snapshot("serve.shed")["serve.shed"]
        assert any(r["labels"] == {"tenant": "a"} and r["value"] >= 1
                   for r in lab)
        futs.append(eng.submit(x[4], tenant="b"))   # other tenant ok
        assert eng.stats()["tenants_queued"].get("a", 0) >= 1
        for f in futs:
            assert f.result(timeout=30) is not None
        assert eng.drain(timeout=30)
        # quota holds fully released — nothing leaked across the storm
        assert eng.stats()["tenants_queued"] == {}
    finally:
        fault.clear()
        eng.close()


def test_top_lane_displaces_low_on_full_queue():
    """A higher-lane submit meeting a FULL queue evicts the newest
    lowest-lane request (shed, typed) and takes its slot — lower-lane
    backlog must not be able to starve the top lane at admission."""
    net = _dense_net(seed=67)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=1,
                          max_wait_us=100, queue_cap=3,
                          lanes=("hi", "lo"), lane_quotas=(1.0, 1.0))
    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        # the stall holds the dispatcher on the FIRST batch after this
        # line (serve.slow stalls and does not fail, so nothing is
        # retried), and outlives every assertion below that needs the
        # queue STILL full: nothing leaves the queue until it ends
        fault.install("serve.slow", at_calls=[1], times=1, seconds=3.0)
        x = _data(8)
        f0 = eng.submit(x[0], lane="lo")    # dispatcher stalls on it
        t_end = time.monotonic() + 10.0
        while not fault.fired_count("serve.slow"):
            assert time.monotonic() < t_end, "the stall never fired"
            time.sleep(0.005)
        lo = [eng.submit(x[i], lane="lo") for i in (1, 2, 3)]
        assert eng._q.qsize() == 3          # full, f0 in the stall
        fh = eng.submit(x[4], lane="hi")    # displaces newest lo
        with pytest.raises(Shed):
            lo[-1].result(timeout=5)
        lab = events.labeled_snapshot("serve.shed")["serve.shed"]
        assert any(r["labels"] == {"lane": "lo", "reason": "displaced"}
                   for r in lab)
        # a lo submit on the still-full queue has nothing lower to
        # displace: plain QueueFull backpressure
        assert eng._q.qsize() == 3 and not f0.done()
        with pytest.raises(QueueFull):
            eng.submit(x[5], lane="lo")
        for f in (f0, lo[0], lo[1], fh):    # the survivors complete
            assert f.result(timeout=30) is not None
    finally:
        fault.clear()
        eng.close()


def _note_measured_row(name):
    """File a row WITH memory analysis under model `name`'s cost label,
    from an executable compiled here: `MeteredJit` rows carry cost
    analysis only (ROADMAP R5), so on the default path nothing else
    gives `reconcile` a measured footprint."""
    import jax
    from incubator_mxnet_tpu.telemetry import costs as _costs
    compiled = jax.jit(lambda w, x: x @ w).lower(
        jax.ShapeDtypeStruct((8, 4), onp.float32),
        jax.ShapeDtypeStruct((4, 8), onp.float32)).compile()
    _costs.note_executable(kind="serve",
                           label="serve.infer:%s[0]" % name,
                           compiled=compiled)
    return _costs.footprint_bytes("serve.infer:%s" % name, kind="serve")


def test_reregister_does_not_inherit_stale_footprint():
    """unregister drops the model's cost rows: a re-registered name is
    admitted on a fresh projection of the NEW block, never on the old
    incarnation's measured footprint."""
    from incubator_mxnet_tpu.telemetry import costs as _costs
    reg = ModelRegistry(devices=[mx.cpu(0)])
    reg.register("m", _dense_net(seed=69), example_shape=(8,),
                 wire_dtype="float32", max_batch=4)
    reg.warmup("m")
    assert _note_measured_row("m") > 0
    reg.unregister("m")
    assert _costs.footprint_bytes("serve.infer:m", kind="serve") == 0
    rec = reg.register("m", _dense_net(units=32, seed=71),
                       example_shape=(8,), wire_dtype="float32",
                       max_batch=4)
    assert rec["basis"] == "projected"
    reg.close()


def test_born_expired_is_shed_typed():
    net = _dense_net(seed=47)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=2)
    try:
        d0 = events.get("serve.deadline_expired")
        with pytest.raises(DeadlineExceeded):
            eng.submit(_data(1)[0], deadline=-0.5)
        assert events.get("serve.deadline_expired") == d0 + 1
        with pytest.raises(ValueError):         # unknown lane
            eng.submit(_data(1)[0], lane="nope")
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# engine lifecycle under sustained overload (ISSUE 8 satellite):
# shed storm, then drain resolves every accepted future exactly once
# ---------------------------------------------------------------------------

def test_overload_storm_then_drain_exactly_once():
    net = _dense_net(seed=49)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=4,
                          max_wait_us=500, queue_cap=12,
                          lanes=("hi", "lo"), lane_quotas=(1.0, 0.5),
                          tenant_quota=3)
    resolved = []
    res_lock = threading.Lock()
    shed_counts = {"sync": 0}
    accepted = []

    def submitter(tid):
        rs = onp.random.RandomState(tid)
        x = _data(64, seed=tid)
        for i in range(64):
            lane = "hi" if rs.rand() < 0.3 else "lo"
            try:
                f = eng.submit(
                    x[i], lane=lane, tenant="t%d" % (i % 5),
                    deadline=0.05 if rs.rand() < 0.3 else None)
            except (Shed, QueueFull, DeadlineExceeded):
                with res_lock:
                    shed_counts["sync"] += 1
                continue
            with res_lock:
                accepted.append(f)
            f.add_done_callback(
                lambda fu: resolved.append(fu))     # list.append is
                                                    # thread-safe
    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert eng.drain(timeout=60)
        assert eng.close(timeout=60)
        # every ACCEPTED future resolved exactly once (done callbacks
        # fire once per future), storm or not
        assert len(accepted) + shed_counts["sync"] == 4 * 64
        assert all(f.done() for f in accepted)
        assert len(resolved) == len(accepted)
        # no leaked tenant holds, no phantom queue accounting, no
        # dispatcher thread left behind
        assert eng.stats()["tenants_queued"] == {}
        assert eng._q.unfinished_tasks == 0
        t = eng._thread
        assert t is None or not t.is_alive()
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# labeled splits reach the export surfaces
# ---------------------------------------------------------------------------

def test_labeled_splits_in_metrics_and_blackbox(tmp_path):
    from incubator_mxnet_tpu.telemetry.export import MetricsExporter
    net = _dense_net(seed=51)
    eng = InferenceEngine(net, ctx=mx.cpu(), max_batch=2,
                          max_wait_us=100, lanes=("hi", "lo"))
    try:
        eng.warmup(example_shape=(8,), wire_dtype="float32")
        x = _data(4)
        for i in range(4):
            eng.submit(x[i], lane="lo" if i % 2 else "hi",
                       tenant="acme").result(timeout=30)
        txt = MetricsExporter().prometheus_text()
        assert 'mxnet_serve_e2e_us{lane="hi",quantile="0.5"}' in txt
        assert 'mxnet_serve_requests{tenant="acme"}' in txt
        path = _bb.dump_blackbox(path=str(tmp_path), reason="test")
        with open(path) as fh:
            doc = json.load(fh)
        lab = doc["labeled"]
        assert any(r["labels"].get("lane") == "hi"
                   for r in lab["percentiles"].get("serve.e2e_us", []))
        assert "serve.requests" in lab["counters"]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# ModelRegistry: admission control, ledger, breaker
# ---------------------------------------------------------------------------

def test_registry_admission_refusal_names_model_in_ring():
    net_a, net_b = _dense_net(seed=53), _dense_net(seed=55)
    fp, detail = project_footprint(net_a, (1, 2, 4, 8), (8,),
                                   "float32")
    assert fp > detail["param_bytes"] > 0
    cfg.set("MXNET_SERVE_HBM_BUDGET", int(fp * 1.5))
    try:
        reg = ModelRegistry(devices=[mx.cpu(0)])
        rec = reg.register("alpha", net_a, example_shape=(8,),
                           wire_dtype="float32", max_batch=8)
        assert rec["basis"] == "projected"
        assert rec["footprint_bytes"] == fp
        r0 = events.get("serve.admission_rejected")
        with pytest.raises(AdmissionDenied):
            reg.register("beta", net_b, example_shape=(8,),
                         wire_dtype="float32", max_batch=8)
        assert events.get("serve.admission_rejected") == r0 + 1
        ring = [e for e in _bb.ring_snapshot()
                if e.get("kind") == "serve"
                and e["name"] == "admission_rejected"]
        assert ring and ring[-1]["model"] == "beta"
        assert ring[-1]["decision"][0]["committed"] == fp
        # serving still works for the admitted model
        out = reg.submit("alpha", _data(1)[0]).result(timeout=30)
        assert out is not None
        # eviction releases the budget: beta now fits
        reg.unregister("alpha")
        assert reg.stats()["ledger"][0]["committed"] == 0
        reg.register("beta", net_b, example_shape=(8,),
                     wire_dtype="float32", max_batch=8)
        reg.close()
    finally:
        cfg.unset("MXNET_SERVE_HBM_BUDGET")


def test_registry_unknown_and_duplicate():
    net = _dense_net(seed=57)
    with ModelRegistry(devices=[mx.cpu(0)]) as reg:
        reg.register("m", net, example_shape=(8,),
                     wire_dtype="float32", max_batch=2)
        with pytest.raises(ValueError):
            reg.register("m", net, example_shape=(8,), max_batch=2)
        with pytest.raises(UnknownModel):
            reg.submit("ghost", _data(1)[0])
        with pytest.raises(UnknownModel):
            reg.unregister("ghost")


def test_registry_breaker_opens_then_probe_recloses():
    cfg.set("MXNET_SERVE_BREAKER_FAILS", 2)
    cfg.set("MXNET_SERVE_BREAKER_COOLDOWN_S", 0.5)
    net = _dense_net(seed=59)
    x = _data(1, seed=61)
    try:
        reg = ModelRegistry(devices=[mx.cpu(0)])
        reg.register("m", net, example_shape=(8,),
                     wire_dtype="float32", max_batch=2)
        eng = reg.engine("m")
        eng.warmup()
        broken = {"on": True}
        orig = eng._run

        def run(dev_i, batch_np):
            if broken["on"]:
                raise RuntimeError("injected backend failure")
            return orig(dev_i, batch_np)

        eng._run = run
        o0 = events.get("serve.breaker_opened")
        for _ in range(2):              # terminal failures trip it
            with pytest.raises(RuntimeError):
                reg.submit("m", x[0]).result(timeout=30)
        assert events.get("serve.breaker_opened") == o0 + 1
        assert reg.stats()["models"]["m"]["breaker"] == "open"
        with pytest.raises(CircuitOpen):    # fast-fail, no queueing
            reg.submit("m", x[0])
        ring = [e for e in _bb.ring_snapshot()
                if e.get("kind") == "serve"]
        assert any(e["name"] == "breaker_open" and e.get("model") == "m"
                   for e in ring)
        # heal the backend, wait out the cooldown: ONE probe re-closes
        broken["on"] = False
        time.sleep(0.6)
        assert reg.submit("m", x[0]).result(timeout=30) is not None
        assert reg.stats()["models"]["m"]["breaker"] == "closed"
        assert events.get("serve.breaker_closed") >= 1
        assert any(e["name"] == "breaker_closed"
                   and e.get("model") == "m"
                   for e in _bb.ring_snapshot()
                   if e.get("kind") == "serve")
        reg.close()
    finally:
        cfg.unset("MXNET_SERVE_BREAKER_FAILS")
        cfg.unset("MXNET_SERVE_BREAKER_COOLDOWN_S")


def test_registry_flow_errors_do_not_trip_breaker():
    cfg.set("MXNET_SERVE_BREAKER_FAILS", 1)
    net = _dense_net(seed=63)
    try:
        reg = ModelRegistry(devices=[mx.cpu(0)])
        reg.register("m", net, example_shape=(8,),
                     wire_dtype="float32", max_batch=1, queue_cap=1,
                     max_wait_us=100)
        # born-expired deadline: a flow-control rejection, breaker
        # stays closed even at max_fails=1
        with pytest.raises(DeadlineExceeded):
            reg.submit("m", _data(1)[0], deadline=-1.0)
        assert reg.stats()["models"]["m"]["breaker"] == "closed"
        assert reg.submit("m", _data(1)[0]).result(timeout=30) \
            is not None
        reg.close()
    finally:
        cfg.unset("MXNET_SERVE_BREAKER_FAILS")


def test_registry_warmup_reconciles_measured_footprint():
    """A row with memory analysis under the model's cost label flows
    back into the admission ledger (projection -> measured); warmup
    alone leaves the admission projected (ROADMAP R5)."""
    net = _dense_net(seed=65)
    reg = ModelRegistry(devices=[mx.cpu(0)])
    rec = reg.register("m", net, example_shape=(8,),
                       wire_dtype="float32", max_batch=4)
    assert rec["basis"] == "projected"
    reg.warmup("m")
    assert reg.stats()["models"]["m"]["basis"] == "projected"
    fp = _note_measured_row("m")
    assert fp > 0 and reg.reconcile("m") == fp
    measured = reg.stats()["models"]["m"]
    assert measured["basis"] == "measured"
    assert measured["footprint_bytes"] == fp
    assert reg.stats()["ledger"][0]["committed"] == fp
    ring = [e for e in _bb.ring_snapshot()
            if e.get("kind") == "serve"
            and e["name"] == "footprint_reconciled"]
    assert ring and ring[-1]["model"] == "m"
    out = reg.submit("m", _data(1)[0]).result(timeout=30)
    assert out is not None
    reg.close()


# ---------------------------------------------------------------------------
# the overload CI gate (slow: ~3 trials x (compile + 5.5s) worst case)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_check_serve_gate():
    import os
    import subprocess
    import sys
    root = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", ".."))
    res = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_serve.py"),
         "--duration", "3"],
        capture_output=True, text=True, timeout=420, cwd=root)
    assert res.returncode == 0, \
        "check_serve failed:\n%s\n%s" % (res.stdout, res.stderr)
    assert ("OK" in res.stdout) or ("SKIP" in res.stdout)
