"""Flight recorder + cost attribution (ISSUE 5): ring bounding, every
dump trigger (explicit, sys/threading excepthook, SIGUSR2, rollback,
preemption, serving dispatcher backstop), atomic dump writes, the cost
registry round-trip (incl. the None-returning-backend guard), the
`mem.*` storage series, and the blackbox CLI — all on CPU."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import fault, gluon, nd, parallel, telemetry
from incubator_mxnet_tpu.monitor import events
from incubator_mxnet_tpu.telemetry import costs, flightrec

pytestmark = pytest.mark.blackbox


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Each test starts with an empty ring/registry and the default
    (enabled) recorder; crash hooks never leak across tests."""
    flightrec.uninstall_crash_hooks()
    flightrec.clear()
    flightrec.configure()
    costs.reset()
    prev = flightrec.enable(True)
    yield
    flightrec.uninstall_crash_hooks()
    flightrec.enable(prev)
    flightrec.clear()
    costs.reset()


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------

def test_ring_bounded_under_churn():
    """10k events from 4 threads stay within the configured bound and
    keep the NEWEST events (it's a flight recorder, not a log)."""
    flightrec.configure(maxlen=64)

    def hammer(tid):
        for i in range(2500):
            flightrec.record("step", "t%d" % tid, i=i)

    ts = [threading.Thread(target=hammer, args=(k,)) for k in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = flightrec.ring_snapshot()
    assert len(evs) == 64
    # the retained tail is the newest slice of SOME thread's stream
    assert max(e["i"] for e in evs) == 2499


def test_record_disabled_is_noop():
    flightrec.enable(False)
    flightrec.record("step", "never")
    assert flightrec.ring_snapshot() == []


def test_counter_delta_samples():
    events.incr("bbtest.count", 5)
    flightrec.sample_counters(prefixes=("bbtest.",))
    events.incr("bbtest.count", 3)
    delta = flightrec.sample_counters(prefixes=("bbtest.",))
    assert delta == {"bbtest.count": 3}
    kinds = [e for e in flightrec.ring_snapshot()
             if e["kind"] == "counters"]
    assert kinds and kinds[-1]["bbtest.count"] == 3


# ---------------------------------------------------------------------------
# dumps
# ---------------------------------------------------------------------------

def _load(path):
    with open(path) as f:
        return json.load(f)


def test_dump_explicit_atomic_selfcontained(tmp_path):
    flightrec.record("marker", "hello", x=1)
    with telemetry.span("bb.span"):     # needs telemetry enabled
        pass
    p = telemetry.dump_blackbox(path=str(tmp_path), reason="unit")
    doc = _load(p)
    for key in ("schema", "reason", "config", "counters", "costs",
                "events", "trace", "hbm"):
        assert key in doc, key
    assert doc["reason"] == "unit"
    assert doc["config"]["MXNET_BLACKBOX"] is True
    assert any(e["kind"] == "marker" and e["name"] == "hello"
               for e in doc["events"])
    assert isinstance(doc["trace"]["traceEvents"], list)
    # atomic: no temp residue next to the dump
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]
    assert flightrec.last_dump_path() == p


def test_dump_span_lands_in_ring_without_profiler(tmp_path):
    """Satellite: MXNET_TELEMETRY=1 and NO running profiler — span
    completions still reach the flight-recorder ring."""
    prev = telemetry.enable(True)
    try:
        assert not telemetry.recording()    # chrome sink stays gated
        with telemetry.span("bb.ringonly"):
            pass
    finally:
        telemetry.enable(prev)
    spans = [e for e in flightrec.ring_snapshot()
             if e["kind"] == "span" and e["name"] == "bb.ringonly"]
    assert spans and spans[0]["dur_us"] >= 0 and spans[0]["trace"]


def test_dump_trigger_sys_excepthook(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    assert flightrec.install_crash_hooks(sigusr2=False)
    try:
        try:
            raise RuntimeError("boom-main")
        except RuntimeError as e:
            sys.excepthook(type(e), e, e.__traceback__)
    finally:
        flightrec.uninstall_crash_hooks()
    p = flightrec.last_dump_path()
    assert p and os.path.dirname(p) == str(tmp_path)
    doc = _load(p)
    assert doc["reason"] == "excepthook"
    assert doc["exception"]["type"] == "RuntimeError"
    assert "boom-main" in doc["exception"]["message"]


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dump_trigger_threading_excepthook(tmp_path, monkeypatch):
    """A raising worker thread leaves a dump via threading.excepthook
    (the real hook path, not a simulation)."""
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    assert flightrec.install_crash_hooks(sigusr2=False)
    try:
        t = threading.Thread(
            target=lambda: (_ for _ in ()).throw(ValueError("boom-bg")),
            name="BBWorker")
        t.start()
        t.join()
    finally:
        flightrec.uninstall_crash_hooks()
    p = flightrec.last_dump_path()
    assert p is not None
    doc = _load(p)
    assert doc["reason"] == "threading.excepthook"
    assert doc["exception"]["type"] == "ValueError"
    assert any(e["kind"] == "fault" and e["name"] == "uncaught"
               and e.get("where") == "BBWorker"
               for e in doc["events"])


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="no SIGUSR2 on this platform")
def test_dump_trigger_sigusr2(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    assert flightrec.install_crash_hooks()
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        while flightrec.last_dump_path() is None and \
                time.monotonic() < deadline:
            time.sleep(0.02)        # handler defers to a thread
    finally:
        flightrec.uninstall_crash_hooks()
    p = flightrec.last_dump_path()
    assert p is not None
    assert _load(p)["reason"] == "sigusr2"


def test_crash_dump_throttled_per_reason(tmp_path, monkeypatch):
    """A persistently-failing loop (the dispatcher backstop fires every
    ~10ms) must not fill the disk: same-reason crash dumps are
    throttled; distinct reasons still dump."""
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    assert flightrec.crash_dump("loopy") is not None
    assert flightrec.crash_dump("loopy") is None        # throttled
    assert flightrec.crash_dump("other-reason") is not None
    # the explicit API stays unthrottled (operator-requested)
    assert telemetry.dump_blackbox(path=str(tmp_path),
                                   reason="loopy") is not None


def test_hbm_sample_gated_when_disabled(monkeypatch):
    """MXNET_BLACKBOX=0 means one bool read per hook — no device
    memory_stats queries, no mem.* counters."""
    import incubator_mxnet_tpu.storage as storage

    def _boom(*a, **k):
        raise AssertionError("memory_events called while disarmed")

    monkeypatch.setattr(storage, "memory_events", _boom)
    flightrec.enable(False)
    assert flightrec.hbm_sample() == []


def test_crash_hooks_chain_and_idempotent():
    seen = {}
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.setdefault("called", True)
    try:
        assert flightrec.install_crash_hooks(sigusr2=False)
        # second excepthook install is a no-op (SIGUSR2 arms
        # independently, so keep it out of this idempotence check)
        assert not flightrec.install_crash_hooks(sigusr2=False)
        try:
            raise KeyError("chained")
        except KeyError as e:
            sys.excepthook(type(e), e, None)
        assert seen.get("called")       # previous hook still ran
    finally:
        flightrec.uninstall_crash_hooks()
        sys.excepthook = prev_hook


# ---------------------------------------------------------------------------
# cost registry
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, cost, mem=None):
        self._cost = cost
        self._mem = mem

    def cost_analysis(self):
        return self._cost

    def memory_analysis(self):
        return self._mem


class _FakeMem:
    argument_size_in_bytes = 4096
    output_size_in_bytes = 1024
    temp_size_in_bytes = 512
    alias_size_in_bytes = 256
    generated_code_size_in_bytes = 128


def test_cost_registry_roundtrip_with_fake_analysis():
    key = costs.note_executable(
        "train", "fake.step",
        compiled=_FakeCompiled({"flops": 1e9, "bytes accessed": 2e6},
                               _FakeMem()),
        compile_s=1.5)
    for _ in range(3):
        costs.invoke(key)
    rows = costs.table()
    assert len(rows) == 1
    r = rows[0]
    assert r["flops"] == 1e9 and r["bytes_accessed"] == 2e6
    assert r["invocations"] == 3 and r["cum_flops"] == 3e9
    assert r["donated_bytes"] == 256 and r["output_bytes"] == 1024
    assert r["compile_wall_s"] == 1.5 and r["analyzed"]
    t = costs.totals()
    assert t["executables"] == 1 and t["invocations"] == 3
    assert t["cum_flops"] == 3e9


def test_cost_registry_none_analysis_guard():
    """A backend whose cost_analysis() returns None: the row degrades
    to zeros — no event, no crash."""
    key = costs.note_executable("serve", "none.bucket",
                                compiled=_FakeCompiled(None, None))
    costs.invoke(key)
    r = costs.table()[0]
    assert r["flops"] == 0.0 and not r["analyzed"]
    assert r["invocations"] == 1
    assert costs.totals()["executables"] == 1


def test_metered_jit_registers_and_counts():
    import jax.numpy as jnp
    f = costs.metered_jit(lambda a, b: a @ b, kind="test", label="mm")
    x = jnp.ones((16, 16), jnp.float32)
    f(x, x)
    f(x, x)
    f(jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32))
    rows = [r for r in costs.table() if r["kind"] == "test"]
    assert len(rows) == 2               # one row per input signature
    by_calls = sorted(rows, key=lambda r: r["invocations"])
    assert by_calls[0]["invocations"] == 1
    assert by_calls[1]["invocations"] == 2
    # CPU XLA resolves real analysis through the lazy resolver
    assert by_calls[1]["flops"] > 0
    assert by_calls[1]["compile_wall_s"] > 0


def test_metered_jit_disabled_recorder_bypasses():
    import jax.numpy as jnp
    flightrec.enable(False)
    f = costs.metered_jit(lambda a: a + 1, kind="test", label="inc")
    assert float(f(jnp.ones(())).sum()) == 2.0
    assert costs.table() == []          # nothing registered while off


# ---------------------------------------------------------------------------
# storage mem.* series
# ---------------------------------------------------------------------------

def test_memory_events_none_guard_and_fake_stats():
    from incubator_mxnet_tpu import storage
    from incubator_mxnet_tpu.monitor import EventCounters

    class _Dev:
        platform, id = "fake", 0

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            if isinstance(self._stats, Exception):
                raise self._stats
            return self._stats

    c = EventCounters()
    # None / raising backends: no event, no crash
    assert storage.memory_events([_Dev(None)], counters=c) == []
    assert storage.memory_events([_Dev(RuntimeError("nope"))],
                                 counters=c) == []
    assert c.snapshot() == {}
    out = storage.memory_events(
        [_Dev({"bytes_in_use": 1000, "peak_bytes_in_use": 2000,
               "bytes_limit": 4000})], counters=c)
    assert out == [{"device": "fake:0", "bytes_in_use": 1000,
                    "peak_bytes": 2000, "bytes_limit": 4000}]
    assert c.snapshot()["mem.bytes_in_use.n"] == 1
    assert c.percentiles("mem.peak_bytes")["p50"] == 2000


# ---------------------------------------------------------------------------
# trainer integration: the acceptance scenario
# ---------------------------------------------------------------------------

def _build_trainer(seed=7):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential(prefix="bb_")
    net.add(gluon.nn.Dense(16, in_units=8, activation="relu",
                           prefix="bb_d1_"),
            gluon.nn.Dense(4, in_units=16, prefix="bb_d2_"))
    net.initialize(force_reinit=True)
    net(nd.ones((2, 8)))
    return parallel.ShardedTrainer(net, optimizer="sgd", lr=1e-2)


@pytest.mark.fault
def test_rollback_then_preemption_leaves_forensic_dump(tmp_path,
                                                       monkeypatch):
    """The ISSUE 5 acceptance path: NaN → rollback, then preemption —
    the final dump carries BOTH markers, the step timeline, a counter
    snapshot, and a cost row for the fused train-step executable, and
    the blackbox CLI summarizes it without error."""
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path / "bb"))
    rs = np.random.RandomState(0)
    xs = rs.randn(8, 8).astype(np.float32)
    ys = rs.randint(0, 4, 8)
    rt = parallel.ResilientTrainer(_build_trainer(),
                                   ckpt_dir=str(tmp_path / "ck"),
                                   rollback_after=2, seed=5,
                                   handle_sigterm=False)
    fault.install("grad_nan", steps=[1, 2], times=2)
    for i in range(3):
        rt.step(xs, ys)                 # step 2 triggers the rollback
    assert events.get("resilience.rollback") >= 1
    rt.request_preemption()
    with pytest.raises(fault.Preempted):
        rt.step(xs, ys)

    dumps = sorted(os.listdir(tmp_path / "bb"))
    assert dumps                        # rollback + preemption dumps
    doc = _load(str(tmp_path / "bb" / dumps[-1]))
    kinds = [e["kind"] for e in doc["events"]]
    assert "rollback" in kinds and "preempt" in kinds
    assert "step" in kinds and "fault" in kinds and "ckpt" in kinds
    assert doc["counters"]["resilience.rollback"] >= 1
    assert doc["counters"]["resilience.preemption"] >= 1
    train_rows = [r for r in doc["costs"]["rows"]
                  if r["label"].startswith("resilient.gstep")]
    assert train_rows and train_rows[0]["invocations"] >= 3
    assert train_rows[0]["flops"] > 0   # CPU XLA resolves analysis

    # CLI summarizes without error and points at the right cause
    from incubator_mxnet_tpu.tools import blackbox as bbcli
    rc = bbcli.main([str(tmp_path / "bb" / dumps[-1])])
    assert rc == 0
    assert "preemption" in bbcli.suspected_cause(doc)


def test_serving_dispatcher_backstop_dumps(tmp_path, monkeypatch):
    """The dispatcher backstop (an exception escaping _collect) leaves
    a dump and keeps the loop alive — exercised against the static
    loop, no model needed."""
    monkeypatch.setenv("MXNET_BLACKBOX_DIR", str(tmp_path))
    import weakref
    from incubator_mxnet_tpu.serving.engine import InferenceEngine

    class _FakeEngine:
        def __init__(self):
            self.calls = 0

        def _collect(self):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("backstop-me")
            return None                 # retire the loop

        def _execute(self, reqs):
            raise AssertionError("unreachable")

    eng = _FakeEngine()
    before = events.get("serve.dispatcher_errors")
    InferenceEngine._dispatch_loop(weakref.ref(eng))
    assert eng.calls == 2
    assert events.get("serve.dispatcher_errors") == before + 1
    p = flightrec.last_dump_path()
    assert p is not None
    doc = _load(p)
    assert doc["reason"] == "serve.dispatcher"
    assert doc["exception"]["type"] == "RuntimeError"


def test_blackbox_cli_golden(tmp_path):
    """CLI on a golden dump: all sections render, --trace extracts the
    chrome view, bad input fails cleanly."""
    flightrec.record("step", "resilient", step=1, loss=0.5, ok=True,
                     us=1000)
    flightrec.record("feed", "stall", us=5000)
    key = costs.note_executable(
        "train", "golden.step",
        compiled=_FakeCompiled({"flops": 5e8, "bytes accessed": 1e6},
                               _FakeMem()))
    costs.invoke(key, 7)
    p = telemetry.dump_blackbox(path=str(tmp_path / "g.json"),
                                reason="golden")
    from incubator_mxnet_tpu.tools import blackbox as bbcli
    out = bbcli.render(bbcli.load_dump(p))
    for frag in ("blackbox — reason=golden", "timeline",
                 "golden.step", "suspected cause:"):
        assert frag in out, frag
    tr = str(tmp_path / "g.trace.json")
    assert bbcli.main([p, "--trace", tr]) == 0
    assert json.load(open(tr))["traceEvents"] is not None
    assert bbcli.main([str(tmp_path / "missing.json")]) == 1


def test_exporter_carries_cost_families():
    """MetricsExporter renders the cost registry in both formats."""
    key = costs.note_executable(
        "serve", "exp.bucket",
        compiled=_FakeCompiled({"flops": 1e6, "bytes accessed": 2e3}))
    costs.invoke(key)
    exp = telemetry.MetricsExporter()
    txt = exp.prometheus_text()
    # the registry key rides as a label so two same-named executables
    # (two engines/trainers in one process) never collide into a
    # duplicate Prometheus series
    assert 'mxnet_executable_flops{kind="serve",label="exp.bucket",' \
        'key="%d"} 1000000' % key in txt
    assert 'mxnet_executable_invocations{kind="serve",' \
        'label="exp.bucket",key="%d"} 1' % key in txt
    j = exp.json_dict()
    assert j["costs"]["totals"]["executables"] == 1

    # teletop renders the cost block from the same snapshot
    from incubator_mxnet_tpu.tools import teletop
    out = teletop.render(json.loads(exp.json_text()))
    assert "exp.bucket" in out


@pytest.mark.slow
def test_recorder_overhead_gate():
    """tools/check_overhead.py: recorder-on vs recorder-off synthetic
    loop stays under the 2%% budget (slow: excluded from tier-1)."""
    script = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "tools", "check_overhead.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.abspath(script), "--steps", "120",
         "--repeats", "2"],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
