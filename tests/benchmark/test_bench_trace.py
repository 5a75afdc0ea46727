"""The reduction from a profiler trace to numbers, on a small trace recorded
on the v5e (three runs of one jitted matmul, two of another) and on planes
made by hand."""
import os

import pytest

from benchpaths import DATA, load

TRACE = os.path.join(DATA, "small_v5e.xplane.pb")


def test_recorded_v5e_trace():
    tr = load("trace_reduce")
    red = tr.reduce(TRACE)
    assert list(red["devices"]) == ["/device:TPU:0"]
    counts = tr.module_counts(red)
    assert sorted(counts.values()) == [2, 3]
    assert all(name.startswith("jit__lambda(") for name in counts)
    dev = red["devices"]["/device:TPU:0"]
    # every op belongs to the module that was running when it started
    for name, runs in dev["ops"].items():
        assert not name.startswith("%") and " = " not in name
        assert all(owner in counts for _, _, owner in runs)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == pytest.approx(4.88e-5, rel=0.05)
    mod3 = next(m for m, c in counts.items() if c == 3)
    assert len(tr.module_runs(red, mod3)) == 3
    assert red["device_ops"][0][0].startswith("module jit__lambda(")
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    roles = load("roles")
    assert roles.by_counts(counts, {"step": 3}) == {"step": [mod3]}
    with pytest.raises(ValueError):
        roles.by_counts(counts, {"step": 7})


def _planes():
    ms = 1e6        # ns
    dev = {"XLA Modules": [("jit_a(1)", 0 * ms, 10 * ms), ("jit_b(2)", 20 * ms, 10 * ms),
                           ("jit_a(1)", 40 * ms, 10 * ms)],
           "XLA Ops": [("%fusion.1 = f32[8] fusion(...)", 0 * ms, 4 * ms),
                       ("%all-reduce.2 = f32[8] all-reduce(...)", 2 * ms, 6 * ms),
                       ("%copy.3 = f32[8] copy(...)", 20 * ms, 10 * ms),
                       ("%fusion.1 = f32[8] fusion(...)", 40 * ms, 10 * ms)]}
    host = {"main": [("Sleep", 8 * ms, 12 * ms), ("Dispatch", 30 * ms, 10 * ms),
                     ("Inner", 33 * ms, 4 * ms)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev), ("/device:TPU:1", dev)]


def test_union_gaps_and_attribution_by_hand():
    tr = load("trace_reduce")
    red = tr.reduce_planes(_planes())
    assert red["window_s"] == pytest.approx(0.050)
    # ops cover [0,8] (two overlapping), [20,30], [40,50]: 28 ms busy
    assert red["busy_s"] == pytest.approx(0.028)
    assert set(red["devices"]) == {"/device:TPU:0", "/device:TPU:1"}
    d0 = red["devices"]["/device:TPU:0"]
    assert d0["ops"]["all-reduce.2"][0][2] == "jit_a(1)"
    assert d0["ops"]["copy.3"][0][2] == "jit_b(2)"
    assert tr.module_counts(red) == {"jit_a(1)": 2, "jit_b(2)": 1}
    # idle gaps [8,20] and [30,40], named by the innermost host event over
    # their middle: 14 ms -> Sleep, 35 ms -> Inner (inside Dispatch)
    assert dict(map(tuple, red["idle_gaps"])) == pytest.approx(
        {"Sleep": 0.012, "Inner": 0.010})
    top = dict(map(tuple, red["device_ops"]))
    assert top["module jit_a(1)"] == pytest.approx(0.020)
    assert tr._union([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert tr._gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_exposed_collective_reader_by_hand():
    tr = load("trace_reduce")
    red = tr.reduce_planes(_planes())
    reader = load("collective_exposed", "readers")
    spec = {"collective_pattern": "all-reduce"}
    record = {"trace": red, "roles": {"step": ["jit_a(1)"]}}
    # all-reduce [2,8]; compute covers [0,4], [20,30], [40,50]: exposed [4,8]
    # = 4 ms over 20 ms of step time
    assert reader.read(spec, record, None) == pytest.approx(20.0)
    assert reader.read(spec, {"trace": None, "roles": None}, None) is None
    share = load("module_share", "readers")
    assert share.read({"role": "step"}, record, None) == pytest.approx(
        100 * 0.020 / 0.028)
    assert share.read({"role": "decode"}, record, None) is None
    med = load("module_median_ms", "readers")
    assert med.read({"role": "step"}, record, None) == pytest.approx(10.0)


def test_no_device_plane_is_an_error():
    tr = load("trace_reduce")
    with pytest.raises(ValueError):
        tr.reduce_planes([("/host:CPU", {"main": [("x", 0.0, 1.0)]})])
