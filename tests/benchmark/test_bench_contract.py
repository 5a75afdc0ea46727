"""BENCHMARK.json against the contract, and the result line's key set."""
import json
import os
import re

import pytest

from benchpaths import BENCH, REPO, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench_json["run_seconds"] <= 51
    assert 1 <= len(bench_json["paths"]) <= 16
    assert len(json.dumps(bench_json)) < 64 * 1024
    cells = len(bench_json["workloads"])
    assert (2 + 14 * 24) * (bench_json["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24


def test_names_units_and_lines(bench_json):
    seen = set()
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench_json[sec]:
            assert NAME.match(e["name"]), e["name"]
            assert (sec, e["name"]) not in seen
            seen.add((sec, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e and sec != "end_to_end" and not (sec == "per_layer" and k == "source"):
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
    for e in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
    for e in bench_json["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in bench_json["per_layer"]:
        assert set(e) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for w in bench_json["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for c in bench_json["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for word in bench_json["command"]:
        assert not word.startswith("/") and ".." not in word


def test_cells_metrics_and_files_hang_together(bench_json):
    cells = {w["name"]: w for w in bench_json["workloads"]}
    configs = {c["name"]: c for c in bench_json["configs"]}
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert {w["config"] for w in cells.values()} == set(configs)
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for c in configs.values():
        path = os.path.join(REPO, c["file"])
        assert os.path.isfile(path)
        assert any(c["file"].startswith(p + "/") for p in bench_json["paths"])
        cfg = json.load(open(path))
        for part in ("builder", "reference"):
            sub = "configs" if part == "builder" else "reference"
            assert os.path.isfile(os.path.join(BENCH, sub, cfg[part] + ".py"))
    for name, w in cells.items():
        mix = json.load(open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "drivers", mix["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "generators", mix["generator"] + ".py"))
        mine = [m for m in e2e.values() if reports(m, name)]
        assert len(mine) >= 2                       # setup_s and one more
        layers = [m for m in bench_json["per_layer"] if name in m["workloads"]]
        assert layers
        assert any("mfu" in m["name"].split(".") for m in layers), name
    for m in bench_json["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
        spec = json.load(open(os.path.join(BENCH, "metrics", m["name"] + ".json")))
        assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    by_layer = {}
    for m in bench_json["per_layer"]:
        by_layer.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values()), by_layer


def test_files_under_paths_are_named_from_a_names_characters(bench_json):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench_json["paths"]:
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_result_line_key_set_and_order():
    harness = load("harness")
    line = json.loads(harness.result_line(
        True, 400, 0, {"setup_s": {"value": 30.25, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1}, {"gap_max": {"value": 0.1, "limit": 0.2}},
        {"device_ops": [], "idle_gaps": []}))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "breakdown", "compared"]
    line = json.loads(harness.result_line(False, 1, 1, {}, {}, {}))
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]


def test_unknown_device_kind_is_an_error():
    harness = load("harness")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks_for("cpu")


def test_quantile_counts_failures_as_infinite():
    harness = load("harness")
    assert harness.quantile([1.0, 2.0, 3.0], 0.5) == 2.0
    assert harness.quantile([1.0, 2.0, 3.0, float("inf")], 0.95) == float("inf")
    assert harness.quantile([1.0] * 99 + [float("inf")], 0.5) == 1.0


def test_no_chip_no_result(monkeypatch, capsys):
    harness = load("harness")
    with pytest.raises(harness.BenchError):
        harness.require_chips(1)            # this process is held to the CPU
    with pytest.raises(harness.BenchError):
        harness.require_chips(64, allow_cpu=True)
