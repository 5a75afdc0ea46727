"""`attn.rows_needed_share.code`: the rows `laguna_xs2`'s full layers attend
from over the rows their decode attention covers.  Declared in
`BENCHMARK.json` for its one cell, in the kernels' layer; its metric file
reads a planted record, and nothing where the program has no such counter
(a parent commit)."""
import json
import os

import pytest

from benchpaths import BENCH, load

NAME = "attn.rows_needed_share.code"
KERNELS = "kernels (ops/attention.py, XLA decode)"


def test_the_metric_is_declared_for_the_cell(bench_json):
    entry = {m["name"]: m for m in bench_json["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": KERNELS,
                     "moves": "serve_tokens_per_s",
                     "workloads": ["laguna_xs2.code_backlog"]}
    # the other metrics of its prefix are the kernels' too
    assert {m["layer"] for m in bench_json["per_layer"]
            if m["name"].split(".")[0] == "attn"} == {KERNELS}


def test_the_metric_file_reads_a_planted_record(monkeypatch):
    from incubator_mxnet_tpu.monitor import events
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as f:
        spec = json.load(f)
    assert spec == {"reader": "counter_ratio",
                    "numerator": "gen.attn_context",
                    "denominator": "gen.attn_rows_read", "times": 100.0}
    ratio = load("counter_ratio", "readers")
    for name, value in {"gen.attn_context": 27000,
                        "gen.attn_rows_read": 27648}.items():
        events.incr(name, value - (events.get(name) or 0))
    assert ratio.read(spec, {}, {}) == pytest.approx(97.65625)
    # a program without the counter
    monkeypatch.setattr(events, "get", lambda name: None)
    assert ratio.read(spec, {}, {}) is None
