"""Whole runs at a tiny size on the CPU (`--rehearse`: no result line, no
device metric): a cell added as files only is found and runs; the program
reproduces the plain reference; the control and each planted fault come
out as not correct."""
import json
import os
import shutil

import pytest

from benchpaths import BENCH, DATA, compared as _compared, load

ROOT = os.path.join(DATA, "root")


def test_a_cell_added_as_files_only_is_found_and_runs(run_cell, tmp_path):
    """A throw-away root: its own BENCHMARK.json, configuration, mix and a
    per-layer metric with a reader of its own; nothing under benchmark/ is
    edited, every other part is found there by name."""
    root = str(tmp_path / "root")
    shutil.copytree(ROOT, root)
    harness = load("harness")
    bench = harness.load_benchmark(root)
    cell, config, traffic, base = harness.find_cell(bench, "nmt_tiny.online_tiny", root)
    assert config["d_model"] == 64 and traffic["arrivals"] == "open_loop"
    assert base == os.path.join(root, "benchmark")
    assert not os.path.exists(os.path.join(BENCH, "readers", "tiny_tokens.py"))
    line, err = run_cell(root, "nmt_tiny.online_tiny", "--trace", "0")
    assert line["correct"] is True
    assert line["end_to_end"] == ["setup_s", "tpot_p95_ms", "ttft_p50_ms"]
    c = _compared(err)
    assert c["gap_max"][0] <= c["gap_max"][1] and c["length_faults"][0] == 0
    assert c["tokens_compared"][0] >= 30
    # the reader that came with the cell is found by the metric's name
    spec = json.load(open(os.path.join(base, "metrics", "tiny.tokens_seen.json")))
    reader = harness.load_module("readers", spec["reader"], base)
    assert reader.read(spec, {"tokens_end": {0: 3, 1: 4}}, None) == 7.0
    with pytest.raises(harness.BenchError):
        harness.find_cell(bench, "nmt_tiny.nothing", root)


def test_backlog_cell_runs_and_counts_tokens(run_cell):
    line, err = run_cell(ROOT, "nmt_tiny.backlog_tiny")
    assert line["correct"] is True
    assert line["end_to_end"] == ["serve_tokens_per_s", "setup_s"]


def test_served_control_int8_is_not_correct(run_cell):
    """The reference in int8 put in the program's place: the token it puts
    first lies below the float32 best by more than the limit."""
    _, err = run_cell(ROOT, "nmt_tiny.online_tiny", "--control", "int8")
    c = _compared(err)
    assert c["gap_max"][0] <= c["gap_max"][1]
    assert c["control.gap_max"][0] > 3 * c["gap_max"][1]


def test_a_token_altered_is_not_correct(run_cell):
    line, err = run_cell(ROOT, "nmt_token_altered.online_tiny")
    assert line["correct"] is False
    c = _compared(err)
    assert c["gap_max"][0] > c["gap_max"][1]


def test_training_follows_the_reference_and_control_and_fault_do_not(run_cell):
    line, err = run_cell(ROOT, "bert_tiny.pretrain_tiny", "--control", "int8",
                         "--fault", "half_batch")
    assert line["correct"] is True
    c = _compared(err)
    for k in ("loss_step1", "loss_step3", "grad_norm_worst", "delta_norm_worst"):
        assert c[k][0] <= c[k][1]
    assert any(c["control." + k][0] > 3 * c[k][1]
               for k in ("loss_step1", "grad_norm_worst", "delta_norm_worst"))
    assert c["fault.grad_norm_worst"][0] > 10 * c["grad_norm_worst"][1]


@pytest.mark.parametrize("cell,number", [
    ("bert_half_batch.pretrain_tiny", "grad_norm_worst"),
    ("bert_state_unchanged.pretrain_tiny", "delta_norm_worst")])
def test_a_broken_training_path_is_not_correct(run_cell, cell, number):
    line, err = run_cell(ROOT, cell)
    assert line["correct"] is False
    c = _compared(err)
    assert c[number][0] > c[number][1]
    if "state_unchanged" in cell:
        assert c[number][0] == pytest.approx(1.0, abs=1e-3)
