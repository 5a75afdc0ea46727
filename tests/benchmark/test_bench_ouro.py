"""The `ouro_2_6b` configuration's benchmark parts at a tiny size on the CPU:
its cell added as files only (`data/ouro_root`) rehearses and is correct,
the int8 control is not; the configuration keeps every published width and
lists exactly its one cut; the reference's `spec` and the builder's byte and
FLOP counts match a hand count at the published widths; the cell and its
metrics are found by name in `BENCHMARK.json`; each new metric file reads a
planted record."""
import json
import math
import os

import pytest

from benchpaths import BENCH, DATA, REPO, compared as _compared, load

ROOT = os.path.join(DATA, "ouro_root")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ouro_2_6b.reasoning_backlog"
NEW = {"loop.cache_share.reasoning": "counter_ratio",
       "loop.passes_per_token.reasoning": "counter_ratio",
       "kernel.prefill_roofline.reasoning": "prefill_roofline",
       "kernel.attn_step_roofline.reasoning": "op_roofline",
       "modelstep.decode_proj_share": "part_share",
       "modelstep.decode_ffn_share": "part_share",
       "modelstep.prefill_ffn_share": "part_share",
       # the five shares that other cells report under the plain name: their
       # lists are asserted letter for letter by test_bench_part_share.py,
       # which only a `benchmark` PR may mend, so this cell has its own
       "modelstep.prefill_attn_share.reasoning": "part_share",
       "modelstep.prefill_proj_share.reasoning": "part_share",
       "modelstep.prefill_unscoped_share.reasoning": "part_share",
       "modelstep.decode_attn_share.reasoning": "part_share",
       "modelstep.decode_unscoped_share.reasoning": "part_share"}
JOINED = ["serve_tokens_per_s", "engine.slot_occupancy.backlog",
          "modelstep.mfu.backlog", "modelstep.decode_device_ms.backlog",
          "kernel.decode_roofline", "compile.recompiles_in_window",
          "compile.cache_entries_added", "compile.setup_compile_s",
          "engine.host_busy_share.backlog",
          "engine.admit_device_share.backlog"]


def test_reasoning_tiny_rehearses_correct_and_the_control_is_not(run_cell):
    from incubator_mxnet_tpu.monitor import events
    names = ("loop.passes", "loop.tokens", "loop.exit_pass",
             "gen.donation_copy")
    before = [events.get(n) or 0 for n in names]
    # a seed past 2**31, as the driver's are (the later --seed wins)
    line, err = run_cell(ROOT, "ouro_tiny.reasoning_tiny", "--control",
                         "int8", "--seed", "3000000011")
    passes, tokens, exits, copies = [
        (events.get(n) or 0) - b for n, b in zip(names, before)]
    # three passes a token, the last one read out, the cache never copied
    assert tokens > 0 and passes == 3 * tokens and exits == 2 * tokens
    assert copies == 0
    assert line["correct"] is True
    assert line["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    c = _compared(err)
    assert c["length_faults"][0] == 0
    assert c["gap_max"][0] <= c["gap_max"][1]
    assert c["gap_mean"][0] <= c["gap_mean"][1]
    assert c["control.gap_max"][0] > 3 * c["gap_max"][1]
    assert c["control.gap_mean"][0] > 3 * c["gap_mean"][1]
    assert c["tokens_compared"][0] >= 30


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "ouro_2_6b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths(published, bench_json):
    """Every key of the catalog's config under its own name; only the depth
    differs, stated with the published count and the deployment beside
    it."""
    cfg = published
    entry = next(c for c in bench_json["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == ["num_hidden_layers"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/ouro_2_6b.json"
    assert len(entry["why"]) <= 200
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["num_hidden_layers"] == 24
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 16, "head_dim": 128,
              "intermediate_size": 5632, "vocab_size": 49152,
              "total_ut_steps": 4, "early_exit_threshold": 1,
              "rope_theta": 1000000, "rms_norm_eps": 1e-06,
              "max_position_embeddings": 65536, "rope_scaling": None,
              "sliding_window": None, "tie_word_embeddings": False}
    assert {k: cfg[k] for k in widths} == widths
    for key in ("norms", "final_norm", "exit_gate", "all_passes", "cache",
                "biases", "rotary", "special_tokens", "dtype"):
        assert cfg["assumed"][key]
    assert len(cfg["departures"]) == 3
    assert cfg["control_precision"] == "int8"
    if os.path.isfile(CATALOG):             # the catalog row, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differs == ["num_hidden_layers"]


def test_the_cell_and_its_metrics_are_found_by_name(published, bench_json):
    cells = {w["name"]: w for w in bench_json["workloads"]}
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro_2_6b", "reasoning_backlog", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m
              for m in bench_json["end_to_end"] + bench_json["per_layer"]}
    for name, reader in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s" and "layer" in m
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == reader
        assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    for name in JOINED:
        assert CELL in listed[name]["workloads"]
    # metrics of one prefix name one layer
    assert len({m["layer"] for n, m in listed.items()
                if n.split(".")[0] == "loop"}) == 1
    # the traffic: contexts that end at 240-480, inside a slot's rows
    with open(os.path.join(BENCH, "traffic", "reasoning_backlog.json")) as f:
        mix = json.load(f)
    assert mix["arrivals"] == "backlog" and mix["backlog"]["headroom"] == 3.0
    assert (mix["source_len"]["min"], mix["source_len"]["max"]) == (96, 192)
    assert mix["new_tokens"]["ratio"] == 1.5
    sv = published["serving"]
    assert mix["source_len"]["max"] + mix["new_tokens"]["max"] <= \
        sv["max_len"] == 480
    assert max(sv["prompt_buckets"]) == mix["source_len"]["max"]
    # two probes' rows in one tick (serving/generation.py `_ADMIT_BYTES`)
    z = load("ouro_2_6b", "configs").sizes(published)
    assert 2 * sv["max_len"] * z["bodies"] * z["row"] <= 768 << 20 \
        < 2 * 512 * z["bodies"] * z["row"] + 1


@pytest.mark.parametrize("layers,total", [(24, 1434652673),
                                          (48, 2667974657)])
def test_spec_sums_to_the_models_parameters(published, layers, total):
    ref = load("ouro_2_6b", "reference")
    cfg = dict(published, num_hidden_layers=layers)
    assert sum(math.prod(s) for _, s, _ in ref.spec(cfg)) == total
    kinds = {n: k for n, _, k in ref.spec(cfg)}
    # nothing is left constant: every scale 1 + 0.1 N, the gate's bias drawn
    assert {kinds[n] for n in ("ln1", "ln2", "ln3", "ln4", "norm")} == \
        {"gamma"} and kinds["gate.b"] == "bias"


def test_builder_counts_match_a_hand_count(published):
    cfg = published
    b = load("ouro_2_6b", "configs")
    D, H, d, F, V, L, R = 2048, 16, 128, 5632, 49152, 24, 4
    layer = 4 * D * D + 3 * D * F
    assert layer == 51380224
    # the layers' weights cross the bus once a PASS: the factor R
    assert b.decode_weight_bytes(cfg) == 2 * (R * L * layer + D * V)
    assert round(b.decode_weight_bytes(cfg) / 1e9, 2) == 10.07
    once = 2 * (L * layer + D * V)
    assert b.decode_weight_bytes(cfg) - once == 3 * 2 * L * layer
    # a slot at context 120: 96 (pass, layer) x 120 rows of K and V
    assert b.decode_state_bytes(cfg, 100, 20) == 96 * 120 * 8192
    assert 96 * 8192 == 768 << 10                       # 768 KiB a token
    row = 4 * H * d                 # a head scores its 128 and sums its 128

    def step(c):
        return R * L * (2 * layer + row * c) + 2 * D * V
    assert b.decode_flops(cfg, 100, 20) == step(120)
    assert b.request_flops(cfg, 100, 5, first=2) == \
        step(102) + step(103) + step(104)
    pre = sum(R * L * (2 * layer + row * c) for c in range(1, 151))
    assert b.prefill_flops(cfg, 150) == pre
    assert b.request_flops(cfg, 150, 2) == pre + step(150) + step(151)
    assert b.prefill_bytes(cfg, 150) == \
        2 * R * L * layer + 2 * 150 * D + 96 * 150 * 8192
    # the step's attention kernel, for 24 live slots at the mean context
    mean = cfg["serving"]["mean_context"]
    assert b.attn_step_bytes(cfg, 24) == 24 * 96 * mean * 8192
    assert b.attn_step_flops(cfg, 24) == 24 * 96 * mean * row
    # a cached row: 1 FLOP/B, far under the v5e's ridge: the memory's rate
    assert row / 8192 == 1.0


def _planted(counters):
    from incubator_mxnet_tpu.monitor import events
    for name, value in counters.items():
        events.incr(name, value - (events.get(name) or 0))


def test_each_new_metric_file_reads_a_planted_record(published):
    """The two counter ratios from planted counters, the kernel's roofline
    from a planted trace; nothing to read where the program has no such
    counter or kernel (a parent commit)."""
    from incubator_mxnet_tpu.monitor import events
    spec = {}
    for name in NEW:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec[name] = json.load(f)
    ratio = load("counter_ratio", "readers")
    record = {"config": published}
    if not events.get("loop.tokens"):       # a program without the counter
        assert ratio.read(spec["loop.passes_per_token.reasoning"], record,
                          {}) is None
    _planted({"gen.cache_kib": 3200, "gen.step_kib": 10000,
              "loop.passes": 4000, "loop.tokens": 1000})
    assert ratio.read(spec["loop.cache_share.reasoning"], record, {}) == 32.0
    assert ratio.read(spec["loop.passes_per_token.reasoning"], record,
                      {}) == 4.0
    for name, module, part in (
            ("modelstep.decode_proj_share", "gen_decode", "proj"),
            ("modelstep.decode_ffn_share", "gen_decode", "ffn"),
            ("modelstep.prefill_ffn_share", "gen_prefill", "ffn")):
        assert spec[name] == {"reader": "part_share", "module": module,
                              "part": part}
    # the attention kernel's roofline: 10 runs of the step in the trace, the
    # kernel's events inside them, 20 of 24 streams live at the close
    reader = load("op_roofline", "readers")
    b = load("ouro_2_6b", "configs")
    peaks = load("harness").peaks_for("TPU v5 lite")
    step = "jit__traced_gen_decode(3)"
    nan = float("nan")
    ops = {"ragged_decode_attention.1": [(0.1 * i, 0.008, step)
                                         for i in range(10)],
           "fusion.7": [(0.1 * i + 0.01, 0.01, step) for i in range(10)]}
    trace = {"window_s": 3.0, "busy_s": 1.0, "devices": {"/device:TPU:0": {
        "busy_s": 1.0, "ops": ops,
        "modules": {step: [(0.1 * i, 0.03) for i in range(10)]}}}}
    record = {"kind": "serve", "trace": trace, "builder": b,
              "config": published,
              "tokens_close": {i: 5 for i in range(24)},
              "tokens_end": {}, "done": [nan] * 20 + [1.0] * 4}
    result = {"device": {"kind": "TPU v5 lite"}}
    got = reader.read(spec["kernel.attn_step_roofline.reasoning"], record,
                      result)
    need = 10 * b.attn_step_bytes(published, 20) / peaks["hbm_bytes_per_s"]
    assert abs(got - 100 * need / 0.08) < 1e-9
    # bound by the rows' bytes, not by their FLOPs
    assert b.attn_step_bytes(published, 20) / peaks["hbm_bytes_per_s"] > \
        b.attn_step_flops(published, 20) / peaks["bf16_flops_per_s"]
    trace["devices"]["/device:TPU:0"]["ops"].pop("ragged_decode_attention.1")
    assert reader.read(spec["kernel.attn_step_roofline.reasoning"], record,
                       result) is None             # a step with no kernel
