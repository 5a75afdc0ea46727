"""The `qwen3_next_80b_a3b` configuration's benchmark parts at a tiny size on
the CPU: its cell added as files only (`data/qwen3_next_root`) rehearses and
is correct, the int8 control is not; the configuration keeps every published
width and lists exactly its three cuts; the builder's byte and FLOP counts
match a hand count at the published widths; `op_roofline` reads a planted op
by its name and finds nothing to read without one."""
import json
import math
import os

import pytest

from benchpaths import BENCH, DATA, REPO, compared as _compared, load

ROOT = os.path.join(DATA, "qwen3_next_root")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_chat_tiny_rehearses_correct_and_the_control_is_not(run_cell):
    # a seed past 2**31, as the driver's are (the later --seed wins)
    line, err = run_cell(ROOT, "qwen3_next_tiny.chat_tiny", "--control",
                         "int8", "--seed", "3000000007")
    assert line["correct"] is True
    assert line["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    c = _compared(err)
    assert c["length_faults"][0] == 0
    assert c["gap_max"][0] <= c["gap_max"][1]
    assert c["gap_mean"][0] <= c["gap_mean"][1]
    assert c["control.gap_max"][0] > 3 * c["gap_max"][1]
    assert c["control.gap_mean"][0] > 3 * c["gap_mean"][1]
    assert c["tokens_compared"][0] >= 18


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths(published):
    """Every key of the catalog's config under its own name; only the three
    cuts differ, and each is stated with the published count."""
    cfg = published
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    cuts = ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["reduced"] == cuts and sorted(cfg["reduced"]) == cuts
    assert entry["source"] == cfg["source"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["num_local_experts"] == cfg["num_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 151936
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"]) == (8, 64)
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    widths = {"hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
              "num_key_value_heads": 2, "linear_key_head_dim": 128,
              "linear_value_head_dim": 128, "linear_num_key_heads": 16,
              "linear_num_value_heads": 32, "linear_conv_kernel_dim": 4,
              "moe_intermediate_size": 512, "num_experts_per_tok": 10,
              "shared_expert_intermediate_size": 512,
              "partial_rotary_factor": 0.25, "full_attention_interval": 4}
    assert {k: cfg[k] for k in widths} == widths
    if os.path.isfile(CATALOG):             # the catalog row, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert sorted(differs) == ["num_hidden_layers", "vocab_size"]
    # the cell and its metrics are declared, each new one for this cell only
    cell = "qwen3_next_80b_a3b.chat_backlog"
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    assert (entry["traffic"], entry["chips"]) == ("chat_backlog", 1)
    new = {"gdn.state_share.chat", "moe.load_imbalance.chat",
           "kernel.prefill_roofline.chat", "kernel.gdn_step_roofline.chat",
           "kernel.gdn_prefill_roofline.chat"}
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert listed[name]["workloads"] == [cell]
        assert listed[name]["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".json"))


def test_builder_counts_match_a_hand_count(published):
    cfg = published
    b = load("qwen3_next_80b_a3b", "configs")
    ref = load("qwen3_next_80b_a3b", "reference")
    n_params = sum(math.prod(s) for _, s, _ in ref.spec(cfg))
    assert round(n_params / 1e9, 3) == 1.979              # ISSUE's count
    D = 2048
    # a DeltaNet mixer by hand: q, k 2048 each, v, z 4096 each, b, a 32 each,
    # the output projection; an attention mixer: 16 x 512 of query and gate,
    # 2 x 256 of k and of v, the output projection
    gdn = D * (2 * 2048 + 2 * 4096 + 2 * 32) + 4096 * D
    attn = D * (16 * 512 + 2 * 512) + 4096 * D
    moe = D * 512 + 3 * D * 512 + D         # router, shared expert, its gate
    expert = 3 * D * 512
    assert (gdn, attn, moe, expert) == (33685504, 27262976, 4196352, 3145728)
    layers = 6 * gdn + 2 * attn + 8 * (moe + 64 * expert)
    head = D * 18992
    assert b.decode_weight_bytes(cfg) == 2 * (layers + head)
    # a slot: 6 layers x (32 x 128 x 128 float32 + 3 rows of 8192 bfloat16),
    # read and written; 2 layers x 2 x 2 x 256 bfloat16 a position of K/V
    state = 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert state == 12877824
    assert b.decode_state_bytes(cfg, 700, 300) == 2 * state + 2 * 1000 * 2048
    # one new token at context c: the mixers and the recurrence (7 operations
    # an entry of a state), 1.25 held experts and the shared one, the head
    rule = 7 * 32 * 128 * 128
    token = 6 * (2 * gdn + 2 * 8192 * 4 + rule) + 2 * 2 * attn \
        + 8 * (2 * moe + 2 * 1.25 * expert)

    def step(c):
        return token + 2 * 4 * 16 * 256 * c + 2 * head
    assert b.decode_flops(cfg, 600, 100) == step(700)
    assert b.request_flops(cfg, 600, 5, first=2) == \
        step(602) + step(603) + step(604)
    pre = sum(token + 2 * 4 * 16 * 256 * c for c in range(1, 601))
    assert b.prefill_flops(cfg, 600) == pre
    assert b.request_flops(cfg, 600, 2) == pre + step(600) + step(601)
    assert b.prefill_bytes(cfg, 600) == \
        2 * layers + 2 * 600 * D + 2 * 2 * 600 * 2048 + state
    # the kernels: a step moves each state twice and the head's vectors
    vectors = 32 * (2 * 128 + 2 * 128 + 2) * 4
    assert b.gdn_step_bytes(cfg, 256) == \
        256 * 6 * (2 * 32 * 128 * 128 * 4 + vectors)
    assert b.gdn_step_flops(cfg, 256) == 256 * 6 * rule
    assert b.gdn_prefill_flops(cfg, 600) == 600 * 6 * rule
    assert b.gdn_prefill_bytes(cfg, 600) == \
        6 * (600 * vectors + 32 * 128 * 128 * 4)


def _record(published, ops, mods):
    from incubator_mxnet_tpu.telemetry import spans
    t0 = spans._now()
    trace = {"window_s": 3.0, "busy_s": 1.0,
             "devices": {"/device:TPU:0": {"busy_s": 1.0, "modules": mods,
                                           "ops": ops}}}
    return t0, {"kind": "serve", "trace": trace,
                "builder": load("qwen3_next_80b_a3b", "configs"),
                "config": published, "t_open": t0, "t_close": t0 + 10.0,
                "tokens_close": {0: 5, 1: 7, 2: 0, 3: 9}, "tokens_end": {},
                "done": [float("nan"), float("nan"), float("nan"), 1.0]}


def test_op_roofline_reads_a_planted_op_by_its_name(published):
    from incubator_mxnet_tpu.telemetry import spans
    reader = load("op_roofline", "readers")
    harness = load("harness")
    peaks = harness.peaks_for("TPU v5 lite")
    result = {"device": {"kind": "TPU v5 lite"}}
    dec, pre = "jit__traced_gen_decode(1)", "jit__traced_gen_prefill(2)"
    mods = {dec: [(0.1, 0.02), (0.2, 0.02)], pre: [(0.3, 0.05)]}
    ops = {"gated_delta_step.3": [(0.1, 0.004, dec), (0.2, 0.004, dec),
                                  (0.5, 0.1, None)],      # outside: not read
           "gated_delta_chunk.7": [(0.3, 0.002, pre), (0.31, 0.002, pre)],
           "fusion.9": [(0.1, 0.01, dec)]}
    t0, record = _record(published, ops, mods)
    b, cfg = record["builder"], published
    step = {"op": "gated_delta_step", "module": "gen_decode",
            "work": "live_slots", "bytes": "gdn_step_bytes",
            "flops": "gdn_step_flops"}
    # two streams were live at the close (one had no token, one had ended)
    need = 2 * max(b.gdn_step_bytes(cfg, 2) / peaks["hbm_bytes_per_s"],
                   b.gdn_step_flops(cfg, 2) / peaks["bf16_flops_per_s"])
    assert abs(reader.read(step, record, result) - 100 * need / 0.008) < 1e-9
    chunk = {"op": "gated_delta_chunk", "module": "gen_prefill",
             "work": "prompts", "bytes": "gdn_prefill_bytes",
             "flops": "gdn_prefill_flops"}
    assert reader.read(chunk, record, result) is None     # no rows yet
    spans.phase_at("gen.prefill", t0 + 3.5, t0 + 3.6, 1, 0, 700)
    spans.phase_at("gen.prefill", t0 + 5.0, t0 + 5.1, 2, 0, 900)   # the one
    spans.phase_at("gen.prefill", t0 + 9.5, t0 + 9.6, 3, 0, 800)   # after
    need = max(b.gdn_prefill_bytes(cfg, 900) / peaks["hbm_bytes_per_s"],
               b.gdn_prefill_flops(cfg, 900) / peaks["bf16_flops_per_s"])
    assert abs(reader.read(chunk, record, result) - 100 * need / 0.004) < 1e-9
    # the metric files name what the reader takes
    for name, spec in (("kernel.gdn_step_roofline.chat", step),
                       ("kernel.gdn_prefill_roofline.chat", chunk)):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f) == dict(spec, reader="op_roofline")


def test_op_roofline_finds_nothing_where_no_op_bears_the_name(published):
    """A program whose kernels have no name of their own (XLA fusions; a
    parent commit), a builder without the counters, no trace: None."""
    reader = load("op_roofline", "readers")
    result = {"device": {"kind": "TPU v5 lite"}}
    dec = "jit__traced_gen_decode(1)"
    spec = {"op": "gated_delta_step", "module": "gen_decode",
            "work": "live_slots", "bytes": "gdn_step_bytes"}
    _, record = _record(published, {"fusion.9": [(0.1, 0.01, dec)]},
                        {dec: [(0.1, 0.02)]})
    assert reader.read(spec, record, result) is None
    assert reader.read(spec, dict(record, trace=None), result) is None
    other = dict(record, builder=load("keye_vl2_30b_a3b", "configs"))
    other["trace"]["devices"]["/device:TPU:0"]["ops"] = {
        "gated_delta_step.1": [(0.1, 0.01, dec)]}
    assert reader.read(spec, other, result) is None
    assert reader.read(spec, dict(other, builder=record["builder"]),
                       result) is not None
