"""The `deepseek_v2` configuration's benchmark parts at a tiny size on the CPU:
its cell added as files only (`data/deepseek_v2_root`) rehearses and is
correct, the int8 control is not; the configuration keeps every published
width and lists exactly its three cuts; the reference's `spec` and the
builder's byte and FLOP counts match a hand count at the published widths;
each new metric file reads a planted record."""
import json
import math
import os

import pytest

from benchpaths import BENCH, DATA, REPO, compared as _compared, load

ROOT = os.path.join(DATA, "deepseek_v2_root")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "deepseek_v2.context_backlog"


def test_context_tiny_rehearses_correct_and_the_control_is_not(run_cell):
    # a seed past 2**31, as the driver's are (the later --seed wins)
    line, err = run_cell(ROOT, "deepseek_v2_tiny.context_tiny", "--control",
                         "int8", "--seed", "3000000007")
    assert line["correct"] is True
    assert line["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    c = _compared(err)
    assert c["length_faults"][0] == 0
    assert c["gap_max"][0] <= c["gap_max"][1]
    assert c["gap_mean"][0] <= c["gap_mean"][1]
    assert c["control.gap_max"][0] > 3 * c["gap_max"][1]
    assert c["control.gap_mean"][0] > 3 * c["gap_mean"][1]
    assert c["tokens_compared"][0] >= 15


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "deepseek_v2.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths(published):
    """Every key of the catalog's config under its own name; only the cuts
    differ, and each is stated with the published count and the deployment
    beside it."""
    cfg = published
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek_v2")
    cuts = ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["reduced"] == cuts and sorted(cfg["reduced"]) == cuts
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/deepseek_v2.json"
    assert cfg["published"]["num_hidden_layers"] == 60
    assert cfg["published"]["num_local_experts"] == \
        cfg["n_routed_experts"] == 160
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 102400
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["first_local_expert"]) == (5, 10, 0)
    # the floors: the leading dense layer and four sparse ones, 8 experts
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_local_experts"] >= 8
    assert cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["num_local_experts"] * 16 == cfg["n_routed_experts"]
    widths = {"hidden_size": 5120, "num_attention_heads": 128,
              "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 12288,
              "moe_intermediate_size": 1536, "n_routed_experts": 160,
              "num_experts_per_tok": 6, "n_group": 8, "topk_group": 3,
              "n_shared_experts": 2, "routed_scaling_factor": 16,
              "first_k_dense_replace": 1, "norm_topk_prob": False,
              "rope_theta": 10000}
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    if os.path.isfile(CATALOG):             # the catalog row, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == cfg["source"])
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert sorted(differs) == ["num_hidden_layers", "vocab_size"]
    # the cell and its metrics are declared, each new one for this cell only
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("deepseek_v2", "context_backlog", 1)
    assert bench["workloads"][-1] is cell and len(bench["workloads"]) == 6
    new = ["mla.cache_share.context", "mla.rows_needed_share.context",
           "moe.load_imbalance.context", "kernel.prefill_roofline.context"]
    assert [m["name"] for m in bench["per_layer"][-4:]] == new
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
    joined = ["serve_tokens_per_s", "engine.slot_occupancy.backlog",
              "engine.host_busy_share.backlog",
              "engine.admit_device_share.backlog", "modelstep.mfu.backlog",
              "modelstep.decode_device_ms.backlog", "kernel.decode_roofline",
              "compile.recompiles_in_window", "compile.cache_entries_added",
              "compile.setup_compile_s"]
    listed = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in joined:
        assert listed[name]["workloads"][-1] == CELL
    # the traffic: contexts that end at 1536-3072, inside a slot's rows
    with open(os.path.join(BENCH, "traffic", "context_backlog.json")) as f:
        mix = json.load(f)
    assert mix["arrivals"] == "backlog" and mix["backlog"]["headroom"] == 3.0
    assert (mix["source_len"]["min"], mix["source_len"]["max"]) == (1024, 2048)
    sv = cfg["serving"]
    assert mix["source_len"]["max"] + mix["new_tokens"]["max"] <= \
        sv["max_len"] == 3072
    assert max(sv["prompt_buckets"]) == mix["source_len"]["max"]


def test_spec_and_builder_counts_match_a_hand_count(published):
    cfg = published
    b = load("deepseek_v2", "configs")
    ref = load("deepseek_v2", "reference")
    n_params = sum(math.prod(s) for _, s, _ in ref.spec(cfg))
    assert round(n_params / 1e9, 4) == 2.2017           # ISSUE's count
    assert round(2 * n_params / 1e9, 2) == 4.40         # GB in bfloat16
    D, H = 5120, 128
    # attention by hand: q_a, q_b (128 heads of 192), kv_a (512 + 64), kv_b
    # (128 heads of 128 + 128 from 512), o
    attn = D * 1536 + 1536 * H * 192 + D * 576 + H * 256 * 512 + H * 128 * D
    ffn = 3 * D * 12288
    moe = D * 160 + 3 * D * 3072            # router, two shared experts
    expert = 3 * D * 1536
    assert (attn, ffn, moe, expert) == (149225472, 188743680, 48005120,
                                        23592960)
    layers = 5 * attn + ffn + 4 * (moe + 10 * expert)
    head = D * 12800
    norms = 5 * (D + 1536 + 512) + 5 * D + D
    assert n_params == layers + 2 * head + norms
    assert b.decode_weight_bytes(cfg) == 2 * (layers + head)
    # a slot at context 1000: 5 layers x 1000 rows of 576 bfloat16
    assert b.decode_state_bytes(cfg, 700, 300) == 5 * 1000 * 1152
    # one new token: every matrix once, 0.375 held experts; at context c
    # every head scores 576 values and sums 512 of each cached row
    token = 5 * 2 * attn + 2 * ffn + 4 * (2 * moe + 2 * 0.375 * expert)
    row = H * (2 * 576 + 2 * 512)
    assert row == 278528                                # ISSUE's count

    def step(c):
        return token + 5 * row * c + 2 * head
    assert b.decode_flops(cfg, 600, 100) == step(700)
    assert b.request_flops(cfg, 600, 5, first=2) == \
        step(602) + step(603) + step(604)
    # a prompt (expanded): a key of 192 and a value of 128 a head a position
    pre = sum(token + 5 * H * 2 * 320 * c for c in range(1, 601))
    assert b.prefill_flops(cfg, 600) == pre
    assert b.request_flops(cfg, 600, 2) == pre + step(600) + step(601)
    assert b.prefill_bytes(cfg, 600) == \
        2 * layers + 2 * 600 * D + 5 * 600 * 1152
    # a row a layer: 242 FLOP/B, the v5e's ridge (197e12 / 819e9 = 241)
    assert round(row / 1152) == 242


def _planted(counters):
    from incubator_mxnet_tpu.monitor import events
    for name, value in counters.items():
        events.incr(name, value - (events.get(name) or 0))


def test_each_new_metric_file_reads_a_planted_record(published):
    """The three counter ratios from planted counters, the prefill's
    roofline from a planted trace and phase rows; nothing to read where the
    program has no such counter (a parent commit)."""
    from incubator_mxnet_tpu.monitor import events
    from incubator_mxnet_tpu.telemetry import spans
    spec = {}
    for name in ("mla.cache_share.context", "mla.rows_needed_share.context",
                 "moe.load_imbalance.context",
                 "kernel.prefill_roofline.context"):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec[name] = json.load(f)
    ratio = load("counter_ratio", "readers")
    record = {"config": published}
    assert spec["mla.cache_share.context"] == {
        "reader": "counter_ratio", "numerator": "gen.cache_kib",
        "denominator": "gen.step_kib", "times": 100.0}
    if not events.get("gen.step_kib"):      # a program without the counter
        assert ratio.read(spec["mla.cache_share.context"], record, {}) is None
    _planted({"gen.cache_kib": 3000, "gen.step_kib": 7500,
              "gen.attn_context": 620, "gen.attn_rows_read": 1000,
              "moe.expert_max": 30, "moe.picks_held": 200})
    assert ratio.read(spec["mla.cache_share.context"], record, {}) == 40.0
    assert ratio.read(spec["mla.rows_needed_share.context"], record, {}) \
        == 62.0
    # the fullest of 10 held experts took 30 of 200 held picks: 1.5 x even
    assert ratio.read(spec["moe.load_imbalance.context"], record, {}) == 1.5
    assert spec["moe.load_imbalance.context"]["times_config"] == \
        "num_local_experts"
    # the prefill's roofline: two prefills in the trace, the two latest rows
    reader = load("prefill_roofline", "readers")
    b = load("deepseek_v2", "configs")
    peaks = load("harness").peaks_for("TPU v5 lite")
    t0 = spans._now()
    pre = "jit__traced_gen_prefill(2)"
    trace = {"window_s": 3.0, "busy_s": 1.0,
             "devices": {"/device:TPU:0": {
                 "busy_s": 1.0, "ops": {},
                 "modules": {pre: [(0.3, 0.08), (0.9, 0.06)]}}}}
    record = {"kind": "serve", "trace": trace, "builder": b,
              "config": published, "t_open": t0, "t_close": t0 + 10.0}
    result = {"device": {"kind": "TPU v5 lite"}}
    assert reader.read(spec["kernel.prefill_roofline.context"], record,
                       result) is None                  # no rows yet
    spans.phase_at("gen.prefill", t0 + 1.0, t0 + 1.1, 1, 0, 1100)
    spans.phase_at("gen.prefill", t0 + 3.5, t0 + 3.6, 2, 0, 2000)
    spans.phase_at("gen.prefill", t0 + 5.0, t0 + 5.1, 3, 0, 1500)
    spans.phase_at("gen.prefill", t0 + 9.5, t0 + 9.6, 4, 0, 1800)  # after
    need = sum(max(b.prefill_flops(published, n) / peaks["bf16_flops_per_s"],
                   b.prefill_bytes(published, n) / peaks["hbm_bytes_per_s"])
               for n in (2000, 1500))
    got = reader.read(spec["kernel.prefill_roofline.context"], record, result)
    assert abs(got - 100 * need / 0.14) < 1e-9
    # a 2000-token prompt is bound by its FLOPs, not by the weights' read
    assert b.prefill_flops(published, 2000) / peaks["bf16_flops_per_s"] > \
        b.prefill_bytes(published, 2000) / peaks["hbm_bytes_per_s"]
