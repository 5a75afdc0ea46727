"""The `keye_vl2_30b_a3b` configuration's benchmark parts at a tiny size on
the CPU: its cell added as files only (`data/keye_root`) rehearses and is
correct, the int8 control is not; the builder's byte and FLOP counts match
a hand count at the published widths; the new readers read what they say and
find nothing to read where the program has nothing."""
import json
import math
import os

import pytest

from benchpaths import BENCH, DATA, REPO, compared as _compared, load

ROOT = os.path.join(DATA, "keye_root")


def test_doc_tiny_rehearses_correct_and_the_control_is_not(run_cell):
    # a seed past 2**31, as the driver's are (the later --seed wins)
    line, err = run_cell(ROOT, "keye_tiny.doc_tiny", "--control", "int8",
                         "--seed", "3000000007")
    assert line["correct"] is True
    assert line["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    c = _compared(err)
    assert c["length_faults"][0] == 0
    assert c["gap_max"][0] <= c["gap_max"][1]
    assert c["gap_mean"][0] <= c["gap_mean"][1]
    assert c["control.gap_max"][0] > 3 * c["gap_max"][1]
    assert c["tokens_compared"][0] >= 12


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths(published):
    """Every number of the catalog's config under its own key; only the
    three cuts differ, and each is stated with the published count."""
    cfg = published
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "keye_vl2_30b_a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert sorted(cfg["reduced"]) == entry["reduced"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"]) == (2048, 128, 32, 4)
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"]) == (128, 8, 768)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["num_hidden_layers"] == 16 and cfg["num_local_experts"] == 16
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8


def test_builder_counts_match_a_hand_count(published):
    cfg = published
    b = load("keye_vl2_30b_a3b", "configs")
    ref = load("keye_vl2_30b_a3b", "reference")
    n_params = sum(math.prod(s) for _, s, _ in ref.spec(cfg))
    assert round(n_params / 1e9, 3) == 1.628              # ISSUE's count
    # a layer by hand: attention 18.87 M, indexer 2.26 M, router 0.26 M,
    # an expert 4.719 M
    attn = 2048 * (4096 + 512 + 512) + 4096 * 2048
    idx = 2048 * (1024 + 64 + 16)
    router = 2048 * 128
    expert = 3 * 2048 * 768
    assert (attn, idx, router, expert) == (18874368, 2260992, 262144, 4718592)
    dense = attn + idx + router
    head = 2048 * 18992
    assert b.decode_weight_bytes(cfg) == 2 * (16 * (dense + 16 * expert) + head)
    # cache a token a layer: K 512 + V 512 + indexer key 64 values
    assert b.decode_state_bytes(cfg, 6000, 300) == \
        2 * 16 * (6300 * 64 + 2048 * 1024)
    assert b.decode_state_bytes(cfg, 1000, 24) == 2 * 16 * 1024 * (64 + 1024)
    # one new token at context c: dense parts, one held expert (8 x 16/128),
    # index scores over c keys, attention over min(2048, c), the head
    def step(c):
        return 16 * (2 * dense + 2 * expert + 2 * 16 * 64 * c
                     + 4 * 32 * 128 * min(2048, c)) + 2 * head
    assert b.decode_flops(cfg, 5000, 100) == step(5100)
    assert b.decode_flops(cfg, 1500, 0) == step(1500)
    assert b.request_flops(cfg, 3000, 5, first=2) == \
        step(3002) + step(3003) + step(3004)
    pre = sum(16 * (2 * dense + 2 * expert + 2 * 16 * 64 * c
                    + 4 * 32 * 128 * min(2048, c)) for c in range(1, 3001))
    assert b.prefill_flops(cfg, 3000) == pre
    assert b.request_flops(cfg, 3000, 2) == pre + step(3000) + step(3001)
    # a prefill's bytes: the weights without the head, the embedding rows
    # read, the cache rows written, K/V/indexer rows read once a layer
    n = 3000
    assert b.prefill_bytes(cfg, n) == \
        2 * 16 * (dense + 16 * expert) + 2 * n * 2048 \
        + 2 * 2 * 16 * n * (1024 + 64)


def test_counter_ratio_reads_the_programs_counters():
    from incubator_mxnet_tpu.monitor import events
    reader = load("counter_ratio", "readers")
    spec = {"numerator": "t.keye.num", "denominator": "t.keye.den",
            "times": 100.0}
    assert reader.read(spec, {}, None) is None              # no such counters
    events.incr("t.keye.num", 30)
    events.incr("t.keye.den", 120)
    assert reader.read(spec, {}, None) == 25.0
    spec = {"numerator": "t.keye.num", "denominator": "t.keye.den",
            "times_config": "num_local_experts"}
    assert reader.read(spec, {"config": {"num_local_experts": 16}}, None) == 4.0


def test_prefill_roofline_reads_the_rows_of_the_traced_span(published):
    from incubator_mxnet_tpu.telemetry import spans
    reader = load("prefill_roofline", "readers")
    b = load("keye_vl2_30b_a3b", "configs")
    harness = load("harness")
    spec = {"contains": "gen_prefill"}
    result = {"device": {"kind": "TPU v5 lite"}}
    t0 = spans._now()
    mods = {"jit__traced_gen_prefill_1": [(0.5, 0.2)],
            "jit__traced_gen_prefill_2": [(1.5, 0.4)],
            "jit__traced_gen_decode_3": [(0.1, 0.01)]}
    trace = {"window_s": 6.0, "busy_s": 1.0,
             "devices": {"/device:TPU:0": {"busy_s": 1.0, "modules": mods,
                                           "ops": {}}}}
    record = {"kind": "serve", "trace": trace, "builder": b,
              "config": published, "t_open": t0, "t_close": t0 + 10.0}
    assert reader.read(spec, record, result) is None        # no rows yet
    spans.phase_at("gen.prefill", t0 + 3.5, t0 + 3.6, 1, 0, 4000)
    spans.phase_at("gen.prefill", t0 + 5.0, t0 + 5.1, 2, 0, 8000)
    spans.phase_at("gen.prefill", t0 + 9.5, t0 + 9.6, 3, 0, 5000)   # after
    peaks = harness.peaks_for("TPU v5 lite")
    need = sum(max(b.prefill_flops(published, n) / peaks["bf16_flops_per_s"],
                   b.prefill_bytes(published, n) / peaks["hbm_bytes_per_s"])
               for n in (4000, 8000))
    got = reader.read(spec, record, result)
    assert abs(got - 100.0 * need / 0.6) < 1e-6
    # a program whose rows carry no lengths (a parent commit): nothing
    record = dict(record, t_open=t0 + 20.0, t_close=t0 + 30.0)
    spans.phase_at("gen.prefill", t0 + 23.5, t0 + 23.6, 4, 0, 0)
    assert reader.read(spec, record, result) is None
    assert reader.read(spec, dict(record, trace=None), result) is None
