"""The `laguna_xs2` configuration's benchmark parts at a tiny size on the CPU:
its cell added as files only (`data/laguna_root`) rehearses and is correct,
the int8 control is not; the configuration keeps every published width and
lists exactly its three cuts; the reference's `spec` and the builder's byte
and FLOP counts match a hand count at the published widths; the cell and
its metrics are found by name in `BENCHMARK.json`; each new metric file
reads a planted record."""
import json
import math
import os

import pytest

from benchpaths import BENCH, DATA, compared as _compared, load

ROOT = os.path.join(DATA, "laguna_root")
CELL = "laguna_xs2.code_backlog"
MODEL_STEP = ("model step (gluon fused step, ShardedTrainer step, "
              "engine decode_step)")
KERNELS = "kernels (ops/attention.py, XLA decode)"
NEW = {"modelstep.prefill_window_share.code": ("part_share", MODEL_STEP),
       "modelstep.decode_window_share.code": ("part_share", MODEL_STEP),
       # the plain names' lists are asserted letter for letter by
       # test_bench_part_share.py, which only a `benchmark` PR may mend
       "modelstep.prefill_attn_share.code": ("part_share", MODEL_STEP),
       "modelstep.decode_attn_share.code": ("part_share", MODEL_STEP),
       "kernel.prefill_roofline.code": ("prefill_roofline", KERNELS),
       "window.rows_needed_share.code": ("counter_ratio", KERNELS),
       # as the attn shares: the plain experts shares' lists are asserted
       "modelstep.prefill_experts_share.code": ("part_share", MODEL_STEP),
       "modelstep.decode_experts_share.code": ("part_share", MODEL_STEP),
       "moe.load_imbalance.code": ("counter_ratio", MODEL_STEP)}
JOINED = ["serve_tokens_per_s", "engine.slot_occupancy.backlog",
          "modelstep.mfu.backlog", "modelstep.decode_device_ms.backlog",
          "kernel.decode_roofline", "compile.recompiles_in_window",
          "compile.cache_entries_added", "compile.setup_compile_s",
          "engine.host_busy_share.backlog",
          "engine.admit_device_share.backlog",
          "engine.join_device_share.backlog"]


def test_code_tiny_rehearses_correct_and_the_control_is_not(run_cell):
    from incubator_mxnet_tpu.monitor import events
    names = ("window.rows_needed", "window.rows_read", "gen.attn_context",
             "gen.tokens", "gen.donation_copy")
    before = [events.get(n) or 0 for n in names]
    # a seed past 2**31: any whole number is a seed (the later --seed wins)
    line, err = run_cell(ROOT, "laguna_tiny.code_tiny", "--control", "int8",
                         "--seed", "3000000043")
    needed, read, context, tokens, copies = [
        (events.get(n) or 0) - b for n, b in zip(names, before)]
    # six window layers read the ring's 8 rows a token; the band is full
    # once a context passes 8, as every one here soon does
    assert tokens > 0 and read == 6 * 8 * tokens
    assert 0.9 * read < needed <= read
    assert context >= 3 * 9 * tokens        # three full layers, contexts > 8
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
    with open(os.path.join(BENCH, "configs", "laguna_xs2.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_the_published_widths(published, bench_json):
    """Every key of the catalog's config under its own name; depth,
    experts held and vocabulary differ, each stated with the published
    count and the deployment beside it."""
    cfg = published
    entry = next(c for c in bench_json["configs"] if c["name"] == "laguna_xs2")
    cuts = ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["reduced"] == cuts == list(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/laguna_xs2.json"
    assert len(entry["why"]) <= 200
    assert cfg["published"] == dict(
        cfg["published"], num_hidden_layers=40, num_local_experts=256,
        vocab_size=100352)
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (9, 32, 12544)
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    widths = {"hidden_size": 2048, "num_attention_heads": 48,
              "num_key_value_heads": 8, "head_dim": 128,
              "intermediate_size": 8192, "num_experts": 256,
              "num_experts_per_tok": 8, "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512,
              "moe_routed_scaling_factor": 2.5, "sliding_window": 512,
              "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-06}
    assert {k: cfg[k] for k in widths} == widths
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["partial_rotary_factor"]) == \
        ("yarn", 500000, 64, 4096, 64, 1, 0.5)
    assert round(full["attention_factor"], 5) == 1.41589
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    # the nine layers held: layer 0 full and dense, then two periods
    L = cfg["num_hidden_layers"]
    window, f = "sliding_attention", "full_attention"
    assert cfg["layer_types"][:L] == [f] + [window, window, window, f] * 2
    assert cfg["mlp_layer_types"][:L] == ["dense"] + ["sparse"] * 8
    assert cfg["num_attention_heads_per_layer"][:L] == \
        [48] + [64, 64, 64, 48] * 2
    for key in ("router", "shared_expert", "gating", "qk_norm", "rotary",
                "yarn_scale", "window", "norms", "special_tokens", "dtype"):
        assert cfg["assumed"][key]
    assert cfg["control_precision"] == "int8"


def test_the_cell_and_its_metrics_are_found_by_name(published, bench_json):
    cells = {w["name"]: w for w in bench_json["workloads"]}
    cell = cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("laguna_xs2", "code_backlog", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"]: m
              for m in bench_json["end_to_end"] + bench_json["per_layer"]}
    for name, (reader, layer) in NEW.items():
        m = listed[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s" and m["layer"] == layer
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == reader
        assert os.path.isfile(os.path.join(BENCH, "readers", reader + ".py"))
    for name in JOINED:
        assert CELL in listed[name]["workloads"]
    # metrics of one prefix name one layer: `window.` the kernels'
    assert {m["layer"] for n, m in listed.items()
            if n.split(".")[0] == "window"} == {KERNELS}
    # the traffic: contexts that end at 6912-9216, inside a slot's rows
    with open(os.path.join(BENCH, "traffic", "code_backlog.json")) as f:
        mix = json.load(f)
    assert mix["arrivals"] == "backlog" and mix["backlog"]["headroom"] == 3.0
    assert (mix["source_len"]["min"], mix["source_len"]["max"]) == \
        (6144, 8192)
    assert mix["new_tokens"] == {"ratio": 0.125, "min": 768, "max": 1024}
    # n queued (3 x the rate x 51 s over 896 tokens a request + 64 slots) in
    # blocks of 64 that hold the same lengths whatever the seed; `block_s`
    # is tied to that count, so a re-based rate must re-derive it
    b = mix["backlog"]
    n = math.ceil(b["headroom"] * b["expected_tokens_per_s"] * 51 / 896) + 64
    gen = load("requests", "generators")
    plans = [gen.generate(mix, published, seed, 51) for seed in (7, 2 ** 31 + 5)]
    for plan in plans:
        assert len(plan["due"]) == len(plan["prompts"]) == \
            len(plan["src_len"]) == n
    assert mix["block_s"] * n == pytest.approx(64)
    for lo in (0, 64, 128):
        assert sorted(plans[0]["src_len"][lo:lo + 64]) == \
            sorted(plans[1]["src_len"][lo:lo + 64])
    assert list(plans[0]["src_len"][:64]) != list(plans[1]["src_len"][:64])
    sv = published["serving"]
    assert (sv["slots"], sv["max_len"], sv["prompt_buckets"]) == \
        (64, 9216, [7168, 8192])
    assert mix["source_len"]["max"] + mix["new_tokens"]["max"] == \
        sv["max_len"]
    # a slot: 3 full layers' rows + 6 rings of 512, 125.8 MB; both probes'
    # rows admitted in one tick (serving/generation.py `_ADMIT_BYTES`)
    z = load("laguna_xs2", "configs").sizes(published)
    slot = (z["NF"] * sv["max_len"] + z["NW"] * z["W"]) * z["row"]
    assert round(slot / 1e6, 1) == 125.8
    assert 2 * slot <= 768 << 20


@pytest.mark.parametrize("layers,experts,vocab,total", [
    (9, 32, 12544, 1252071424), (40, 256, 100352, 33442596864)])
def test_spec_sums_to_the_models_parameters(published, layers, experts,
                                            vocab, total):
    ref = load("laguna_xs2", "reference")
    cfg = dict(published, num_hidden_layers=layers, num_local_experts=experts,
               vocab_size=vocab)
    assert sum(math.prod(s) for _, s, _ in ref.spec(cfg)) == total
    if layers == 9:
        assert round(total / 1e9, 3) == 1.252      # 2.50 GB in bfloat16
    kinds = {n: k for n, _, k in ref.spec(cfg)}
    assert {kinds[n] for n in ("full.ln", "window.ln", "dense.ln", "moe.ln",
                               "norm")} == {"gamma"}


def test_builder_counts_match_a_hand_count(published):
    cfg = published
    b = load("laguna_xs2", "configs")
    D, G, d, V, W = 2048, 8, 128, 12544, 512
    full = 2 * D * 48 * d + 2 * D * G * d + 48 * D
    window = 2 * D * 64 * d + 2 * D * G * d + 64 * D
    assert (full, window) == (29458432, 37879808)
    dense, moe, expert = 3 * D * 8192, D * 256 + 3 * D * 512, 3 * D * 512
    layers = 3 * full + 6 * window + dense + 8 * (moe + 32 * expert)
    assert b.decode_weight_bytes(cfg) == 2 * (layers + D * V)
    # a slot at context 7000: every row of the full layers, 512 of a ring
    row = 2 * G * d * 2
    assert b.decode_state_bytes(cfg, 6900, 100) == (3 * 7000 + 6 * W) * row
    assert b.decode_state_bytes(cfg, 100, 50) == 9 * 150 * row
    # one held expert a token on average: 8 picks x 32 held / 256
    token = 2 * layers - 2 * 8 * 32 * expert + 2 * 8 * expert
    key_full, key_window = 4 * 48 * d, 4 * 64 * d

    def step(c):
        return token + 3 * key_full * c + 6 * key_window * min(c, W) \
            + 2 * D * V
    assert b.decode_flops(cfg, 6900, 100) == step(7000)
    assert b.request_flops(cfg, 600, 5, first=2) == \
        step(602) + step(603) + step(604)
    # a prompt: position t attends over t + 1 keys, min(t + 1, 512) in a
    # window layer: the band, not the causal triangle
    n = 1500
    pre = sum(token + 3 * key_full * c + 6 * key_window * min(c, W)
              for c in range(1, n + 1))
    assert b.prefill_flops(cfg, n) == pre
    assert b.request_flops(cfg, n, 2) == pre + step(n) + step(n + 1)
    triangle = sum(6 * key_window * (c - min(c, W)) for c in range(1, n + 1))
    assert b.prefill_flops(cfg, n) + triangle == \
        sum(token + (3 * key_full + 6 * key_window) * c
            for c in range(1, n + 1))
    assert b.prefill_bytes(cfg, n) == \
        2 * layers + 2 * n * D + (3 * n + 6 * W) * row


def _planted(counters):
    from incubator_mxnet_tpu.monitor import events
    for name, value in counters.items():
        events.incr(name, value - (events.get(name) or 0))


def test_each_new_metric_file_reads_a_planted_record(published):
    """The rows' ratio from planted counters, the prefill's roofline from a
    planted trace and the band-counted work; nothing to read where the
    program has no such counter (a parent commit)."""
    from incubator_mxnet_tpu.monitor import events
    spec = {}
    for name in NEW:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec[name] = json.load(f)
    ratio = load("counter_ratio", "readers")
    if not events.get("window.rows_read"):  # a program without the counter
        assert ratio.read(spec["window.rows_needed_share.code"], {},
                          {}) is None
    _planted({"window.rows_needed": 2970, "window.rows_read": 3072})
    assert ratio.read(spec["window.rows_needed_share.code"], {},
                      {}) == pytest.approx(96.6796875)
    for name, module, part in (
            ("modelstep.prefill_window_share.code", "gen_prefill", "window"),
            ("modelstep.decode_window_share.code", "gen_decode", "window"),
            ("modelstep.prefill_attn_share.code", "gen_prefill", "attn"),
            ("modelstep.decode_attn_share.code", "gen_decode", "attn"),
            ("modelstep.prefill_experts_share.code", "gen_prefill",
             "experts"),
            ("modelstep.decode_experts_share.code", "gen_decode",
             "experts")):
        assert spec[name] == {"reader": "part_share", "module": module,
                              "part": part}
    # the fullest held expert against the held experts' mean, 32 of them
    _planted({"moe.expert_max": 90, "moe.picks_held": 960})
    assert ratio.read(spec["moe.load_imbalance.code"], {"config": published},
                      {}) == pytest.approx(3.0)
    assert spec["kernel.prefill_roofline.code"] == {
        "reader": "prefill_roofline", "contains": "gen_prefill"}
