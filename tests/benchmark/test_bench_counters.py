"""FLOP and byte counters against hand counts, for the three configurations."""
import json
import math
import os

import pytest

from benchpaths import BENCH, load


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _job(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_bert_step_flops():
    b, cfg, job = load("bert_base", "configs"), _cfg("bert_base"), _job("pretrain_s512")
    H, I, V, T = 768, 3072, 30522, 512
    per_token_layer = 8 * H * H + 4 * H * I + 4 * T * H      # qkvo, ffn, scores+values
    B = job["batch"]
    fwd = B * T * 12 * per_token_layer + B * 80 * (2 * H * H + 2 * H * V)
    assert b.step_flops(cfg, job) == 3 * fwd
    # about 6 x encoder parameters x tokens, plus attention and the head
    assert 0.9e13 < b.step_flops(cfg, job) * 32 / B < 1.1e13


def test_resnet_flops_match_the_published_count():
    b, cfg, job = load("resnet50_v1b", "configs"), _cfg("resnet50_v1b"), _job("train_dp4")
    fwd = b.forward_flops(cfg, 224)
    # ResNet-50 v1b at 224: 4.1 G multiply-adds (He et al. give 3.8 G for
    # v1; the stride on the 3x3 adds the rest)
    assert 8.0e9 < fwd < 8.4e9
    assert b.step_flops(cfg, job) == 3 * fwd * 512
    # the stem by hand: 3 -> 64 channels, 7x7, 112x112 outputs
    assert b._conv_macs(3, 64, 7, 112) == 3 * 64 * 49 * 112 * 112


def test_nmt_counters():
    b, cfg = load("nmt_base", "configs"), _cfg("nmt_base")
    U, F, V = 512, 2048, 32000
    s, pos = 30, 9
    layer = 8 * U * U + 4 * U * U + 4 * U * F + 4 * U * (pos + 1) + 4 * U * s
    assert b.decode_flops(cfg, s, pos) == 6 * layer + 2 * U * V
    enc_layer = 8 * U * U * s + 4 * U * F * s + 4 * U * s * s
    assert b.prefill_flops(cfg, s) == 6 * enc_layer + 6 * 4 * U * U * s
    whole = b.prefill_flops(cfg, s) + sum(b.decode_flops(cfg, s, p) for p in range(12))
    assert b.request_flops(cfg, s, 12) == whole
    part = sum(b.decode_flops(cfg, s, p) for p in range(5, 12))
    assert b.request_flops(cfg, s, 12, 5) == part
    assert b.decode_weight_bytes(cfg) == 2 * (6 * (6 * U * U + 2 * U * F) + U * V)
    assert b.decode_state_bytes(cfg, s, pos) == 2 * 6 * 2 * U * (pos + 1 + s)


def test_slot_bytes_are_what_the_config_says():
    cfg = _cfg("nmt_base")
    sv = cfg["serving"]
    per_slot = 6 * (2 * sv["max_len"] + 2 * max(sv["prompt_buckets"])) * 512 * 4
    assert per_slot == 12582912
    assert 0.39 < sv["slots"] * per_slot / 16e9 < 0.41


@pytest.mark.parametrize("name", ["nmt_base", "bert_base", "resnet50_v1b"])
def test_reference_spec_covers_the_model(name):
    ref, cfg = load(name, "reference"), _cfg(name)
    spec = ref.spec(cfg)
    names = [n for n, _, _ in spec]
    assert len(names) == len(set(names))
    n_params = sum(math.prod(s) for _, s, _ in spec)
    want = {"nmt_base": (9.0e7, 1.0e8), "bert_base": (1.3e8, 1.36e8),
            "resnet50_v1b": (2.5e7, 2.6e7)}[name]
    assert want[0] < n_params < want[1]
