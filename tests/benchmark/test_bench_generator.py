"""The stratified, seed-permuted request generator."""
import json
import os

import numpy as np
import pytest

from benchpaths import BENCH, load

CONFIG = {"vocab_size": 32000, "serving": {"slots": 512}}


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["online_steady", "offline_backlog"])
def test_same_multiset_every_seed_other_order(mix):
    gen = load("requests", "generators")
    plans = [gen.generate(_mix(mix), CONFIG, seed, 51) for seed in (1, 2, 2**31 + 5)]
    key = lambda p: sorted(zip(p["phase"], p["src_len"], p["max_new"]))
    assert key(plans[0]) == key(plans[1]) == key(plans[2])
    assert not np.array_equal(plans[0]["src_len"], plans[1]["src_len"])
    # token ids are drawn from the seed too
    assert not np.array_equal(plans[0]["prompts"][0][:4], plans[1]["prompts"][0][:4]) \
        or len(plans[0]["prompts"][0]) != len(plans[1]["prompts"][0])


def test_same_seed_same_plan():
    gen = load("requests", "generators")
    a, b = (gen.generate(_mix("online_steady"), CONFIG, 9, 51) for _ in range(2))
    assert np.array_equal(a["due"], b["due"])
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"], b["prompts"]))


def test_open_loop_phases_and_load():
    gen = load("requests", "generators")
    mix = _mix("online_steady")
    plan = gen.generate(mix, CONFIG, 3, 51)
    win = plan["phase"] == gen.PHASES.index("window")
    assert win.sum() == round(mix["rate_per_s"] * 51)
    due = plan["due"]
    assert due[win].min() >= 0 and due[win].max() < 51
    lead = plan["phase"] == gen.PHASES.index("lead")
    assert due[lead].min() >= -mix["lead_in_s"] and due[lead].max() < 0
    tail = plan["phase"] == gen.PHASES.index("tail")
    assert due[tail].min() >= 51
    # the same gaps in another order: the same set of gap lengths
    other = gen.generate(mix, CONFIG, 4, 51)
    gaps = lambda p: np.sort(np.diff(np.concatenate([[0.0], np.sort(p["due"][win])])))
    assert gaps(plan).sum() == pytest.approx(gaps(other).sum(), rel=1e-3)
    # lengths as the mix states them
    src = plan["src_len"][win]
    assert 4 <= src.min() and src.max() <= 200
    assert abs(np.median(src) - mix["source_len"]["median"]) <= 1
    assert (plan["max_new"] <= 250).all() and (plan["max_new"] >= 4).all()
    assert all(len(p) == s for p, s in zip(plan["prompts"], plan["src_len"]))
    assert min(int(p.min()) for p in plan["prompts"]) >= 3


def test_backlog_is_deep_enough():
    gen = load("requests", "generators")
    mix = _mix("offline_backlog")
    plan = gen.generate(mix, CONFIG, 3, 51)
    tokens = plan["max_new"].sum()
    assert np.isinf(plan["due"]).all()
    need = mix["backlog"]["headroom"] * mix["backlog"]["expected_tokens_per_s"] * 51
    assert tokens >= need


def test_burst_gaps_have_the_asked_variation():
    gen = load("requests", "generators")
    g = gen.gaps({"dist": "gamma", "cv": 2.0}, 2000, 100.0)
    assert g.sum() == pytest.approx(100.0)
    assert 1.6 < g.std() / g.mean() < 2.4
    e = gen.gaps({"dist": "exponential"}, 2000, 100.0)
    assert 0.9 < e.std() / e.mean() < 1.1


def test_resident_batch_rows_all_differ():
    gen = load("resident_batch", "generators")
    with open(os.path.join(BENCH, "traffic", "pretrain_s512.json")) as f:
        job = json.load(f)
    b = gen.generate(job, {"vocab_size": 30522}, 5, 51)
    B = job["batch"]
    assert b["tokens"].shape == (B, 512) and b["items_per_step"] == B * 512
    assert len({r.tobytes() for r in b["tokens"]}) == B
    pos = b["positions"].reshape(B, 80)
    assert all(len(set(r)) == 80 for r in pos)
    assert all((r // 512 == i).all() for i, r in enumerate(pos))
    c = gen.generate(job, {"vocab_size": 30522}, 6, 51)
    assert not np.array_equal(b["tokens"], c["tokens"])
