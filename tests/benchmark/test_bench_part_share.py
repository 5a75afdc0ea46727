"""`readers/part_share.py` by hand, on planes made as `test_bench_trace.py`
makes them and a stand-in for the program's `op_parts`: self time, which
executable is which module, what reads None, and the eleven metrics that
name the reader."""
import json
import os

import pytest

from benchpaths import BENCH, load

MS = 1e6        # ns
PREFILL_A = "jit__traced_gen_prefill(11)"
PREFILL_B = "jit__traced_gen_prefill(12)"
DECODE = "jit__traced_gen_decode(13)"
LAYER = ("model step (gluon fused step, ShardedTrainer step, "
         "engine decode_step)")


def _op(name, start, dur):
    return ("%%%s = f32[8] fusion(...)" % name, start * MS, dur * MS)


def _trace(ops, modules):
    planes = [("/host:CPU", {"main": [("Sleep", 0.0, 1 * MS)]}),
              ("/device:TPU:0", {"XLA Modules": [(m, s * MS, d * MS)
                                                 for m, s, d in modules],
                                 "XLA Ops": ops})]
    return load("trace_reduce").reduce_planes(planes)


def _exe(name, instructions, stale=False):
    return {"name": name, "instructions": instructions, "stale": stale}


@pytest.fixture
def read(monkeypatch):
    """read(entries, trace, module, part): the reader over a program whose
    `op_parts` gives `entries` for every role."""
    from incubator_mxnet_tpu.telemetry import costs
    reader = load("part_share", "readers")

    def go(entries, trace, module, part):
        monkeypatch.setattr(costs, "op_parts", lambda role: [
            e for e in entries if role in e["name"]], raising=False)
        return reader.read({"reader": "part_share", "module": module,
                            "part": part}, {"trace": trace}, None)
    return go


# one run of 10 ms: a loop of 8 ms that holds three fusions, a copy after it
LOOP_OPS = [_op("while.3", 1, 8), _op("fusion.1", 1, 2), _op("fusion.2", 3, 3),
            _op("fusion.4", 6, 3), _op("copy.5", 9, 1)]
LOOP_EXE = _exe("jit__traced_gen_decode", {
    "while.3": None, "fusion.1": "attn", "fusion.2": "proj",
    "fusion.4": "attn", "copy.5": None, "fusion.9": "experts"})


def test_a_loop_is_not_counted_beside_its_body(read):
    tr = _trace(LOOP_OPS, [(DECODE, 0, 10)])
    share = {p: read([LOOP_EXE], tr, "gen_decode", p)
             for p in ("attn", "proj", None)}
    # attn 2 + 3 of 10 ms, proj 3; no part: the loop's own 0, the copy's 1
    # and the millisecond of the run in which no op ran
    assert share == pytest.approx({"attn": 50.0, "proj": 30.0, None: 20.0})
    assert sum(share.values()) == pytest.approx(100.0)
    # a part the executable has and the span never ran; one it lacks
    assert read([LOOP_EXE], tr, "gen_decode", "experts") is None
    assert read([LOOP_EXE], tr, "gen_decode", "state") is None


def test_self_time_by_hand():
    ps = load("part_share", "readers")
    events = [(1.0, 8.0, "while"), (1.0, 2.0, "a"), (3.0, 3.0, "b"),
              (3.5, 1.0, "inner"), (9.0, 1.0, "c"), (30.0, 1.0, "lost")]
    got = ps.self_times(events, [(0.0, 10.0), (20.0, 5.0)])
    assert got == pytest.approx({None: 6.0, "while": 3.0, "a": 2.0, "b": 2.0,
                                 "inner": 1.0, "c": 1.0})


def test_two_buckets_with_clashing_numbers_are_told_by_covering(read):
    ops = [_op("while.140", 0, 6), _op("fusion.5", 1, 5),
           _op("while.102", 10, 4), _op("fusion.5", 11, 3)]
    tr = _trace(ops, [(PREFILL_A, 0, 6), (PREFILL_B, 10, 4)])
    exe_a = _exe("jit__traced_gen_prefill", {"while.140": None,
                                             "fusion.5": "attn"})
    exe_b = _exe("jit__traced_gen_prefill", {"while.102": None,
                                             "fusion.5": "proj"})
    both = [exe_a, exe_b, LOOP_EXE]
    assert read(both, tr, "gen_prefill", "attn") == pytest.approx(50.0)
    assert read(both, tr, "gen_prefill", "proj") == pytest.approx(30.0)
    assert read(both, tr, "gen_prefill", None) == pytest.approx(20.0)
    # the second bucket's executable is gone: its module is covered by none
    assert read([exe_a, LOOP_EXE], tr, "gen_prefill", "attn") is None
    # no run of the role in the span
    assert read(both, tr, "gen_decode", "attn") is None


def test_several_that_cover_must_agree(read):
    tr = _trace(LOOP_OPS, [(DECODE, 0, 10)])
    twin = _exe("jit__traced_gen_decode", dict(LOOP_EXE["instructions"],
                                               **{"fusion.9": "ffn"}))
    assert read([LOOP_EXE, twin], tr, "gen_decode", "attn") == \
        pytest.approx(50.0)
    other = _exe("jit__traced_gen_decode", dict(LOOP_EXE["instructions"],
                                                **{"fusion.2": "experts"}))
    assert read([LOOP_EXE, other], tr, "gen_decode", "attn") is None


def test_a_stale_executable_and_a_program_without_parts_read_none(
        read, monkeypatch):
    tr = _trace(LOOP_OPS, [(DECODE, 0, 10)])
    stale = _exe("jit__traced_gen_decode",
                 dict.fromkeys(LOOP_EXE["instructions"]), stale=True)
    assert read([stale], tr, "gen_decode", None) is None
    assert read([LOOP_EXE], None, "gen_decode", "attn") is None
    from incubator_mxnet_tpu.telemetry import costs
    reader = load("part_share", "readers")
    monkeypatch.delattr(costs, "op_parts")          # the parent's program
    assert reader.read({"module": "gen_decode", "part": "attn"},
                       {"trace": tr}, None) is None


CHAT, CONTEXT, DOC, BERT = (
    "qwen3_next_80b_a3b.chat_backlog", "deepseek_v2.context_backlog",
    "keye_vl2_30b_a3b.doc_backlog", "bert_base.pretrain_s512")


@pytest.mark.parametrize("name,module,part,cells", [
    ("modelstep.prefill_attn_share", "gen_prefill", "attn", [CHAT, CONTEXT]),
    ("modelstep.prefill_proj_share", "gen_prefill", "proj", [CHAT, CONTEXT]),
    ("modelstep.prefill_experts_share", "gen_prefill", "experts",
     [CHAT, CONTEXT]),
    ("modelstep.prefill_state_share", "gen_prefill", "state", [CHAT]),
    ("modelstep.prefill_unscoped_share", "gen_prefill", None,
     [CHAT, CONTEXT]),
    ("modelstep.decode_attn_share", "gen_decode", "attn",
     [DOC, CHAT, CONTEXT]),
    ("modelstep.decode_experts_share", "gen_decode", "experts",
     [DOC, CHAT, CONTEXT]),
    ("modelstep.decode_state_share", "gen_decode", "state", [CHAT]),
    ("modelstep.decode_index_share", "gen_decode", "index", [DOC]),
    ("modelstep.decode_unscoped_share", "gen_decode", None,
     [DOC, CHAT, CONTEXT]),
    ("modelstep.train_attn_share", "gluon_train_step", "attn", [BERT]),
])
def test_the_metric_is_declared_and_names_the_reader(bench_json, name, module,
                                                     part, cells):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        assert json.load(f) == {"reader": "part_share", "module": module,
                                "part": part}
    from incubator_mxnet_tpu.telemetry import costs
    assert part is None or part in costs.PARTS
    (entry,) = [m for m in bench_json["per_layer"] if m["name"] == name]
    moves = "train_items_per_s" if cells == [BERT] else "serve_tokens_per_s"
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "device_trace", "layer": LAYER, "moves": moves,
                     "workloads": cells}
