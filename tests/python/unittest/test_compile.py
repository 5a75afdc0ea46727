"""Compile-loop tests (ISSUE 18): history-trained autotuner evidence
ladder and lax.scan layer-stacking parity/measurement.

Covers the satellite contracts explicitly:
- history.query(kind="cost"/"autotune") across runs as the autotuner
  consumes it — labeled splits, torn-tail tolerance, and a two-process
  proof (run 2's tuner reads run 1's rows);
- the suggest_bucket_mb deprecation shim warning once, only when it is
  the DECIDING input;
- the blackbox/teletop autotune row.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu import config as _cfg
from incubator_mxnet_tpu.compile import autotune, stacking
from incubator_mxnet_tpu.parallel.zero import BucketPlan
from incubator_mxnet_tpu.telemetry import costs as _costs
from incubator_mxnet_tpu.telemetry import flightrec as _bb
from incubator_mxnet_tpu.telemetry import history as _hist

pytestmark = pytest.mark.compile

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture
def fresh(tmp_path, monkeypatch):
    """An isolated history dir and clean per-process tuner /
    warn-once state, restored afterwards."""
    hist_dir = tmp_path / "hist"
    monkeypatch.setenv("MXNET_HISTORY_DIR", str(hist_dir))
    # env alone is not enough: earlier tests in the same process may
    # leave a process-local config override, and overrides win over
    # the environment — pin ours and drop it afterwards.
    _cfg.set("MXNET_HISTORY_DIR", str(hist_dir))
    _hist.reset()
    autotune.reset()
    _costs._HEURISTIC_WARNED.clear()
    yield tmp_path
    _cfg.unset("MXNET_HISTORY_DIR")
    _hist.reset()
    autotune.reset()
    _costs._HEURISTIC_WARNED.clear()


def _layer(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _params(n, dim, seed=3):
    rng = np.random.RandomState(seed)
    return [{"w": jnp.asarray(rng.randn(dim, dim)
                              .astype(np.float32) * 0.1),
             "b": jnp.asarray(rng.randn(dim).astype(np.float32))}
            for _ in range(n)]


# -- stacking ----------------------------------------------------------
class TestStacking:
    def test_stack_unstack_roundtrip(self):
        params = _params(3, 8)
        stacked = stacking.stack_params(params)
        assert stacked["w"].shape == (3, 8, 8)
        back = stacking.unstack_params(stacked)
        assert len(back) == 3
        for a, b in zip(params, back):
            assert np.array_equal(np.asarray(a["w"]),
                                  np.asarray(b["w"]))
            assert np.array_equal(np.asarray(a["b"]),
                                  np.asarray(b["b"]))

    def test_stackable_rejects_mismatch(self):
        good = _params(2, 8)
        assert stacking.stackable(good)
        ragged = _params(1, 8) + _params(1, 4)
        assert not stacking.stackable(ragged)
        with pytest.raises(ValueError):
            stacking.stack_params(ragged)
        # structure mismatch, not just shapes
        odd = [good[0], {"w": good[1]["w"]}]
        assert not stacking.stackable(odd)

    def test_parity_is_bitwise(self):
        params = _params(4, 8)
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 8).astype(np.float32))
        rep = stacking.verify_parity(_layer, params, x)
        assert rep["ok"] and rep["bitwise"]
        assert rep["max_abs_diff"] == 0.0
        assert rep["n_layers"] == 4

    def test_measure_counts_and_fields(self):
        params = _params(4, 8)
        x = jnp.ones((2, 8), jnp.float32)
        m = stacking.measure(_layer, params, x, calls=3,
                             label="test.measure")
        assert m["executables_unstacked"] == 4
        assert m["executables_stacked"] == 1
        assert m["parity_ok"]
        assert m["compile_wall_unstacked_s"] > 0
        assert m["compile_wall_stacked_s"] > 0
        assert m["dispatch_unstacked_us"] >= 0


# -- durable history as tuner input ------------------------------------
class TestHistoryAsTunerInput:
    def test_cost_rows_across_runs_with_torn_tail(self, fresh):
        d = str(fresh / "hist")
        w1 = _hist.HistoryWriter(directory=d, run="run-one")
        w2 = _hist.HistoryWriter(directory=d, run="run-two")
        w1.append("cost", "train.step[0]", 1.0,
                  labels={"kind": "step"}, bytes_accessed=64e6)
        w2.append("cost", "train.step[0]", 1.0,
                  labels={"kind": "step"}, bytes_accessed=96e6)
        w2.append("cost", "other.fn", 1.0, labels={"kind": "serve"},
                  bytes_accessed=1e6)
        with open(w2.path, "a") as f:
            f.write('{"kind": "cost", "name": "torn')   # killed writer
        rows = _hist.query(name="train.step", kind="cost", directory=d)
        assert len(rows) == 2
        assert {r["run"] for r in rows} == {"run-one", "run-two"}
        # labeled split: the label subset filter selects per kind
        serve_rows = _hist.query(kind="cost", labels={"kind": "serve"},
                               directory=d)
        assert [r["name"] for r in serve_rows] == ["other.fn"]

    def test_modeled_tier_uses_measured_bytes(self, fresh):
        # cost rows (no probes) -> the 1/32 rule on MEASURED traffic,
        # not on param bytes
        _hist.record("cost", "train.step[abc]", 1.0,
                     labels={"kind": "step"}, bytes_accessed=256e6)
        cap = autotune.suggest_bucket_cap(4 * 1024, 8,
                                          label="train.step")
        assert cap == pytest.approx(256e6 / 32.0 / 1e6)
        dec = autotune.decisions()[-1]
        assert dec["source"] == "modeled"
        assert dec["evidence"]["basis_bytes"] == int(256e6)

    def test_two_process_proof(self, fresh):
        """Run 1 (a real child process) writes probe rows; run 2 (this
        process) tunes from them — the cross-run contract."""
        d = str(fresh / "hist")
        child = (
            "from incubator_mxnet_tpu.telemetry import history\n"
            "p = {'knob': 'zero_bucket_mb', 'label': 'twoproc'}\n"
            "history.record('autotune', 'probe', 900.0,"
            " labels=dict(p, value='1.0'))\n"
            "history.record('autotune', 'probe', 400.0,"
            " labels=dict(p, value='4.0'))\n"
            "print(history.get_writer().run)\n")
        env = dict(os.environ, MXNET_HISTORY_DIR=d,
                   JAX_PLATFORMS="cpu")
        res = subprocess.run([sys.executable, "-c", child],
                             capture_output=True, text=True,
                             timeout=120, env=env, cwd=_ROOT)
        assert res.returncode == 0, res.stderr
        child_run = res.stdout.strip().splitlines()[-1]
        assert child_run != _hist.get_writer().run
        cap = autotune.suggest_bucket_cap(512 * 1024 * 1024, 8,
                                          label="twoproc")
        assert cap == 4.0
        dec = autotune.decisions()[-1]
        assert dec["source"] == "measured"
        assert child_run in dec["evidence"]["runs"]


# -- the autotuner evidence ladder -------------------------------------
class TestAutotune:
    def test_measured_argmin_and_delta(self, fresh):
        for val, score in [(1.0, 900.0), (4.0, 500.0), (16.0, 700.0)]:
            autotune.note_probe("zero_bucket_mb", "tune.me", val,
                                score)
        cap = autotune.suggest_bucket_cap(512 * 1024 * 1024, 8,
                                          label="tune.me")
        assert cap == 4.0
        dec = autotune.decisions()[-1]
        assert dec["source"] == "measured"
        assert dec["evidence"]["rows"] == 3
        assert set(dec["evidence"]["candidates"]) == \
            {"1.0", "4.0", "16.0"}
        # the tuned-vs-heuristic delta rides on the record
        assert dec["heuristic"] == _costs.suggest_bucket_mb(
            512 * 1024 * 1024, 8)
        assert dec["delta_vs_heuristic"] == \
            pytest.approx(4.0 - dec["heuristic"])
        # and the decision itself is durable for the NEXT run
        rows = _hist.query(name="decision", kind="autotune",
                           labels={"knob": "zero_bucket_mb"})
        assert rows and rows[-1]["labels"]["source"] == "measured"

    def test_one_distinct_value_is_not_evidence(self, fresh):
        autotune.note_probe("zero_bucket_mb", "thin", 4.0, 500.0)
        autotune.note_probe("zero_bucket_mb", "thin", 4.0, 510.0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            autotune.suggest_bucket_cap(8 << 20, 4, label="thin")
        assert autotune.decisions()[-1]["source"] == "heuristic"

    def test_heuristic_fallback_warns_once_with_label(self, fresh):
        with pytest.warns(UserWarning, match="DECIDING.*cold.one"):
            autotune.suggest_bucket_cap(8 << 20, 4, label="cold.one")
        # warn-once: the same label does not warn again
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            autotune.suggest_bucket_cap(8 << 20, 4, label="cold.one")
        assert autotune.decisions()[-1]["source"] == "heuristic"

    def test_plain_suggest_bucket_mb_does_not_warn(self, fresh):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            got = _costs.suggest_bucket_mb(int(64e6), 1)
        assert got == 2.0

    def test_disabled_returns_fallback_silently(self, fresh,
                                                monkeypatch):
        monkeypatch.setenv("MXNET_AUTOTUNE", "0")
        for val, score in [(1.0, 900.0), (4.0, 500.0)]:
            autotune.note_probe("zero_bucket_mb", "off", val, score)
        cap = autotune.suggest_bucket_cap(512 * 1024 * 1024, 8,
                                          label="off")
        assert cap != 4.0               # probes ignored when disabled
        assert autotune.decisions() == []

    def test_batch_and_serve_and_donate_knobs(self, fresh):
        assert autotune.suggest_batch_size("b", (8, 32), default=32) \
            == 32
        autotune.note_probe("batch_size", "b", 8, 10.0)
        autotune.note_probe("batch_size", "b", 32, 4.0)
        assert autotune.suggest_batch_size("b", (8, 32)) == 32
        assert autotune.suggest_serve_buckets("s", (1, 8)) == (1, 8)
        autotune.note_probe("serve_buckets", "s", "1,8", 20.0)
        autotune.note_probe("serve_buckets", "s", "1,8,32", 9.0)
        assert autotune.suggest_serve_buckets("s", (1, 8)) == (1, 8, 32)
        _hist.record("cost", "d.step", 1.0, labels={"kind": "step"},
                     donated_bytes=4096, argument_bytes=8192)
        assert autotune.suggest_donate("d.step") is True
        assert autotune.decisions()[-1]["source"] == "measured"

    def test_remat_flips_on_measured_temp_bytes(self, fresh):
        assert autotune.suggest_remat("r.step", 1 << 30) is False
        _hist.record("cost", "r.step", 1.0, labels={"kind": "step"},
                     temp_bytes=2 << 30)
        assert autotune.suggest_remat("r.step", 1 << 30) is True
        assert autotune.suggest_remat("r.step", 4 << 30) is False

    def test_bucketplan_steered_by_tuner(self, fresh):
        for val, score in [(2.0, 300.0), (8.0, 120.0)]:
            autotune.note_probe("zero_bucket_mb", "bp.test", val,
                                score)
        plan = BucketPlan({"w%d" % i: (256, 256) for i in range(8)},
                          n_shards=2, cap_mb=0, label="bp.test")
        assert plan.cap_mb == 8.0
        assert autotune.decisions()[-1]["knob"] == "zero_bucket_mb"


# -- blackbox / teletop visibility -------------------------------------
class TestVisibility:
    def test_blackbox_carries_autotune_block(self, fresh):
        autotune.note_probe("zero_bucket_mb", "bb.see", 1.0, 900.0)
        autotune.note_probe("zero_bucket_mb", "bb.see", 4.0, 400.0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            autotune.suggest_bucket_cap(8 << 20, 4, label="bb.see")
        path = _bb.dump_blackbox(path=str(fresh / "bb.json"),
                                 reason="test")
        with open(path) as f:
            doc = json.load(f)
        blk = doc.get("autotune")
        assert blk and blk["decisions"]
        dec = blk["decisions"][-1]
        assert dec["knob"] == "zero_bucket_mb"
        assert dec["label"] == "bb.see"
        assert dec["chosen"] == 4.0

    def test_teletop_renders_autotune_rows(self, fresh):
        from incubator_mxnet_tpu.tools.teletop import _autotune_lines
        blk = {"decisions": [
            {"knob": "zero_bucket_mb", "label": "train.step",
             "chosen": 4.0, "source": "measured", "heuristic": 16.0}]}
        text = "\n".join(_autotune_lines(blk))
        assert "autotune" in text
        assert "zero_bucket_mb" in text and "measured" in text
        assert _autotune_lines(None) == []


# -- the CI gate (slow) ------------------------------------------------
@pytest.mark.slow
class TestCompileGate:
    def test_gate_passes_or_skips(self):
        res = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "check_compile.py")],
            capture_output=True, text=True, timeout=900, cwd=_ROOT)
        assert res.returncode == 0, \
            "gate failed:\n%s\n%s" % (res.stdout, res.stderr)
