"""No silent CPU, one compile cache, and chip_smoke.py's exit contract
(ISSUE 21).  Everything here runs on the CPU: what it checks is that the
paths which need a chip SAY so instead of running on the host."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.ops import attention as att

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
_CACHE_PY = os.path.join(REPO, "incubator_mxnet_tpu", "compile_cache.py")


# -- contexts ----------------------------------------------------------

@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_on_cpu(make):
    with pytest.raises(MXNetError, match="'cpu'"):
        make(0).jax_device
    assert mx.num_tpus() == 0 and mx.num_gpus() == 0


def test_cpu_context_resolves():
    assert mx.cpu(0).jax_device.platform == "cpu"


# -- forced kernels ----------------------------------------------------

def _qkv(T=256):
    rs = np.random.RandomState(5)
    return tuple(jnp.asarray(rs.randn(1, 2, T, 32).astype(np.float32)
                             * 0.5) for _ in range(3))


def test_forced_pallas_raises_without_tpu(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "2")
    monkeypatch.delenv("MXNET_PALLAS_INTERPRET", raising=False)
    with pytest.raises(MXNetError, match="backend is 'cpu'"):
        att.flash_attention(*_qkv())
    # auto mode keeps its quiet dispatch to the reference
    monkeypatch.setenv("MXNET_USE_PALLAS", "1")
    assert att.flash_attention(*_qkv()).shape == (1, 2, 256, 32)


def test_forced_pallas_raises_when_blocks_do_not_tile(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "2")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", "96")
    with pytest.raises(MXNetError, match="does not tile"):
        att.flash_attention(*_qkv())


def test_forced_pallas_interpret_matches_naive(monkeypatch):
    monkeypatch.setenv("MXNET_USE_PALLAS", "2")
    monkeypatch.setenv("MXNET_FLASH_BWD_PALLAS", "2")
    monkeypatch.setenv("MXNET_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("MXNET_FLASH_BLOCK_K", "128")
    q, k, v = _qkv()
    out = att.flash_attention(q, k, v, causal=True)
    ref = att.naive_attention(q, k, v, 1.0 / np.sqrt(32), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- the compile-cache rule (no jax: the module is loaded by path) -----

def _load_cache_module():
    spec = importlib.util.spec_from_file_location("_cc", _CACHE_PY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cache_dir_follows_env(monkeypatch, tmp_path):
    cc = _load_cache_module()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.cache_dir() == str(tmp_path)
    assert cc.entry_count(str(tmp_path / "absent")) == 0


def test_cache_dir_default_is_fixed_and_ignored():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("import importlib.util as u, sys;"
            "s = u.spec_from_file_location('_cc', sys.argv[1]);"
            "m = u.module_from_spec(s); s.loader.exec_module(m);"
            "assert 'jax' not in sys.modules; print(m.cache_dir())")
    seen = {subprocess.run([sys.executable, "-c", code, _CACHE_PY],
                           env=env, capture_output=True, text=True,
                           check=True, timeout=60).stdout.strip()
            for _ in range(2)}
    assert seen == {os.path.join(REPO, ".jax_cache")}
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


# -- chip_smoke.py -----------------------------------------------------

def _smoke(tmp_path, *flags, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("MXNET_PALLAS_INTERPRET", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *flags],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=timeout)


def test_chip_smoke_fails_without_a_chip(tmp_path):
    res = _smoke(tmp_path)
    assert res.returncode != 0
    assert "no TPU" in res.stderr and "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout          # no result is printed


def _lines(res):
    return [json.loads(ln) for ln in res.stdout.splitlines()
            if ln.startswith("{")]


def _is_result_line(res, summary):
    """The last line of stdout is the result object the chip check
    parses: exactly these keys, nothing beside them."""
    last = json.loads(res.stdout.splitlines()[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    assert "ok" not in summary and "device" not in summary


def test_chip_smoke_dry_run_kernels(tmp_path):
    res = _smoke(tmp_path, "--cpu-dry-run", "--phases", "kernels")
    assert res.returncode == 0, res.stderr[-2000:]
    lines = _lines(res)
    assert [ln["phase"] for ln in lines[:-1]] == ["env", "kernels",
                                                  "summary"]
    for ln in lines[:-2]:
        assert ln["platform"] == "cpu" and ln["dry_run"] is True
        assert ln["cache_dir"] == str(tmp_path / "cache")
    assert lines[-2]["partial"] is True and lines[-2]["dry_run"] is True
    _is_result_line(res, lines[-2])


@pytest.mark.slow
def test_chip_smoke_dry_run_all_phases(tmp_path):
    res = _smoke(tmp_path, "--cpu-dry-run", timeout=1200)
    assert res.returncode == 0, res.stderr[-2000:]
    lines = _lines(res)
    phases = ["env", "kernels", "train_resnet50", "train_bert_base",
              "serve_nmt", "multichip"]
    assert [ln["phase"] for ln in lines[:-2]] == phases
    assert lines[-2] == {"phase": "summary", "smoke": True,
                         "dry_run": True, "phases": phases}
    _is_result_line(res, lines[-2])
    # entries landed where the variable said, and only there
    assert lines[-3]["cache_entries_after"] > 0
    assert os.listdir(tmp_path / "cache")
