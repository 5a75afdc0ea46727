"""Fixtures of the benchmark's own tests."""
import json
import os

import pytest

from benchpaths import REPO, load


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def run_cell(tmp_path, monkeypatch, capsys):
    """Drive `benchmark/run.py --rehearse` in this process, on the CPU that
    tests/conftest.py forced, with the compile cache in a temporary
    directory.  Returns the rehearsal line and what was printed to stderr."""

    def go(root, workload, *extra):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
        run = load("run")
        monkeypatch.setattr(run, "ROOT", str(tmp_path))     # scratch goes there
        rc = run.main(["--root", root, "--workload", workload, "--seed", "7",
                       "--seconds", "2", "--rehearse", *extra])
        assert rc == 0
        out = capsys.readouterr()
        return json.loads(out.out.strip().splitlines()[-1]), out.err
    return go
