"""Where the benchmark's files are, for its own tests.  The yardstick is a
directory of scripts, not a package: its modules are loaded by path."""
import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def load(name, sub=None):
    """A module of the benchmark by file name (`sub`: its directory)."""
    for p in (BENCH, REPO):
        if p not in sys.path:
            sys.path.insert(0, p)
    if sub is None:
        return importlib.import_module(name)
    import harness
    return harness.load_module(sub, name)


def compared(err):
    """{name: (value, limit)} from the `compared` lines a run prints to stderr."""
    out = {}
    for line in err.splitlines():
        if line.startswith("compared "):
            _, name, _, value, _, limit = line.split()[:6]
            out[name] = (float(value), None if limit == "None" else float(limit))
    return out
