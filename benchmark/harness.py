"""What every run shares: finding a cell's parts by name, the device
facts, quantiles, the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
`BENCHMARK.json` names them and the files under `benchmark/` are found
from those names (see README.md).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_file(kind, filename, base=HERE):
    """`<base>/<kind>/<filename>`, or the same under this directory: a cell
    kept elsewhere (a test's throw-away root) names parts that live here."""
    for root in (base, HERE):
        path = os.path.join(root, kind, filename)
        if os.path.isfile(path):
            return path
    raise BenchError("no %s/%s under %s" % (kind, filename, base))


def load_module(kind, name, base=HERE):
    """`benchmark/<kind>/<name>.py` as a module, found by name."""
    path = find_file(kind, name + ".py", base)
    mod_name = "benchmark_%s_%s" % (kind, name.replace(".", "_").replace("-", "_"))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench, workload, root=ROOT):
    """The cell's entry, its configuration (file read) and its traffic."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError("unknown workload %r; BENCHMARK.json has %s"
                         % (workload, sorted(cells)))
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    base = os.path.join(root, os.path.dirname(os.path.dirname(conf["file"])))
    with open(os.path.join(base, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic, base


def metrics_for(bench, workload, section):
    """Metric entries of `section` that this cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" not in m or workload in m["workloads"]:
            out.append(m)
    return out


def peaks_for(kind):
    table = load_json("peaks.json")
    if kind not in table:
        raise BenchError("device kind %r is not in benchmark/peaks.json "
                         "(known: %s): add its published peaks with their "
                         "source" % (kind, sorted(k for k in table
                                                  if not k.startswith("_"))))
    return table[kind]


def require_chips(n, allow_cpu=False):
    """The devices of the run: `n` TPU chips, or an error.  `allow_cpu`
    is the rehearsal (tests, README): it never prints a result line."""
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and not allow_cpu:
        raise BenchError("JAX found no accelerator: platform %r" % plat)
    if len(devs) < n:
        raise BenchError("the cell asks for %d chip(s), JAX found %d"
                         % (n, len(devs)))
    return devs[:n]


def device_facts(devs, rehearse=False):
    """`device` of the result line, as JAX reports it."""
    peak = 0
    for d in devs:
        ms = d.memory_stats()
        if ms is None and rehearse:
            continue
        if ms is None:
            raise BenchError("device %s reports no memory_stats()" % d)
        peak = max(peak, int(ms["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def quantile(xs, q):
    """Linear-interpolated quantile of all of `xs`; +inf entries (failed
    requests) sort last, so a tail that reaches them reads inf."""
    xs = sorted(xs)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) and pos > lo:
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None):
    """The last line of stdout.  `compared` (each number beside its
    limit) comes last, as the contract asks."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return json.dumps(line)
