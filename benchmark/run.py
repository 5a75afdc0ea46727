#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's parts are found by name (README.md): its configuration file
and builder, its traffic file and generator, its driver, its plain
reference, and one reader for each per-layer metric.  `--trace 0` prints
the cell's end-to-end metrics, `--trace 1` its per-layer metrics and a
breakdown.  The last line of stdout is the result object; the numbers
that decided `correct` are its last key and the last lines of stderr.

Without an accelerator, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.  `--rehearse` (tests, README)
runs the control flow on the CPU and prints no result line either.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness      # noqa: E402


class RunContext:
    """What a driver is handed, and where it leaves what readers read."""

    def __init__(self, args, bench, cell, config, traffic, base, devices):
        self.workload = args.workload
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.control = args.control or None
        self.fault = args.fault or None
        self.rehearse = bool(args.rehearse)
        self.bench, self.cell = bench, cell
        self.config, self.traffic, self.base = config, traffic, base
        self.devices = devices
        self.t_start = T_START
        self.record = {}
        self.plan = None

    def note(self, msg):
        print("[bench %7.2fs] %s" % (time.monotonic() - T_START, msg),
              file=sys.stderr, flush=True)

    def scratch(self, name):
        """An emptied directory inside the checkout (gitignored)."""
        path = os.path.join(ROOT, ".bench_scratch", self.workload, name)
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        return path


def per_layer(ctx, result):
    """Each per-layer metric of the cell through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in harness.metrics_for(ctx.bench, ctx.workload, "per_layer"):
        with open(harness.find_file("metrics", m["name"] + ".json",
                                    ctx.base)) as f:
            spec = json.load(f)
        reader = harness.load_module("readers", spec["reader"], ctx.base)
        value = reader.read(spec, ctx.record, result)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", default="",
                    help="also read the control of this precision (study)")
    ap.add_argument("--fault", default="",
                    help="plant a named fault in the reference put in the "
                         "program's place (study)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="override the mix's arrival rate (the knee sweep)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: no result line is printed")
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json lies (tests)")
    args = ap.parse_args(argv)

    bench = harness.load_benchmark(args.root)
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    cell, config, traffic, base = harness.find_cell(bench, args.workload,
                                                    args.root)

    if args.rate:
        traffic["rate_per_s"] = args.rate
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("MXNET_PALLAS_INTERPRET", "1")
        if cell["chips"] > 1 and "host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
                " --xla_force_host_platform_device_count=%d" % cell["chips"]
    from incubator_mxnet_tpu import compile_cache
    cache_dir = compile_cache.enable()
    entries_before = compile_cache.entry_count(cache_dir)
    devices = harness.require_chips(cell["chips"], allow_cpu=args.rehearse)

    ctx = RunContext(args, bench, cell, config, traffic, base, devices)
    ctx.note("cell %s seed %d seconds %g trace %d on %s x%d"
             % (args.workload, ctx.seed, ctx.seconds, ctx.trace,
                devices[0].device_kind, len(devices)))
    driver = harness.load_module("drivers", traffic["driver"], base)
    result = driver.run(ctx)
    ctx.record.update(config=config, traffic=traffic)
    ctx.record["cache_entries_added"] = \
        compile_cache.entry_count(cache_dir) - entries_before

    names = {m["name"]: m for m in harness.metrics_for(
        bench, args.workload, "end_to_end")}
    if ctx.trace:
        metrics = per_layer(ctx, result)
        tr = ctx.record.get("trace")
        if tr is None or not tr["busy_s"] > 0:
            raise harness.BenchError("the traced window saw no device work")
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    else:
        missing = set(names) - set(result["end_to_end"])
        if missing:
            raise harness.BenchError("the driver reported no %s"
                                     % sorted(missing))
        metrics = {k: {"value": result["end_to_end"][k],
                       "unit": names[k]["unit"]} for k in names}
        breakdown = None
    for k, v in metrics.items():
        if v["value"] is None or v["value"] != v["value"] or \
                v["value"] in (float("inf"), float("-inf")):
            result["correct"] = False
            result["compared"]["unreadable." + k] = {"value": str(v["value"]),
                                                     "limit": "finite"}
            v["value"] = -1.0
    for k, v in result["compared"].items():
        print("compared %-24s value %s limit %s %s"
              % (k, v["value"], v["limit"],
                 " ".join("%s=%s" % kv for kv in v.items()
                          if kv[0] not in ("value", "limit"))), file=sys.stderr)
    print("correct %s attempted %d failed %d"
          % (result["correct"], result["attempted"], result["failed"]),
          file=sys.stderr, flush=True)
    if args.rehearse:
        ctx.note("rehearsal on %s: no result line" % devices[0].platform)
        print(json.dumps({"rehearsal": True,
                          "end_to_end": sorted(result["end_to_end"]),
                          "per_layer": sorted(metrics) if ctx.trace else [],
                          "correct": result["correct"]}))
        return 0
    print(harness.result_line(result["correct"], result["attempted"],
                              result["failed"], metrics, result["device"],
                              result["compared"], breakdown), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except harness.BenchError as e:
        print("benchmark: %s" % e, file=sys.stderr)
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    if code:
        os._exit(code)      # no result was printed: leave at once
    sys.exit(0)
