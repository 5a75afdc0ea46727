"""teletop — the `top(1)` of the telemetry ledger.

Renders one table from a `MetricsExporter` snapshot: counters, latency
percentiles (p50/p90/p99 per observed series), and the derived health
ratios operators actually page on (serving batch fill vs pad waste,
feed stall fraction, AOT hit rate, skipped-step rate).

Sources (one of):

    python -m incubator_mxnet_tpu.tools.teletop --url http://host:9100
        scrape a live `telemetry.start()` endpoint (`/metrics.json`)
    python -m incubator_mxnet_tpu.tools.teletop --file snap.json
        a JSON snapshot written by `MetricsExporter.export_file()` /
        the periodic exporter (MXNET_TELEMETRY_EXPORT_PATH)

With neither, MXNET_TELEMETRY_PORT (when nonzero) implies
`--url http://127.0.0.1:$MXNET_TELEMETRY_PORT`.  `--watch S` redraws
every S seconds (live mode); `--prefix serve.` filters the table.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

__all__ = ["load_snapshot", "render", "main"]


def load_snapshot(url=None, path=None) -> dict:
    """One `{counters, percentiles, ...}` snapshot from an endpoint or
    an exporter JSON file."""
    if url:
        import urllib.request
        base = url.rstrip("/")
        if not base.endswith((".json", "/json")):
            base += "/metrics.json"
        with urllib.request.urlopen(base, timeout=10) as r:
            return json.loads(r.read().decode())
    with open(path) as f:
        snap = json.loads(f.read())
    # bench fixtures: a BENCH_r*/BENCH_serve blob (or its parsed line)
    # carries the snapshot as a nested "telemetry" block — unwrap it
    if "counters" not in snap:
        inner = snap.get("telemetry") or \
            snap.get("parsed", {}).get("telemetry")
        if isinstance(inner, dict):
            snap = inner
    return snap


def _ratio(num, den):
    return (100.0 * num / den) if den else None


def _derived(c):
    """The fill/waste/health ratios, from whatever families are
    present (missing subsystems simply contribute no rows)."""
    out = []
    fill, waste = c.get("serve.batch_fill", 0), c.get("serve.pad_waste", 0)
    r = _ratio(fill, fill + waste)
    if r is not None:
        out.append(("serve batch fill", "%.1f%% (pad waste %.1f%%)"
                    % (r, 100 - r)))
    stall, step = c.get("feed.stall_us", 0), c.get("feed.step_us", 0)
    r = _ratio(stall, stall + step)
    if r is not None:
        out.append(("feed stall fraction",
                    "%.1f%% of consumer wall" % r))
    steps = c.get("train.steps", 0)
    if steps:
        out.append(("train steps skipped", "%d / %d (%.2f%%)"
                    % (c.get("train.steps_skipped", 0), steps,
                       _ratio(c.get("train.steps_skipped", 0), steps))))
        dw, tot = c.get("train.data_wait_us", 0), c.get("train.step_us", 0)
        r = _ratio(dw, tot)
        if r is not None:
            out.append(("train data-wait share", "%.1f%% of step wall" % r))
    req, rej = c.get("serve.requests", 0), c.get("serve.rejected", 0)
    if req or rej:
        out.append(("serve rejected", "%d (%.2f%% of %d accepted+rej)"
                    % (rej, _ratio(rej, req + rej) or 0.0, req + rej)))
    if c.get("mesh.straggler"):
        out.append(("fleet stragglers", "%d flagged (%d recovered) — "
                    "see the fleet table / mesh.straggler events"
                    % (c["mesh.straggler"],
                       c.get("mesh.straggler_recovered", 0))))
    if c.get("blackbox.dumps"):
        out.append(("blackbox dumps", "%d written this process"
                    % c["blackbox.dumps"]))
    return out


def _fmt_qty(v, unit=""):
    v = float(v)
    for mag, suf in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= mag:
            return "%.2f%s%s" % (v / mag, suf, unit)
    return "%g%s" % (v, unit)


def _cost_lines(costs):
    """The executable cost block (ISSUE 5) as table lines: per-row
    kind/label/calls/flops/bytes/compile columns plus the totals."""
    rows = costs.get("rows", [])
    if not rows and not costs.get("totals"):
        return []
    lines = ["", "%-6s %-28s %8s %10s %10s %9s"
             % ("kind", "executable", "calls", "flops", "bytes",
                "compile_s"), "-" * 78]
    for r in rows[:15]:
        lines.append("%-6s %-28s %8d %10s %10s %9.2f"
                     % (str(r.get("kind", "?"))[:6],
                        str(r.get("label", "?"))[:28],
                        r.get("invocations", 0),
                        _fmt_qty(r.get("flops", 0)),
                        _fmt_qty(r.get("bytes_accessed", 0), "B"),
                        r.get("compile_wall_s", 0)))
    t = costs.get("totals", {})
    if t:
        lines.append("TOTAL  %-28s %8d %10s %10s %9.2f"
                     % ("(cumulative)", t.get("invocations", 0),
                        _fmt_qty(t.get("cum_flops", 0)),
                        _fmt_qty(t.get("cum_bytes", 0), "B"),
                        t.get("compile_wall_s", 0)))
        if t.get("hbm_peak_bytes"):
            lines.append("%-35s %s" % ("hbm peak",
                                       _fmt_qty(t["hbm_peak_bytes"],
                                                "B")))
    return lines


def _autotune_lines(tune):
    """The compile-loop block (ISSUE 18) as table rows, next to the
    cost table: one line per autotune decision — knob, label, chosen
    value, evidence tier, the heuristic's answer (the tuned-vs-
    heuristic delta an operator audits)."""
    decs = (tune or {}).get("decisions") or []
    if not decs:
        return []
    lines = ["", "autotune (%d decision(s))" % len(decs),
             "%-14s %-22s %12s %-10s %12s"
             % ("knob", "label", "chosen", "source", "heuristic"),
             "-" * 78]
    for d in decs[-15:]:
        heur = d.get("heuristic")
        lines.append("%-14s %-22s %12s %-10s %12s"
                     % (str(d.get("knob", "?"))[:14],
                        str(d.get("label", ""))[:22],
                        str(d.get("chosen", "?"))[:12],
                        str(d.get("source", "?"))[:10],
                        "" if heur is None else str(heur)[:12]))
    return lines


def _fleet_lines(fleet):
    """The merged per-replica fleet view (ISSUE 11) as one table:
    a row per replica — step, step/dispatch/collective µs, HBM peak
    — with stragglers marked ``*SLOW*``."""
    reps = (fleet or {}).get("replicas") or {}
    if not reps:
        return []
    stragglers = {str(r) for r in fleet.get("stragglers", ())}
    lines = ["", "fleet (per replica%s)" % (
        ", straggler window=%s sigma=%s"
        % (fleet.get("straggler_window", "?"),
           fleet.get("straggler_sigma", "?"))),
        "%-8s %8s %10s %10s %10s %10s %s"
        % ("replica", "step", "step_us", "disp_us", "coll_us",
           "hbm_peak", ""),
        "-" * 78]
    for rid in sorted(reps, key=lambda r: int(r)):
        row = reps[rid]
        lines.append(
            "%-8s %8d %10d %10d %10d %10s %s"
            % (rid, row.get("step", 0), row.get("step_us", 0),
               row.get("dispatch_us", 0), row.get("collective_us", 0),
               _fmt_qty(row.get("hbm_peak_bytes", 0), "B"),
               "*SLOW*" if rid in stragglers else ""))
    return lines


def _slo_lines(slo):
    """The SLO rule/alert block (ISSUE 12) as table rows: one ALERT
    line per firing rule (with its evidence), one quiet line per
    registered-but-clear rule — an operator's eye lands on the
    alerts, and 'no rules registered' is distinguishable from 'all
    clear'."""
    if not slo or not (slo.get("rules") or slo.get("active")):
        return []
    active = slo.get("active") or {}
    rules = slo.get("rules") or []
    lines = ["", "slo (%d rule(s), %d firing)"
             % (len(rules), len(active)), "-" * 46]
    for name in sorted(active):
        info = active[name]
        extra = " ".join(
            "%s=%s" % (k, info[k]) for k in sorted(info)
            if k != "since" and isinstance(info[k],
                                           (int, float, str, bool)))
        lines.append("ALERT  %-28s %s" % (name, extra[:44]))
    for r in rules:
        if r.get("rule") in active:
            continue
        lines.append("ok     %-28s %s" % (r.get("rule", "?"),
                                          r.get("kind", "")))
    return lines


def _reqtrace_lines(rt):
    """The request-journal block (ISSUE 19) as table rows: one line
    per (engine, lane) — window size, rolling p99, and the SLOWEST
    retired request's rid / e2e / dominant phase — then one line per
    recent promoted exemplar, so the operator's eye goes from 'lane
    p99 is high' straight to WHICH request and WHICH phase."""
    if not rt:
        return []
    journals = rt.get("journals") or []
    exemplars = rt.get("exemplars") or []
    if not journals and not exemplars:
        return []
    lines = ["", "reqtrace (%d journal(s), %d exemplar(s))"
             % (len(journals), len(exemplars)),
             "%-6s %-10s %-8s %6s %10s %8s %10s %-10s"
             % ("kind", "model", "lane", "win", "p99_us", "rid",
                "slow_us", "dominant"),
             "-" * 78]
    for j in journals:
        for lane in sorted(j.get("lanes") or {}):
            row = j["lanes"][lane]
            slow = row.get("slowest") or {}
            p99 = row.get("p99_us")
            lines.append(
                "%-6s %-10s %-8s %6d %10s %8s %10s %-10s"
                % (str(j.get("engine", "?"))[:6],
                   str(j.get("model", ""))[:10], str(lane)[:8],
                   row.get("window_n", 0),
                   "-" if p99 is None else "%d" % p99,
                   slow.get("rid", "-"),
                   "-" if "e2e_us" not in slow
                   else "%d" % slow["e2e_us"],
                   str(slow.get("dominant", ""))[:10]))
    for ex in exemplars[-8:]:
        phases = ex.get("phases") or {}
        water = " ".join("%s=%d" % (k, v) for k, v in sorted(
            phases.items(), key=lambda kv: -kv[1])[:4])
        lines.append(
            "  #%-6s %-6s %-8s %-9s %9dus %s"
            % (ex.get("rid", "?"), str(ex.get("engine", "?"))[:6],
               str(ex.get("lane", "-"))[:8],
               str(ex.get("status", "?"))[:9],
               int(ex.get("e2e_us", 0)), water[:40]))
    return lines


def _memwatch_lines(mw):
    """The memory-observatory block (ISSUE 20) as table rows: one
    line per device (used / peak watermark / limit, with the sampling
    source — ``memory_stats`` vs the ``live_arrays`` fallback —
    spelled out), then the tenant attribution join: committed ledger
    bytes vs the measured share, and the drift ratio an operator
    reads before the MemDriftRule pages them."""
    if not mw or not mw.get("sample"):
        return []
    smp = mw.get("sample") or {}
    devices = smp.get("devices") or {}
    marks = mw.get("watermarks") or {}
    lines = ["", "memwatch (phase=%s, sample %s%s)"
             % (mw.get("phase", "?"), smp.get("tag", "?"),
                "" if mw.get("fresh", True) else ", STALE"),
             "%-12s %10s %10s %10s %-12s"
             % ("device", "used", "peak", "limit", "source"),
             "-" * 60]
    for dev in sorted(devices):
        row = devices[dev]
        # the highest watermark across phases — the per-phase split
        # lives in the block for the autopsy
        peak = max([row.get("peak_bytes", 0)] +
                   [m.get(dev, 0) for m in marks.values()])
        lim = row.get("limit_bytes", 0)
        lines.append("%-12s %10s %10s %10s %-12s"
                     % (dev[:12], _fmt_qty(row.get("used_bytes", 0), "B"),
                        _fmt_qty(peak, "B"),
                        _fmt_qty(lim, "B") if lim else "-",
                        str(row.get("source", "?"))[:12]))
    attr = mw.get("attribution") or []
    if attr:
        lines += ["%-22s %-10s %10s %10s %7s %-6s"
                  % ("tenant", "device", "committed", "measured",
                     "drift", "kind"),
                  "-" * 72]
        for r in attr[:12]:
            drift = r.get("drift")
            lines.append(
                "%-22s %-10s %10s %10s %7s %-6s"
                % (str(r.get("tenant", "?"))[:22],
                   str(r.get("device", "?"))[:10],
                   _fmt_qty(r.get("committed_bytes", 0), "B"),
                   _fmt_qty(r.get("measured_bytes", 0), "B"),
                   "-" if drift is None else "%.2fx" % drift,
                   str(r.get("kind", ""))[:6]))
    return lines


def render(snap: dict, prefix: str = "") -> str:
    """The snapshot as one fixed-width table block."""
    counters = {k: v for k, v in snap.get("counters", {}).items()
                if k.startswith(prefix)}
    pcts = {k: v for k, v in snap.get("percentiles", {}).items()
            if k.startswith(prefix)}
    sampled_companions = {n + ".n" for n in pcts}
    lines = []
    ts = snap.get("ts")
    head = "teletop — %d counters, %d sampled series" \
        % (len(counters), len(pcts))
    if ts:
        head += " — " + time.strftime("%Y-%m-%d %H:%M:%S",
                                      time.localtime(ts))
    lines += [head, "=" * len(head), ""]

    lines.append("%-36s %14s" % ("counter", "value"))
    lines.append("-" * 51)
    for name in sorted(counters):
        if name in sampled_companions:
            continue            # shown as n in the percentile table
        lines.append("%-36s %14d" % (name, counters[name]))

    if pcts:
        lines += ["", "%-36s %8s %10s %10s %10s"
                  % ("series", "n", "p50", "p90", "p99"),
                  "-" * 78]
        for name in sorted(pcts):
            p = pcts[name]
            fmt = lambda k: ("%10g" % p[k]) if k in p else "%10s" % "-"
            lines.append("%-36s %8d %s %s %s"
                         % (name, p.get("n", 0), fmt("p50"),
                            fmt("p90"), fmt("p99")))

    costs = snap.get("costs")
    if isinstance(costs, dict):
        # a bench "telemetry" block carries totals only; a full
        # exporter snapshot carries rows+totals — render what's there
        lines += _cost_lines(costs if "rows" in costs
                             else {"rows": [], "totals": costs})

    # the compile-loop decisions ride next to the cost table they
    # were trained on (blackbox dumps carry the block; a live
    # exporter snapshot without one contributes no rows)
    lines += _autotune_lines(snap.get("autotune"))

    lines += _fleet_lines(snap.get("fleet"))
    lines += _slo_lines(snap.get("slo"))
    lines += _reqtrace_lines(snap.get("reqtrace"))
    lines += _memwatch_lines(snap.get("memwatch"))

    derived = _derived(snap.get("counters", {}))
    if derived:
        lines += ["", "derived", "-" * 7]
        for k, v in derived:
            lines.append("%-24s %s" % (k, v))
    return "\n".join(lines)


def main(argv=None) -> int:
    from .. import config as _cfg
    ap = argparse.ArgumentParser(
        prog="teletop",
        description="table view of the telemetry counters/percentiles")
    ap.add_argument("--url", help="telemetry endpoint base URL "
                    "(e.g. http://host:9100)")
    ap.add_argument("--file", help="exporter JSON snapshot file")
    ap.add_argument("--prefix", default="",
                    help="only show names with this prefix "
                    "(e.g. serve.)")
    ap.add_argument("--watch", type=float, default=0.0, metavar="S",
                    help="redraw every S seconds (live sources)")
    args = ap.parse_args(argv)
    url, path = args.url, args.file
    if not url and not path:
        port = int(_cfg.get("MXNET_TELEMETRY_PORT"))
        if not port:
            ap.error("need --url or --file (or MXNET_TELEMETRY_PORT)")
        url = "http://127.0.0.1:%d" % port
    while True:
        try:
            snap = load_snapshot(url=url, path=path)
        except Exception as e:      # noqa: BLE001 — operator tool:
            print("teletop: cannot read %s: %s"
                  % (url or path, e), file=sys.stderr)
            return 1
        out = render(snap, prefix=args.prefix)
        if args.watch > 0:
            sys.stdout.write("\x1b[2J\x1b[H" + out + "\n")
            sys.stdout.flush()
            try:
                time.sleep(args.watch)
            except KeyboardInterrupt:
                return 0
        else:
            print(out)
            return 0


if __name__ == "__main__":
    sys.exit(main())
