"""blackbox — summarize a flight-recorder dump (ISSUE 5).

A black-box dump (telemetry.dump_blackbox / the crash hooks) is a
self-contained forensic JSON: config snapshot, counter ledger,
executable cost table, HBM watermarks, and the last-N event timeline
with an embedded chrome-trace view.  This CLI renders the parts an
operator reads first:

    python -m incubator_mxnet_tpu.tools.blackbox dump.json
    python -m incubator_mxnet_tpu.tools.blackbox dump.json --events 80
    python -m incubator_mxnet_tpu.tools.blackbox dump.json \
        --trace out.trace.json      # extract the chrome-trace view

Sections: header (reason / pid / exception), the timeline tail, the
nonzero counters, the cost table (per-executable FLOPs / bytes /
invocations / compile wall), HBM peaks, and ONE suspected-cause line —
a heuristic ranking of what the evidence points at.

`verify` (ISSUE 9) checks a checkpoint directory against its integrity
manifest without loading it into a trainer:

    python -m incubator_mxnet_tpu.tools.blackbox verify /ckpt/run42
    python -m ... verify /ckpt/run42/step_00000200

Pointed at a single checkpoint it verifies that one; pointed at a
keep-K directory it verifies every published ``step_*`` child.  Exit
code 0 = everything verifiable; != 0 with a per-file / per-leaf report
on any mismatch (the same `integrity.verify_checkpoint` the trainer's
verify-on-load runs).

`merge` (ISSUE 11) joins per-process chrome traces into ONE timeline —
a fleet's forensics are N dumps from N processes, and the question is
always "what was everyone doing at step K":

    python -m ... merge --out fleet.trace.json rank0.json worker.json

Inputs are black-box dumps (their embedded trace view is extracted) or
raw chrome-trace JSONs.  Events keep their own pid rows (process_name
metadata is added), and the summary reports the correlation keys: how
many trace ids and global steps have spans from MORE than one process
— the (trace_id, step) join this PR's propagation exists to make
possible.  Exit code 0 on a merged output, 1 when nothing merged.

`history` (ISSUE 12) renders the durable on-disk telemetry history
(MXNET_HISTORY_DIR shards, telemetry/history.py) as cross-run trends:

    python -m ... history                         # per-run summary
    python -m ... history --name serve.           # trend + sparkline
    python -m ... history --kind cost --name serve.infer
    python -m ... history --diff                  # newest two runs
    python -m ... history --diff RUN_A RUN_B --threshold 15

Without ``--name`` it lists the runs (rows, span, alerts fired) in
the directory.  With one, each matching series gets a row per run —
last value, delta vs the previous run, and a sparkline over the run's
samples.  ``--diff`` compares the last-value-per-series of two runs
using `tools/bench_diff.py`'s direction heuristics (``*_us``/``p99``/
``stale`` lower-better, throughput/hit higher-better), prints the
regressions, and exits 1 when any directional series regressed past
``--threshold`` percent.

`autopsy` (ISSUE 19) renders ONE promoted slow-request exemplar from
a dump's reqtrace block as a per-phase waterfall with a
phase-dominance verdict — the "why was THIS request slow" answer a
firing lane alert attaches to its own dump:

    python -m ... autopsy dump.json               # the worst one
    python -m ... autopsy dump.json --rid 42
    python -m ... autopsy dump.json --lane high --all

`memautopsy` (ISSUE 20) renders a dump's memwatch block as an OOM /
memory-drift post-mortem: the last per-device sample (with its
source — PJRT memory_stats or the live_arrays fallback), the rolling
per-phase peak watermarks, the committed-vs-measured tenant
attribution join, the recent allocation-lifecycle timeline, and a
verdict naming the tenant whose footprint drifted furthest from its
ledger commitment:

    python -m ... memautopsy dump.json
    python -m ... memautopsy dump.json --top 10
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from .teletop import (_autotune_lines, _fleet_lines, _fmt_qty,
                      _memwatch_lines, _reqtrace_lines, _slo_lines)

__all__ = ["load_dump", "render", "suspected_cause", "merge_traces",
           "verify_main", "merge_main", "history_main", "sparkline",
           "autopsy_main", "autopsy_lines", "slow_request_family",
           "memautopsy_main", "memautopsy_lines", "main"]


def load_dump(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema", "").split("/")[0] != "mxtpu-blackbox":
        raise ValueError("%s is not a black-box dump (schema=%r)"
                         % (path, doc.get("schema")))
    return doc


#: dominant phase -> (family, what the operator does about it)
_PHASE_FAMILY = {
    "queue": ("queue-dominated",
              "the request sat waiting for admission — add capacity, "
              "shed earlier, or rebalance lane quotas"),
    "coalesce": ("coalesce-dominated",
                 "the batching window held the request while the "
                 "batch filled — shrink the coalesce delay or the "
                 "batch-size target"),
    "dispatch": ("dispatch-dominated",
                 "the batch waited for a free replica/dispatch slot — "
                 "replicas are saturated or unhealthy"),
    "infer": ("device-dominated",
              "device execution itself was the wall — the batch's "
              "compute, not the serving machinery"),
    "prefill": ("device-dominated",
                "prompt prefill was the wall — long prompts or a "
                "cold prefill executable"),
    "decode": ("decode-dominated",
               "token-by-token decode was the wall — long emissions "
               "or slow decode steps"),
    "join": ("join-dominated",
             "device→host join / fan-out was the wall — D2H "
             "transfers or result distribution"),
    "resolve": ("resolve-dominated",
                "future resolution was the wall — a slow consumer "
                "callback holding the fan-out thread"),
}


def slow_request_family(exemplar: dict):
    """(family, advice) for an exemplar's dominant/budget phase —
    the slow-request taxonomy `suspected_cause` and ``autopsy``
    share."""
    phase = exemplar.get("budget_phase") or exemplar.get("dominant")
    return _PHASE_FAMILY.get(
        phase, ("unattributed", "no phase dominated; read the "
                                "waterfall"))


def _worst_drifter(mw):
    """The attribution row whose measured share strayed furthest from
    its ledger commitment (either direction), ties broken by measured
    bytes — the tenant `memautopsy` and the memwatch: suspected-cause
    line both name.  None when the block carries no judgeable row."""
    rows = [r for r in (mw or {}).get("attribution") or []
            if r.get("committed_bytes", 0) > 0
            and r.get("measured_bytes") is not None]

    def score(r):
        m = float(r.get("measured_bytes", 0))
        c = float(r.get("committed_bytes", 1))
        return ((m / c) if m >= c else
                (float("inf") if m <= 0 else c / m))
    if not rows:
        return None
    return max(rows, key=lambda r: (score(r),
                                    r.get("measured_bytes", 0)))


def suspected_cause(doc: dict) -> str:
    """One line: what the evidence points at, strongest signal first.
    A heuristic, not a verdict — the timeline is the ground truth."""
    c = doc.get("counters", {})
    evs = doc.get("events", [])
    kinds = [e.get("kind") for e in evs]
    exc = doc.get("exception")
    reason = doc.get("reason", "")
    if reason.startswith("memwatch:"):
        # proactive OOM-forensics dump (ISSUE 20): the memwatch block
        # was captured BEFORE the unwind freed the arrays, so the
        # attribution join can still name the tenant — checked ahead
        # of the generic exception line, which would otherwise claim
        # this dump as a mere uncaught RESOURCE_EXHAUSTED
        worst = _worst_drifter(doc.get("memwatch"))
        site = reason.split(":", 2)[-1]
        if worst is not None:
            return ("allocation failure at %r: tenant %r on %s held "
                    "%s measured vs %s committed (%.2fx its ledger "
                    "row) — the leading suspect; run `blackbox "
                    "memautopsy <dump>` for the full join"
                    % (site, worst.get("tenant"), worst.get("device"),
                       _fmt_qty(worst.get("measured_bytes", 0), "B"),
                       _fmt_qty(worst.get("committed_bytes", 0), "B"),
                       worst.get("drift") or 0.0))
        return ("allocation failure at %r — no tenant attribution "
                "available (memwatch block empty or no committed "
                "rows); read the hbm peaks and the timeline" % site)
    if exc:
        return ("uncaught %s: %s" % (exc.get("type"),
                                     (exc.get("message") or "")[:120]))
    if reason.startswith("slo:"):
        info = (doc.get("slo") or {}).get("active", {}).get(
            reason[4:], {})
        ex = info.get("exemplar")
        if isinstance(ex, dict):
            # the attached slow-request exemplar (ISSUE 19) names the
            # FAMILY, not just the firing rule
            family, advice = slow_request_family(ex)
            return ("SLO alert %r fired, %s: exemplar request #%s "
                    "(lane %s, %s) spent %dµs of its %dµs e2e in "
                    "%r — %s; run `blackbox autopsy <dump>` for the "
                    "waterfall"
                    % (reason[4:], family, ex.get("rid"),
                       ex.get("lane"), ex.get("status"),
                       (ex.get("phases") or {}).get(
                           ex.get("budget_phase")
                           or ex.get("dominant"), 0),
                       ex.get("e2e_us", 0),
                       ex.get("budget_phase") or ex.get("dominant"),
                       advice))
        return ("SLO alert %r fired — PROACTIVE dump, the run was "
                "still alive (%s); read the slo block and the slo.* "
                "ring events"
                % (reason[4:],
                   " ".join("%s=%s" % (k, info[k]) for k in
                            sorted(info)
                            if isinstance(info[k],
                                          (int, float, str)))[:100]
                   or "no evidence recorded"))
    if reason.startswith("controlplane:"):
        # proactive supervisor dumps (ISSUE 16): the rollback ring
        # event names the breaching rule and the reverted version
        rb = [e for e in evs if e.get("kind") == "controlplane"
              and e.get("name") == "rollback"]
        if reason.startswith("controlplane:rollback:") or rb:
            last = rb[-1] if rb else {}
            return ("canary rollback: version %r of model %r breached "
                    "rule %r — traffic reverted, version deregistered "
                    "(PROACTIVE dump, the fleet kept serving); read "
                    "the controlplane block and controlplane.* ring "
                    "events"
                    % (last.get("version",
                                reason.rsplit("@", 1)[-1]),
                       last.get("model", "?"),
                       last.get("rule", "?")))
        if reason.startswith("controlplane:unhealthy:"):
            return ("whole replica set of model %r went unhealthy — "
                    "supervisor forced an emergency rebuild (resize "
                    "in place); read replica_health in the fleet "
                    "block and the controlplane.* ring events"
                    % reason.rsplit(":", 1)[-1])
        return ("fleet supervisor dump (%s) — read the controlplane "
                "block and controlplane.* ring events" % reason)
    # integrity family first: silent corruption outranks everything a
    # run can do to itself — the bytes were wrong
    sdc = [e for e in evs
           if e.get("kind") == "integrity" and e.get("name") == "sdc"]
    if sdc or reason == "sdc" or c.get("integrity.sdc"):
        last = sdc[-1] if sdc else {}
        return ("silent data corruption: replica(s) %s diverged from "
                "the mesh on %s — evicted/rolled back"
                % (last.get("replicas", "?"),
                   last.get("leaves") or "replicated state"))
    salv = [e for e in evs if e.get("kind") == "integrity"
            and e.get("name") in ("ckpt_corrupt", "ckpt_salvaged")]
    if salv or reason in ("ckpt.salvage", "ckpt.salvage_failed") \
            or c.get("integrity.ckpt_corrupt"):
        failed = reason == "ckpt.salvage_failed" or (
            c.get("integrity.ckpt_corrupt", 0) and
            not c.get("integrity.ckpt_salvaged", 0) and
            not c.get("resilience.restored", 0))
        bad = [e for e in salv if e.get("name") == "ckpt_corrupt"]
        what = (bad[-1].get("leaves") or bad[-1].get("files", "?")) \
            if bad else "?"
        if failed:
            return ("checkpoint corruption: every keep-K candidate "
                    "failed verification (bad leaf/file: %s) — "
                    "nothing salvageable" % (what,))
        return ("checkpoint corruption SALVAGED: %d checkpoint(s) "
                "failed verification (bad leaf/file: %s), an older "
                "verifiable one was restored"
                % (c.get("integrity.ckpt_corrupt", 0), what))
    if "preempt" in kinds or reason == "preemption":
        extra = " after earlier rollback(s)" if "rollback" in kinds \
            else ""
        return "preemption (SIGTERM) — checkpointed and resumable%s" \
            % extra
    if "rollback" in kinds or reason == "rollback":
        return ("numeric instability: %d step(s) skipped "
                "(non-finite/spiking loss) forced a rollback"
                % c.get("resilience.step_skipped", 0))
    if c.get("serve.dispatcher_errors"):
        return ("serving dispatcher backstop fired %d time(s) — an "
                "exception escaped batch execution"
                % c["serve.dispatcher_errors"])
    if c.get("resilience.step_skipped"):
        return ("%d training step(s) skipped on non-finite/spiking "
                "loss (below the rollback threshold)"
                % c["resilience.step_skipped"])
    if c.get("io.decode.records_corrupt"):
        return ("corrupt input records: %d quarantined (skipped, "
                "ledgered in the io-quarantine JSONL) — see "
                "integrity/record_corrupt events for file/offset"
                % c["io.decode.records_corrupt"])
    # fleet skew OUTRANKS feed stall (ISSUE 11): one slow replica
    # drags every synchronized step, which then LOOKS like input
    # starvation on the survivors — blame the replica the detector
    # named, not the pipeline feeding it
    strag = [e for e in evs if e.get("kind") == "mesh"
             and e.get("name") == "straggler"]
    if strag or c.get("mesh.straggler"):
        last = strag[-1] if strag else {}
        fleet = (doc.get("fleet") or {})
        who = last.get("replica",
                       (fleet.get("stragglers") or ["?"])[0])
        return ("fleet skew: replica %s is a straggler (windowed step "
                "time %sµs vs fleet median %sµs) — a slow replica "
                "bounds every synchronized step; check that replica's "
                "host before blaming the input pipeline"
                % (who, last.get("step_us", "?"),
                   last.get("fleet_median_us", "?")))
    stall, step = c.get("feed.stall_us", 0), c.get("feed.step_us", 0)
    if stall and step and stall > step:
        return ("input-pipeline starvation: feed stalls (%.1fs) exceed "
                "compute wall between batches" % (stall / 1e6))
    if c.get("serve.deadline_expired"):
        return ("serving overload: %d request(s) expired in queue"
                % c["serve.deadline_expired"])
    if reason == "sigusr2":
        return "operator-requested snapshot (SIGUSR2) — no failure"
    return "no anomaly detected by the heuristics; read the timeline"


def render(doc: dict, events_tail=40) -> str:
    lines = []
    head = "blackbox — reason=%s pid=%s %s" % (
        doc.get("reason"), doc.get("pid"),
        time.strftime("%Y-%m-%d %H:%M:%S",
                      time.localtime(doc.get("ts", 0))))
    lines += [head, "=" * len(head)]
    exc = doc.get("exception")
    if exc:
        lines.append("exception: %s: %s"
                     % (exc.get("type"), (exc.get("message") or "")[:200]))

    evs = doc.get("events", [])
    tail = evs[-int(events_tail):]
    lines += ["", "timeline (last %d of %d events)"
              % (len(tail), len(evs)), "-" * 46]
    t_end = doc.get("ts", 0)
    for e in tail:
        extra = " ".join(
            "%s=%s" % (k, e[k]) for k in sorted(e)
            if k not in ("ts", "tid", "kind", "name"))
        lines.append("%+9.3fs %6s %-10s %-24s %s"
                     % (e.get("ts", 0) - t_end, "t%d" % e.get("tid", 0),
                        e.get("kind", "?"), e.get("name", "?"),
                        extra[:60]))

    counters = {k: v for k, v in doc.get("counters", {}).items() if v}
    if counters:
        lines += ["", "counters (nonzero)", "-" * 18]
        for k in sorted(counters):
            lines.append("%-36s %14d" % (k, counters[k]))

    rows = doc.get("costs", {}).get("rows", [])
    if rows:
        lines += ["", "cost table (per executable)", "-" * 27,
                  "%-6s %-28s %7s %10s %10s %9s" %
                  ("kind", "label", "calls", "flops", "bytes",
                   "compile_s")]
        for r in rows[:20]:
            lines.append("%-6s %-28s %7d %10s %10s %9.2f"
                         % (r.get("kind", "?")[:6],
                            r.get("label", "?")[:28],
                            r.get("invocations", 0),
                            _fmt_qty(r.get("flops", 0)),
                            _fmt_qty(r.get("bytes_accessed", 0), "B"),
                            r.get("compile_wall_s", 0)))
        t = doc.get("costs", {}).get("totals", {})
        if t:
            lines.append("TOTAL  %-28s %7d %10s %10s %9.2f"
                         % ("(cumulative)", t.get("invocations", 0),
                            _fmt_qty(t.get("cum_flops", 0)),
                            _fmt_qty(t.get("cum_bytes", 0), "B"),
                            t.get("compile_wall_s", 0)))

    # the compile-loop decisions (ISSUE 18) render next to the cost
    # table they were trained on: chosen config, evidence tier, the
    # tuned-vs-heuristic provenance, manifest hit counts
    lines += _autotune_lines(doc.get("autotune"))

    peaks = doc.get("hbm", {}).get("peaks", {})
    if peaks:
        lines += ["", "hbm peaks", "-" * 9]
        for dev in sorted(peaks):
            lines.append("%-24s %s" % (dev, _fmt_qty(peaks[dev], "B")))

    # the merged per-replica fleet view (ISSUE 11) — same table
    # teletop renders live, embedded here so a dead run's dump still
    # answers "which replica"
    lines += _fleet_lines(doc.get("fleet"))
    # the SLO rule/alert state (ISSUE 12): a proactive slo:<rule>
    # dump's firing evidence, or "was anything firing" for any other
    lines += _slo_lines(doc.get("slo"))
    # the request journals + promoted slow-request exemplars (ISSUE
    # 19) — `blackbox autopsy` renders one exemplar's full waterfall
    lines += _reqtrace_lines(doc.get("reqtrace"))
    # the memory-observatory block (ISSUE 20) — `blackbox memautopsy`
    # renders the full committed-vs-measured post-mortem
    lines += _memwatch_lines(doc.get("memwatch"))

    lines += ["", "suspected cause: " + suspected_cause(doc)]
    return "\n".join(lines)


# -- merge (ISSUE 11) --------------------------------------------------
def _trace_events_of(path):
    """The chrome-trace events of one input: a black-box dump's
    embedded trace view, or a raw chrome-trace JSON ({"traceEvents":
    [...]} or a bare event list)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):
        return doc
    if doc.get("schema", "").split("/")[0] == "mxtpu-blackbox":
        return doc.get("trace", {}).get("traceEvents", [])
    return doc.get("traceEvents", [])


def merge_traces(paths, out_path=None) -> dict:
    """Join per-process chrome traces into one timeline and report the
    cross-process correlation keys.

    Events keep their own pid (each process renders as its own row;
    `process_name` metadata events are added).  The summary counts the
    joins the fleet-tracing layer exists for: trace ids and global
    steps whose spans come from MORE than one process.  Returns
    ``{events, processes, cross_process_traces, cross_process_steps,
    timebases, out}``.

    Timebases: black-box dump trace views stamp events in EPOCH µs
    (wall clock — genuinely comparable across processes on one host),
    while a raw `profiler.dump()` trace stamps perf_counter-relative
    µs from its own process origin.  Mixing the two cannot be aligned
    without an offset only the producing process knew, so the merge
    detects the base per input (`epoch` vs `relative`), reports it in
    the summary, and WARNS on a mix instead of silently writing a
    timeline whose rows sit decades apart."""
    import sys as _sys
    events = []
    timebases = {}
    for p in paths:
        evs = _trace_events_of(p)
        ts = sorted(e.get("ts", 0) for e in evs
                    if e.get("ph") != "M")
        mid = ts[len(ts) // 2] if ts else 0
        # epoch-µs stamps are ~1.7e15; perf-relative ones live in the
        # seconds-to-hours range
        timebases[p] = "epoch" if mid > 1e12 else "relative"
        events.extend(evs)
    if len(set(timebases.values())) > 1:
        print("blackbox merge: WARNING — inputs mix timebases %s; "
              "epoch-stamped (dump) and process-relative (profiler "
              "dump) events cannot share one timeline without an "
              "offset only the producer knew. Merge dumps with "
              "dumps, or profiler traces with profiler traces."
              % timebases, file=_sys.stderr)
    pids, traces, steps = set(), {}, {}
    for e in events:
        pid = e.get("pid")
        pids.add(pid)
        args = e.get("args") or {}
        # the profiler sink spells it trace_id; the flight-recorder
        # ring's chrome view spells it trace — join on either
        tr = args.get("trace_id", args.get("trace"))
        if tr is not None:
            traces.setdefault(tr, set()).add(pid)
        st = args.get("step")
        if st is not None:
            steps.setdefault(int(st), set()).add(pid)
    events.sort(key=lambda e: e.get("ts", 0))
    meta = [{"ph": "M", "name": "process_name", "pid": p,
             "args": {"name": "pid %s" % p}} for p in sorted(
                 p for p in pids if p is not None)]
    merged = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
    if out_path:
        with open(out_path, "w") as f:
            json.dump(merged, f)
    return {
        "events": len(events),
        "processes": sorted(p for p in pids if p is not None),
        "cross_process_traces": sorted(
            t for t, ps in traces.items() if len(ps) > 1),
        "cross_process_steps": sorted(
            s for s, ps in steps.items() if len(ps) > 1),
        "timebases": timebases,
        "out": out_path,
    }


def merge_main(argv) -> int:
    """``blackbox merge`` body: merge N dumps/traces into one chrome
    trace + print the correlation summary.  rc 0 = merged events
    written; 1 = nothing to merge."""
    ap = argparse.ArgumentParser(
        prog="blackbox merge",
        description="join per-process chrome traces (black-box dumps "
                    "or raw trace JSONs) into one timeline keyed on "
                    "(trace_id, step)")
    ap.add_argument("inputs", nargs="+",
                    help="black-box dumps and/or chrome-trace JSONs")
    ap.add_argument("--out", default="merged.trace.json",
                    help="merged chrome-trace output path "
                    "(default merged.trace.json)")
    args = ap.parse_args(argv)
    try:
        summary = merge_traces(args.inputs, out_path=args.out)
    except Exception as e:          # noqa: BLE001 — operator tool
        print("blackbox merge: %s" % e, file=sys.stderr)
        return 1
    print("merged %d event(s) from %d input(s) -> %s"
          % (summary["events"], len(args.inputs), args.out))
    print("processes: %s" % (summary["processes"] or "none"))
    print("trace ids spanning >1 process: %d"
          % len(summary["cross_process_traces"]))
    print("global steps spanning >1 process: %s"
          % (summary["cross_process_steps"] or "none"))
    return 0 if summary["events"] else 1


# -- history trends (ISSUE 12) -----------------------------------------
_SPARK = "▁▂▃▄▅▆▇█"


def sparkline(values, width=24) -> str:
    """A unicode block sparkline of a value series (downsampled to
    `width` by last-value-per-bin; a flat series renders mid-height so
    'no variance' doesn't read as 'no data')."""
    vals = [float(v) for v in values]
    if not vals:
        return ""
    if len(vals) > width:
        step = len(vals) / float(width)
        vals = [vals[min(len(vals) - 1, int((i + 1) * step) - 1)]
                for i in range(width)]
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK[3] * len(vals)
    return "".join(_SPARK[min(len(_SPARK) - 1,
                              int((v - lo) / (hi - lo)
                                  * (len(_SPARK) - 1)))]
                   for v in vals)


def _bench_diff_mod():
    """tools/bench_diff.py (repo root, not a package) loaded by path —
    the `--diff` direction heuristics are DEFINED there so the two
    trend tools cannot drift apart.  None when the file isn't present
    (an installed package without the repo checkout)."""
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, "tools", "bench_diff.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("_bench_diff", path)
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except Exception:               # noqa: BLE001 — operator tool
        return None
    return mod


def _series_key(row):
    """kind-qualified series key: a name can exist as BOTH a counter
    and a pct series (observe_time's convention — serve.e2e_us), and
    collapsing them would interleave per-tick deltas with p99s in one
    trend row."""
    labels = row.get("labels") or {}
    name = "%s:%s" % (row.get("kind", "?"), row.get("name", "?"))
    if not labels:
        return name
    return "%s{%s}" % (name, ",".join("%s=%s" % kv
                                      for kv in sorted(labels.items())))


def _row_value(row):
    """The trendable scalar of one row: counters by their CUMULATIVE
    total (the per-tick delta is an arbitrary single-tick sample),
    everything else by the row value — ONE definition for the trend
    table and --diff so the two subcommands cannot disagree."""
    if row.get("kind") == "counter":
        return float(row.get("total", row.get("v", 0)))
    return float(row.get("v", 0))


def _history_runs_table(hist, directory):
    lines = ["%-28s %7s %9s %7s %7s %s"
             % ("run", "rows", "span_s", "alerts", "marks", "kinds"),
             "-" * 78]
    for run in hist.runs(directory):
        rows = hist.query(directory=directory, run=run)
        if not rows:
            lines.append("%-28s %7d" % (run, 0))
            continue
        ts = [r.get("ts", 0) for r in rows]
        kinds = {}
        for r in rows:
            kinds[r.get("kind", "?")] = kinds.get(r.get("kind", "?"),
                                                  0) + 1
        fired = sum(1 for r in rows if r.get("kind") == "slo"
                    and r.get("event") == "fired")
        lines.append("%-28s %7d %9.1f %7d %7d %s"
                     % (run, len(rows), max(ts) - min(ts), fired,
                        kinds.get("marker", 0),
                        ",".join("%s:%d" % kv
                                 for kv in sorted(kinds.items()))))
    return lines


def history_main(argv) -> int:
    """``blackbox history`` body: cross-run trend tables (and
    ``--diff``) over the durable history shards.  rc 0 = rendered;
    1 = --diff found regressions; 2 = unusable directory."""
    ap = argparse.ArgumentParser(
        prog="blackbox history",
        description="cross-run trend tables over the durable "
                    "telemetry history (MXNET_HISTORY_DIR shards)")
    ap.add_argument("--dir", default=None,
                    help="history directory (default "
                    "MXNET_HISTORY_DIR)")
    ap.add_argument("--name", default=None, metavar="PREFIX",
                    help="series name prefix to trend (without it: "
                    "per-run summary table)")
    ap.add_argument("--kind", default=None,
                    help="restrict to one row kind "
                    "(counter/pct/cost/fleet/marker/slo)")
    ap.add_argument("--runs", type=int, default=8, metavar="N",
                    help="newest N runs to show (default 8)")
    ap.add_argument("--diff", nargs="*", metavar="RUN", default=None,
                    help="compare two runs' last-value-per-series "
                    "(default: the newest two) with bench_diff's "
                    "direction heuristics; rc 1 on regression")
    ap.add_argument("--threshold", type=float, default=10.0,
                    metavar="PCT", help="--diff regression threshold "
                    "percent (default 10)")
    args = ap.parse_args(argv)
    from ..telemetry import history as hist
    directory = args.dir if args.dir is not None else \
        hist.history_dir()
    if not directory:
        print("blackbox history: no directory (--dir or "
              "MXNET_HISTORY_DIR)", file=sys.stderr)
        return 2
    all_runs = hist.runs(directory)
    if not all_runs:
        print("blackbox history: no history-*.jsonl shards under %s"
              % directory, file=sys.stderr)
        return 2

    if args.diff is not None:
        if len(args.diff) == 2:
            run_a, run_b = args.diff
        elif len(args.diff) == 0 and len(all_runs) >= 2:
            run_a, run_b = all_runs[-2], all_runs[-1]
        else:
            print("blackbox history --diff needs two runs (or a "
                  "directory holding at least two)", file=sys.stderr)
            return 2
        missing = [r for r in (run_a, run_b) if r not in all_runs]
        if missing:
            # a typo'd run id must be a loud usage error, not an
            # empty intersection reading as "no regressions"
            print("blackbox history --diff: no shard for run(s) %s "
                  "under %s (known: %s)"
                  % (", ".join(missing), directory,
                     ", ".join(all_runs[-6:])), file=sys.stderr)
            return 2
        bd = _bench_diff_mod()
        if bd is None:
            # without the direction heuristics nothing can be judged
            # a regression — 'OK' here would be a silent false pass
            # for any CI job relying on the rc-1 contract
            print("blackbox history --diff: tools/bench_diff.py not "
                  "loadable (no repo checkout?) — cannot judge "
                  "directions", file=sys.stderr)
            return 2
        last = {}
        for tag, run in (("a", run_a), ("b", run_b)):
            per = {}
            for r in hist.query(args.name, kind=args.kind,
                                directory=directory, run=run):
                per[_series_key(r)] = _row_value(r)
            last[tag] = per
        print("history diff: %s -> %s" % (run_a, run_b))
        print("%-52s %12s %12s %9s %7s %s"
              % ("series", "old", "new", "delta%", "dir", "verdict"))
        print("-" * 100)
        regressions = []
        for key in sorted(set(last["a"]) & set(last["b"])):
            a, b = last["a"][key], last["b"][key]
            if a == b:
                continue
            pct = 100.0 * (b - a) / abs(a) if a else float("inf")
            d = bd.direction_of(key) if bd is not None else None
            verdict = ""
            if d is not None and abs(pct) > args.threshold:
                worse = pct > 0 if d == "lower" else pct < 0
                verdict = "REGRESSION" if worse else "improved"
                if worse:
                    regressions.append(key)
            if verdict or abs(pct) > args.threshold:
                print("%-52s %12g %12g %+8.1f%% %7s %s"
                      % (key[:52], a, b, pct, d or "?", verdict))
        # bench_diff parity: series present in only one run are
        # surfaced, not silently dropped from the comparison — a
        # vanished SLO metric must not read as a pass
        gone = sorted(set(last["a"]) - set(last["b"]))
        new = sorted(set(last["b"]) - set(last["a"]))
        if gone:
            print("series VANISHED in %s: %d (%s%s)"
                  % (run_b, len(gone), ", ".join(gone[:6]),
                     ", ..." if len(gone) > 6 else ""))
        if new:
            print("series added in %s: %d (%s%s)"
                  % (run_b, len(new), ", ".join(new[:6]),
                     ", ..." if len(new) > 6 else ""))
        if regressions:
            print("FAIL: %d series regressed past %.1f%%: %s"
                  % (len(regressions), args.threshold,
                     ", ".join(regressions[:8])), file=sys.stderr)
            return 1
        print("OK: no regressions past %.1f%%" % args.threshold)
        return 0

    if args.name is None and args.kind is None:
        print("\n".join(_history_runs_table(hist, directory)))
        return 0

    runs = all_runs[-max(1, args.runs):]
    print("%-44s %-28s %5s %12s %8s %s"
          % ("series", "run", "n", "last", "delta%", "trend"))
    print("-" * 110)
    prev_last = {}
    shown = 0
    for run in runs:
        per = {}
        for r in hist.query(args.name, kind=args.kind,
                            directory=directory, run=run):
            per.setdefault(_series_key(r), []).append(_row_value(r))
        for key in sorted(per):
            vals = per[key]
            lastv = vals[-1]
            delta = ""
            if key in prev_last and prev_last[key]:
                delta = "%+.1f" % (100.0 * (lastv - prev_last[key])
                                   / abs(prev_last[key]))
            print("%-44s %-28s %5d %12g %8s %s"
                  % (key[:44], run[:28], len(vals), lastv, delta,
                     sparkline(vals)))
            prev_last[key] = lastv
            shown += 1
    if not shown:
        print("(no matching rows)")
    return 0


def verify_main(argv) -> int:
    """``blackbox verify <dir>`` body: verify one checkpoint (a dir
    holding an integrity manifest) or every ``step_*`` child of a
    keep-K directory.  rc 0 = all verifiable; 1 = mismatch (per-file +
    per-leaf report), 2 = usage/unreadable."""
    ap = argparse.ArgumentParser(
        prog="blackbox verify",
        description="verify checkpoint(s) against their integrity "
                    "manifests (per-file + per-leaf CRCs)")
    ap.add_argument("ckpt", help="checkpoint dir, or a keep-K dir of "
                                 "step_* checkpoints")
    args = ap.parse_args(argv)
    from .. import integrity
    import os
    root = os.path.abspath(args.ckpt)
    if not os.path.isdir(root):
        print("blackbox verify: %s is not a directory" % root,
              file=sys.stderr)
        return 2
    if os.path.exists(os.path.join(root, integrity.MANIFEST)):
        targets = [root]
    else:
        targets = sorted(
            os.path.join(root, n) for n in os.listdir(root)
            if n.startswith("step_") and
            os.path.isdir(os.path.join(root, n)))
        if not targets:
            print("blackbox verify: no manifest and no step_* "
                  "checkpoints under %s" % root, file=sys.stderr)
            return 2
    rc = 0
    for t in targets:
        try:
            rep = integrity.verify_checkpoint(t)
        except integrity.CheckpointCorrupt as e:
            rc = 1
            print("CORRUPT  %s" % t)
            for rel, why in sorted(e.files.items()):
                print("         file %-44s %s" % (rel, why))
            for leaf in e.leaves:
                print("         leaf %s" % leaf)
            if e.kind == "manifest":
                print("         %s" % e)
            continue
        if rep.get("verified"):
            print("OK       %s  (%d files, %d leaves, %s)"
                  % (t, rep["files"], rep.get("leaves", 0),
                     rep["algo"]))
        else:
            print("UNVERIFIED %s  (%s)" % (t, rep.get("reason")))
    return rc


# -- autopsy (ISSUE 19) ------------------------------------------------
def _dump_exemplars(doc):
    """Every exemplar a dump carries: the reqtrace block's recent
    ring, plus any exemplar attached to a firing SLO alert (a
    proactive slo:<rule> dump may have rotated its ring past the one
    the alert named)."""
    seen, out = set(), []
    for ex in (doc.get("reqtrace") or {}).get("exemplars") or []:
        if isinstance(ex, dict) and ex.get("rid") not in seen:
            seen.add(ex.get("rid"))
            out.append(ex)
    for info in ((doc.get("slo") or {}).get("active") or {}).values():
        ex = info.get("exemplar") if isinstance(info, dict) else None
        if isinstance(ex, dict) and ex.get("rid") not in seen:
            seen.add(ex.get("rid"))
            out.append(ex)
    return out


def autopsy_lines(ex: dict) -> list:
    """One exemplar's full phase waterfall + the dominance verdict —
    the 'why was THIS request slow' rendering."""
    e2e = float(ex.get("e2e_us") or 0.0)
    phases = ex.get("phases") or {}
    head = "autopsy — request #%s (%s%s, lane %s, status %s)" % (
        ex.get("rid", "?"), ex.get("engine", "?"),
        " %s" % ex.get("model") if ex.get("model") else "",
        ex.get("lane", "-"), ex.get("status", "?"))
    lines = [head, "=" * len(head)]
    if ex.get("ts"):
        lines.append("admitted %s   e2e %dµs   batch n=%s bucket=%s"
                     % (time.strftime("%Y-%m-%d %H:%M:%S",
                                      time.localtime(ex["ts"])),
                        e2e, ex.get("n", 1), ex.get("bucket", "-")))
    if ex.get("reason"):
        lines.append("terminated: %s" % ex["reason"])
    lines += ["", "%-10s %12s %6s  %s" % ("phase", "µs", "%", ""),
              "-" * 62]
    # ladder order, not size order: the waterfall reads top-to-bottom
    # as the request's life
    order = ("queue", "coalesce", "dispatch", "infer", "prefill",
             "decode", "join", "resolve")
    budget = ex.get("budget_phase") or ex.get("dominant")
    for ph in sorted(phases, key=lambda p: (
            order.index(p) if p in order else len(order), p)):
        us = float(phases[ph])
        frac = us / e2e if e2e > 0 else 0.0
        bar = "#" * max(1 if us > 0 else 0, int(round(frac * 36)))
        mark = "  <- budget" if ph == budget else ""
        lines.append("%-10s %12d %5.1f%%  %s%s"
                     % (ph, us, frac * 100.0, bar, mark))
    family, advice = slow_request_family(ex)
    lines += ["", "verdict: %s — %.1f%% of e2e in %r; %s"
              % (family,
                 (float(phases.get(budget, 0.0)) / e2e * 100.0)
                 if e2e > 0 else 0.0,
                 budget, advice)]
    return lines


def autopsy_main(argv) -> int:
    """``blackbox autopsy`` body: render the waterfall of one
    promoted slow-request exemplar from a dump — by --rid, or the
    worst-e2e exemplar (preferring one attached to a firing alert)."""
    ap = argparse.ArgumentParser(
        prog="blackbox autopsy",
        description="per-phase waterfall + phase-dominance verdict "
                    "for a promoted slow-request exemplar")
    ap.add_argument("dump", help="black-box dump JSON path")
    ap.add_argument("--rid", type=int, default=None,
                    help="exemplar request id (default: the worst)")
    ap.add_argument("--lane", default=None,
                    help="restrict to one lane")
    ap.add_argument("--all", action="store_true",
                    help="render every matching exemplar")
    args = ap.parse_args(argv)
    try:
        doc = load_dump(args.dump)
    except Exception as e:          # noqa: BLE001 — operator tool
        print("blackbox: cannot read %s: %s" % (args.dump, e),
              file=sys.stderr)
        return 1
    pool = _dump_exemplars(doc)
    if args.lane is not None:
        pool = [e for e in pool if e.get("lane") == args.lane]
    if args.rid is not None:
        pool = [e for e in pool if e.get("rid") == args.rid]
    if not pool:
        print("blackbox autopsy: no matching exemplar in %s (the "
              "dump's reqtrace block is empty — tracing off, or no "
              "request crossed its lane p99)" % args.dump,
              file=sys.stderr)
        return 1
    pool.sort(key=lambda e: -float(e.get("e2e_us") or 0.0))
    chosen = pool if args.all else pool[:1]
    out = []
    for ex in chosen:
        if out:
            out.append("")
        out += autopsy_lines(ex)
    print("\n".join(out))
    return 0


# -- memautopsy (ISSUE 20) ---------------------------------------------
def memautopsy_lines(doc: dict, top=10) -> list:
    """A dump's memwatch block as an OOM / drift post-mortem: the
    per-device sample (with source), the per-phase peak watermarks,
    the committed-vs-measured tenant join, the recent allocation
    lifecycle, and the verdict naming the worst drifter."""
    mw = doc.get("memwatch") or {}
    smp = mw.get("sample") or {}
    head = "memautopsy — reason=%s phase=%s %s" % (
        doc.get("reason"), mw.get("phase", "?"),
        time.strftime("%Y-%m-%d %H:%M:%S",
                      time.localtime(doc.get("ts", 0))))
    lines = [head, "=" * len(head)]
    exc = doc.get("exception")
    if exc:
        lines.append("exception: %s: %s"
                     % (exc.get("type"),
                        (exc.get("message") or "")[:200]))
    if not smp:
        lines += ["", "no memwatch sample in this dump — memwatch "
                      "was disabled, or the dump predates the first "
                      "sample"]
        return lines

    devices = smp.get("devices") or {}
    lines += ["", "devices (sample tag=%s%s)"
              % (smp.get("tag", "?"),
                 "" if mw.get("fresh", True) else ", STALE"),
              "%-12s %10s %10s %10s %-12s"
              % ("device", "used", "peak", "limit", "source"),
              "-" * 60]
    for dev in sorted(devices):
        row = devices[dev]
        lim = row.get("limit_bytes", 0)
        lines.append("%-12s %10s %10s %10s %-12s"
                     % (dev[:12],
                        _fmt_qty(row.get("used_bytes", 0), "B"),
                        _fmt_qty(row.get("peak_bytes", 0), "B"),
                        _fmt_qty(lim, "B") if lim else "-",
                        str(row.get("source", "?"))[:12]))

    marks = mw.get("watermarks") or {}
    if any(marks.values()):
        lines += ["", "peak watermarks (per phase)", "-" * 27]
        for phase in sorted(marks):
            for dev in sorted(marks[phase]):
                lines.append("%-10s %-12s %s"
                             % (phase, dev[:12],
                                _fmt_qty(marks[phase][dev], "B")))

    attr = (mw.get("attribution") or [])[:max(1, int(top))]
    if attr:
        lines += ["", "tenant attribution (committed vs measured)",
                  "%-24s %-10s %10s %10s %7s %-6s %-10s"
                  % ("tenant", "device", "committed", "measured",
                     "drift", "kind", "basis"),
                  "-" * 78]
        for r in attr:
            drift = r.get("drift")
            lines.append(
                "%-24s %-10s %10s %10s %7s %-6s %-10s"
                % (str(r.get("tenant", "?"))[:24],
                   str(r.get("device", "?"))[:10],
                   _fmt_qty(r.get("committed_bytes", 0), "B"),
                   _fmt_qty(r.get("measured_bytes", 0), "B"),
                   "-" if drift is None else "%.2fx" % drift,
                   str(r.get("kind", ""))[:6],
                   str(r.get("basis", ""))[:10]))

    evs = mw.get("events") or []
    if evs:
        lines += ["", "allocation lifecycle (last %d)" % len(evs),
                  "-" * 30]
        for e in evs:
            extra = " ".join(
                "%s=%s" % (k, e[k]) for k in sorted(e)
                if k not in ("ts", "tid", "kind", "name"))
            lines.append("%-12s %-28s %s"
                         % (e.get("kind", "?"), e.get("name", "?"),
                            extra[:36]))

    worst = _worst_drifter(mw)
    if worst is not None:
        lines += ["", "verdict: tenant %r on %s drifted %.2fx from "
                      "its ledger row (%s measured vs %s committed) "
                      "— re-reconcile it (registry.reconcile) or "
                      "lower its admission footprint"
                  % (worst.get("tenant"), worst.get("device"),
                     worst.get("drift") or 0.0,
                     _fmt_qty(worst.get("measured_bytes", 0), "B"),
                     _fmt_qty(worst.get("committed_bytes", 0), "B"))]
    else:
        lines += ["", "verdict: no judgeable tenant row (nothing "
                      "committed, or no fresh measurement) — read "
                      "the device table and the timeline"]
    return lines


def memautopsy_main(argv) -> int:
    """``blackbox memautopsy`` body: render a dump's memwatch block
    as a memory post-mortem.  rc 0 = rendered (even without a
    sample); 1 = unreadable dump."""
    ap = argparse.ArgumentParser(
        prog="blackbox memautopsy",
        description="OOM / memory-drift post-mortem from a dump's "
                    "memwatch block: per-device sample, phase "
                    "watermarks, committed-vs-measured tenant join, "
                    "verdict naming the worst drifter")
    ap.add_argument("dump", help="black-box dump JSON path")
    ap.add_argument("--top", type=int, default=10, metavar="N",
                    help="attribution rows to show (default 10)")
    args = ap.parse_args(argv)
    try:
        doc = load_dump(args.dump)
    except Exception as e:          # noqa: BLE001 — operator tool
        print("blackbox: cannot read %s: %s" % (args.dump, e),
              file=sys.stderr)
        return 1
    print("\n".join(memautopsy_lines(doc, top=args.top)))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "verify":
        return verify_main(argv[1:])
    if argv and argv[0] == "merge":
        return merge_main(argv[1:])
    if argv and argv[0] == "history":
        return history_main(argv[1:])
    if argv and argv[0] == "autopsy":
        return autopsy_main(argv[1:])
    if argv and argv[0] == "memautopsy":
        return memautopsy_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="blackbox",
        description="summarize a flight-recorder black-box dump "
                    "(or: blackbox verify <ckpt_dir> / "
                    "blackbox merge <dumps...> / blackbox history / "
                    "blackbox autopsy / blackbox memautopsy)")
    ap.add_argument("dump", help="black-box dump JSON path")
    ap.add_argument("--events", type=int, default=40, metavar="N",
                    help="timeline tail length (default 40)")
    ap.add_argument("--trace", metavar="OUT",
                    help="also extract the embedded chrome-trace view "
                    "to OUT (open in Perfetto / chrome://tracing)")
    args = ap.parse_args(argv)
    try:
        doc = load_dump(args.dump)
    except Exception as e:          # noqa: BLE001 — operator tool
        print("blackbox: cannot read %s: %s" % (args.dump, e),
              file=sys.stderr)
        return 1
    if args.trace:
        with open(args.trace, "w") as f:
            json.dump(doc.get("trace", {"traceEvents": []}), f)
        print("chrome trace written to %s" % args.trace,
              file=sys.stderr)
    print(render(doc, events_tail=args.events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
