"""Unified telemetry backbone (ISSUE 4): spans, metrics export, and
per-step training telemetry over the `monitor.events` ledger.

Four layers, one ledger:

- `telemetry.phase(name, ident, parent, n)` / `phase_at` / `phase_log` /
  `phase_totals` — the ALWAYS-ON phase log (spans.py, PR 27): one ring
  of the newest 65536 rows `(name, t0, t1, ident, parent, n)` on
  `time.monotonic()`, each `phase()` mirrored to the profiler as a
  `jax.profiler.TraceAnnotation`.  The generation engine (`gen.*`),
  the trainers (`gluon.step`, `sharded.step`) and the compile path
  (`compile.call`, `compile.jax.*`) write it; no switch gates it.
- `telemetry.span(name, parent=ctx)` — thread-safe spans with explicit
  cross-thread parent propagation, emitted into the profiler's
  chrome-trace sink (spans.py).  A span is a phase too.
- `telemetry.MetricsExporter` — `monitor.events` counters + latency
  percentiles rendered as Prometheus text / JSON, with periodic file
  export and an optional `/metrics` + `/healthz` HTTP thread
  (export.py).
- `telemetry.StepTelemetry` — per-step `train.*` counters/samples,
  wired into `ResilientTrainer` / `ShardedTrainer` (stepstats.py).

Switch (spans, exporter, step telemetry; not the phase log):
`MXNET_TELEMETRY=1` or `telemetry.enable()`.  Disabled, every hot-path
hook of theirs is a single bool read.  `telemetry.start()` boots the
process-wide exporter off the MXNET_TELEMETRY_* knobs;
`python -m incubator_mxnet_tpu.tools.teletop` renders a live or
file-snapshot table.  See docs/observability.md.

ISSUE 5 adds the push-based layer the pull-based surfaces above can't
replace when a run dies:

- `telemetry.flightrec` — the ALWAYS-ON flight recorder: a bounded
  ring of structured events (steps, spans, markers, stalls, HBM
  watermarks) dumped atomically as a self-contained forensic JSON on
  rollback/preemption/uncaught exceptions/SIGUSR2 or an explicit
  `telemetry.dump_blackbox()` (`MXNET_BLACKBOX=0` disarms).
- `telemetry.costs` — the per-executable FLOPs/HBM cost registry every
  jitted executable (fused imperative step, trainer steps,
  serving buckets) reports into.

`python -m incubator_mxnet_tpu.tools.blackbox <dump>` summarizes a
dump.

ISSUE 12 makes the telemetry DURABLE and JUDGED:

- `telemetry.history` — an append-only, bounded on-disk time series
  (MXNET_HISTORY_DIR): the periodic exporter tick writes counter
  deltas, percentile summaries, cost-registry rows and per-replica
  fleet rows to per-process shard files, queryable across runs
  (`history.query`; `blackbox history` renders the trends).
- `telemetry.slo` — declarative SLO/alert rules (static thresholds,
  multi-window burn-rate over an error budget, MAD anomaly vs
  history baselines) evaluated each exporter tick; a firing rule is
  a typed event: `slo.*` counters, a ring event, the
  `mxnet_alert_active{rule=}` gauge, and a PROACTIVE black-box dump
  naming the rule.
"""
from __future__ import annotations

from .spans import (SpanContext, TraceContext, current, emit_foreign,
                    enable, enabled, get_global_step, phase, phase_at,
                    phase_log, phase_totals, propagate, recording,
                    set_global_step, span)
from .export import MetricsExporter
from .stepstats import StepTelemetry
from . import costs
from . import flightrec
from . import fleet
from . import history
from . import memwatch
from . import slo
from .fleet import (FleetReporter, FleetTelemetry, FleetView,
                    StragglerDetector)
from .flightrec import dump_blackbox, install_crash_hooks
from .slo import (AnomalyRule, BurnRateRule, ThresholdRule,
                  register_rule)

__all__ = ["SpanContext", "TraceContext", "span", "current", "enable",
           "enabled", "recording", "propagate", "set_global_step",
           "phase", "phase_at", "phase_log", "phase_totals",
           "get_global_step", "emit_foreign", "MetricsExporter",
           "StepTelemetry", "start", "stop", "get_exporter",
           "snapshot_dict", "costs", "flightrec", "fleet", "history",
           "memwatch", "slo",
           "FleetReporter", "FleetView", "FleetTelemetry",
           "StragglerDetector", "ThresholdRule", "BurnRateRule",
           "AnomalyRule", "register_rule", "dump_blackbox",
           "install_crash_hooks"]

#: counter families the condensed snapshot (bench.py JSON) carries
SNAPSHOT_PREFIXES = ("serve.", "feed.", "train.", "resilience.",
                     "mem.", "fault.", "blackbox.", "mesh.", "fleet.",
                     "slo.", "history.", "memwatch.")

_exporter = None


def start(port=None, path=None, period_s=None) -> MetricsExporter:
    """Boot (or return) the process-wide exporter: HTTP endpoint when
    `port`/MXNET_TELEMETRY_PORT is nonzero, periodic file export when
    `path`/MXNET_TELEMETRY_EXPORT_PATH is set.  Also flips
    `telemetry.enable()` on — starting an export surface means the
    operator wants the instrumentation feeding it."""
    from .. import config as _cfg
    global _exporter
    enable()
    # a started export surface implies a production run — arm the
    # black-box crash hooks too (idempotent; MXNET_BLACKBOX=0 disarms)
    flightrec.install_crash_hooks()
    if _exporter is None:
        _exporter = MetricsExporter()
    if port is not None:
        # explicit port starts the endpoint (0 = ephemeral bind)
        _exporter.serve_http(port)
    elif int(_cfg.get("MXNET_TELEMETRY_PORT")):
        # knob semantics: 0 means "no endpoint"
        _exporter.serve_http()
    if path or _cfg.get("MXNET_TELEMETRY_EXPORT_PATH"):
        _exporter.start(path=path, period_s=period_s)
    return _exporter


def get_exporter():
    """The process-wide exporter (None until `start()`)."""
    return _exporter


def stop():
    """Flag-drain the process-wide exporter (idempotent)."""
    global _exporter
    exp, _exporter = _exporter, None
    if exp is not None:
        exp.close()


def snapshot_dict(prefixes=SNAPSHOT_PREFIXES, pcts=(50, 99)) -> dict:
    """Condensed counter + percentile snapshot of the telemetry
    families, sized for embedding in a one-line JSON record (bench.py's
    BENCH_r*/BENCH_serve schema)."""
    from ..monitor import events
    keep = lambda k: any(k.startswith(p) for p in prefixes)
    out = {"counters": {k: v for k, v in events.snapshot().items()
                        if keep(k)},
           "percentiles": {k: v for k, v in
                           events.latency_snapshot(pcts=pcts).items()
                           if keep(k)}}
    try:
        t = costs.totals()
        if t.get("executables"):
            # cost-table totals ride in the same one-line record
            # (flops / bytes / hbm peak — the bench.py contract)
            out["costs"] = t
    except Exception:               # noqa: BLE001 — attribution is
        pass                        # best-effort in a snapshot
    return out
