"""Per-step training telemetry (ISSUE 4 tentpole part 3).

`StepTelemetry` turns one train step into the `train.*` ledger on
`monitor.events`:

    train.steps             steps recorded
    train.step_us           step wall (counter total + p50/p99 samples)
    train.data_wait_us      batch placement / feed wait inside the step
    train.compute_us        dispatch→host-sync wall (guarded steps)
    train.dispatch_us       async dispatch wall (ShardedTrainer steps —
                            loss stays on device, so compute wall is
                            not observable without forfeiting overlap)
    train.dispatch_replica_us  per-replica batch-shard upload wall,
                            labeled {replica=<i>} (the DispatchPool
                            fan-out — ISSUE 10); aggregate + labeled
                            percentile rings
    train.collective_us     attributed collective wall per step where
                            a caller can measure it (the bench's
                            weak-scaling breakdown derives it from a
                            collective-free compiled baseline; inside
                            ONE fused executable it is not separately
                            observable)
    train.loss              loss samples (percentiles; no counter)
    train.steps_skipped     guarded steps whose update was not applied
    train.steps_compiling   steps that traced a new executable
                            (`train.traces` moved — the recompile
                            smoke alarm)
    train.checkpoint_us     checkpoint write wall

`ResilientTrainer` / `ShardedTrainer` instantiate one lazily when
`telemetry.enabled()` — the disabled hot path pays a single bool read.
The trace counter `train.traces` itself is incremented inside the
jitted step bodies (trace-time python side effect, the serving
`serve.traces` pattern): zero cost in the executable, and a cache hit
never touches it.
"""
from __future__ import annotations

import math

from ..monitor import events

__all__ = ["StepTelemetry"]


class StepTelemetry:
    """Records per-step training telemetry onto an `EventCounters`
    ledger (default: the process-wide `monitor.events`)."""

    def __init__(self, counters=None, own_traces=0):
        self._c = counters if counters is not None else events
        # compile-delta baselines taken NOW: `own_traces` is the owning
        # trainer's trace count at creation (nonzero when telemetry is
        # enabled mid-run — those earlier compiles must not fire the
        # alarm on the first recorded step), the global counter
        # baselines itself the same way
        self._last_own = int(own_traces)
        self._last_global = self._c.get("train.traces")

    def record_step(self, loss=None, ok=True, wall_s=None,
                    data_wait_s=None, compute_s=None,
                    dispatch_s=None, collective_s=None, traces=None):
        """One step's telemetry.  Durations in seconds (None = not
        measured); `loss` a host float (NaN/None skipped as a sample);
        `ok` False counts the step as skipped (guarded-step contract);
        `traces` the OWNING trainer's executable-trace count (falls
        back to the process-global `train.traces` — which misattributes
        another trainer's compile in multi-trainer processes, so
        trainers pass their own)."""
        c = self._c
        c.incr("train.steps")
        if wall_s is not None:
            c.observe_time("train.step_us", wall_s)
        if data_wait_s is not None:
            c.observe_time("train.data_wait_us", data_wait_s)
        if compute_s is not None:
            c.observe_time("train.compute_us", compute_s)
        if dispatch_s is not None:
            c.observe_time("train.dispatch_us", dispatch_s)
        if collective_s is not None:
            c.observe_time("train.collective_us", collective_s)
        if loss is not None and math.isfinite(loss):
            c.observe("train.loss", float(loss))
        if not ok:
            c.incr("train.steps_skipped")
        if traces is not None:
            if traces > self._last_own:
                c.incr("train.steps_compiling")
            self._last_own = traces
        else:
            g = c.get("train.traces")
            if g > self._last_global:
                c.incr("train.steps_compiling")
            self._last_global = g

    def record_checkpoint(self, seconds):
        self._c.observe_time("train.checkpoint_us", seconds)
