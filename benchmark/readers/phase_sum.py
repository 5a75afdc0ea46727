"""Seconds of set-up that rows under one prefix cover: the union of the
intervals of the rows that end before the window opens (a cache retrieval
lies inside its backend compile, and a nested jit is traced inside its
caller's trace, so the plain sum would count those twice).  The window
opens at `t_open` where the driver records it; else at the start of the
oldest of the newest `steps` rows of the metric's `window_phase`, so that
what the reference compiles after the window is not counted."""
import harness
import trace_reduce


def read(spec, record, result):
    rows = harness.load_module("readers", "phase_rows")
    t_open = record.get("t_open")
    if t_open is None and record.get("steps"):
        steps = rows.named(spec["window_phase"])[-int(record["steps"]):]
        t_open = steps[0][1] if steps else None
    if t_open is None:
        return None
    spent = [(r[1], r[2]) for r in rows.under(spec["prefix"], None, t_open)
             if r[2] <= t_open]
    return trace_reduce._union(spent) if spent else None
