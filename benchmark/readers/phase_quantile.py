"""A quantile, in milliseconds, of one phase's durations: over the rows
that start in [t_open, t_close], or, where the metric names a count of the
driver's record under `newest` (a training run's `steps`), over the newest
that many rows."""
import harness


def read(spec, record, result):
    named = harness.load_module("readers", "phase_rows").named
    if "newest" in spec:
        k = int(record.get(spec["newest"]) or 0)
        rows = named(spec["phase"])[-k:] if k else []
    elif record.get("t_open") is None or record.get("t_close") is None:
        return None
    else:
        rows = named(spec["phase"], record["t_open"], record["t_close"])
    if not rows:
        return None
    return harness.quantile([(r[2] - r[1]) * 1e3 for r in rows],
                            float(spec["q"]))
