"""Rows `(name, t0, t1, ident, parent, n)` of the program's phase log
(`incubator_mxnet_tpu/telemetry/spans.py`), by start time on
`time.monotonic()`, the clock the drivers open and close the window on.
No rows where the program keeps no such log (a commit before PR 27): the
readers built on this then find nothing to read."""


def under(prefix, since=None, until=None):
    """Rows whose name starts with `prefix` and that start in [since, until]."""
    from incubator_mxnet_tpu.telemetry import spans
    log = getattr(spans, "phase_log", None)
    return [] if log is None else log(since=since, until=until, prefix=prefix)


def named(name, since=None, until=None):
    """Rows of exactly this name."""
    return [r for r in under(name, since, until) if r[0] == name]
