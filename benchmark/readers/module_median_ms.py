"""Median device duration of one role's executable in the traced window."""
import statistics

import trace_reduce


def read(spec, record, result):
    tr, roles = record.get("trace"), record.get("roles")
    if not tr or not roles or spec["role"] not in roles:
        return None
    mods = roles[spec["role"]]
    mods = mods if isinstance(mods, list) else [mods]
    runs = [d for m in mods for _, d in trace_reduce.module_runs(tr, m)]
    return 1e3 * statistics.median(runs) if runs else None
