"""Device time of one role's executable over the device's busy time, in the
traced window.  The role's module name was learned in warm-up (roles.py)."""
import trace_reduce


def read(spec, record, result):
    tr, roles = record.get("trace"), record.get("roles")
    if not tr or not roles or spec["role"] not in roles:
        return None
    mods = roles[spec["role"]]
    mods = mods if isinstance(mods, list) else [mods]
    t = sum(d for m in mods for _, d in trace_reduce.module_runs(tr, m))
    if t <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * t / tr["busy_s"]
