"""All-reduce time with no compute running on that device, over the step
time, on the worst device, in the traced window."""
import re

import trace_reduce


def read(spec, record, result):
    tr, roles = record.get("trace"), record.get("roles")
    if not tr or not roles or "step" not in roles:
        return None
    pat = re.compile(spec["collective_pattern"])
    worst = None
    for dev in tr["devices"].values():
        step_t = sum(d for m in roles["step"]
                     for _, d in dev["modules"].get(m, []))
        coll, comp = [], []
        for name, runs in dev["ops"].items():
            (coll if pat.search(name) else comp).extend(
                (s, s + d) for s, d, _ in runs)
        if not coll or step_t <= 0:
            continue
        exposed = trace_reduce._union(coll + comp) - trace_reduce._union(comp)
        share = 100.0 * max(exposed, 0.0) / step_t
        worst = share if worst is None else max(worst, share)
    return worst
