"""Device time of the executables whose name contains one of `contains`
(the program names an executable by its role: `jit__traced_gen_join(...)`)
over the device's busy time, in the traced window.  Found by name, so it
holds when admission is batched or fused and the run counts change."""
import trace_reduce


def read(spec, record, result):
    tr = record.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    mods = [m for m in trace_reduce.module_counts(tr)
            if any(part in m for part in spec["contains"])]
    t = sum(d for m in mods for _, d in trace_reduce.module_runs(tr, m))
    if t <= 0:
        return None
    return 100.0 * t / tr["busy_s"]
