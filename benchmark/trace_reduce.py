"""From a profiler trace (`*.xplane.pb`) to numbers.

`reduce(path)` reads the trace with `jax.profiler.ProfileData` and
returns plain data, so that readers and tests work on dicts:

    {"window_s": ..., "devices": {plane name: {
         "busy_s": union of the device's op intervals,
         "modules": {module name: [(start_s, dur_s), ...]},
         "ops": {op name: [(start_s, dur_s, module name), ...]}}},
     "busy_s": mean over the device planes,
     "idle_gaps": [(host event name, seconds), ...],   # longest first
     "device_ops": [(name, seconds), ...]}              # longest first

A TPU plane is named `/device:TPU:<n>`; its `XLA Modules` line has one
event per executable run, its `XLA Ops` line one per HLO op (a Pallas
call is an op named after the kernel).  Times are seconds from the first
device event.  No role is guessed here: which module is the decode step
or the train step is learned by the drivers during warm-up (roles.py).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def start(logdir):
    """Start the profiler into `logdir`, with the Python tracer off: it
    slows the host and the reduction reads none of its events."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)


def stop(logdir, host_span_s=0.0):
    """Stop the profiler and reduce what it wrote.  `host_span_s` is the
    traced span on the host's clock: the window is never reported shorter."""
    import jax
    jax.profiler.stop_trace()
    trace = reduce(find_trace(logdir))
    trace["window_s"] = max(trace["window_s"], host_span_s)
    return trace


def find_trace(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    return paths[-1]


def _union(intervals):
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Idle (start, end) between the merged intervals inside [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(event_name):
    """An op event carries its whole HLO line (`%fusion.3 = bf16[...] ...`):
    the op's own name is what stands before the ` = `."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_planes(path):
    """[(plane name, {line name: [(name, start_ns, dur_ns)]})]."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        out.append((plane.name, lines))
    return out


def reduce_planes(planes, top=10):
    dev = [(n, ls) for n, ls in planes if DEVICE_PLANE.match(n)]
    if not dev:
        raise ValueError("the trace has no /device:TPU plane: %s"
                         % [n for n, _ in planes])
    t0 = min(s for _, ls in dev for evs in ls.values() for _, s, _ in evs)
    t1 = max(s + d for _, ls in dev for evs in ls.values() for _, s, d in evs)
    devices, op_time = {}, {}
    for name, lines in dev:
        mods, ops = {}, {}
        mod_iv = []
        for ev, s, d in lines.get(MODULE_LINE, []):
            mods.setdefault(ev, []).append(((s - t0) * 1e-9, d * 1e-9))
            mod_iv.append((s, s + d, ev))
        mod_iv.sort()
        starts = [m[0] for m in mod_iv]
        for ev, s, d in lines.get(OP_LINE, []):
            ev = op_name(ev)
            i = bisect.bisect_right(starts, s) - 1
            owner = mod_iv[i][2] if i >= 0 and s < mod_iv[i][1] else None
            ops.setdefault(ev, []).append(((s - t0) * 1e-9, d * 1e-9, owner))
        src = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        busy = _union([(s, s + d) for _, s, d in src]) * 1e-9
        devices[name] = {"busy_s": busy, "modules": mods, "ops": ops}
        for ev, runs in mods.items():
            op_time["module " + ev] = op_time.get("module " + ev, 0.0) \
                + sum(d for _, d in runs)
        for ev, runs in ops.items():
            key = "%s / %s" % (runs[0][2], ev) if runs[0][2] else ev
            op_time[key] = op_time.get(key, 0.0) + sum(d for _, d, _ in runs)
    n = len(devices)
    device_ops = sorted(((k[:64], v / n) for k, v in op_time.items()),
                        key=lambda kv: -kv[1])[:top]

    # idle gaps of the first device, each named by the host event that
    # covers its middle (the innermost one: the shortest that covers it)
    name0, lines0 = dev[0]
    src = lines0.get(OP_LINE) or lines0.get(MODULE_LINE) or []
    gaps = sorted(_gaps([(s, s + d) for _, s, d in src], t0, t1),
                  key=lambda g: g[0] - g[1])[:50]
    host = [(ev, s, s + d) for n_, ls in planes if n_.startswith("/host:CPU")
            for evs in ls.values() for ev, s, d in evs if d > 0]
    by_name = {}
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        best = None
        for ev, s, e in host:
            if s <= mid < e and (best is None or e - s < best[1]):
                best = (ev, e - s)
        key = (best[0] if best else "(no host event)")[:64]
        by_name[key] = by_name.get(key, 0.0) + (ge - gs) * 1e-9
    idle = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (t1 - t0) * 1e-9, "devices": devices,
            "busy_s": sum(d["busy_s"] for d in devices.values()) / n,
            "idle_gaps": [[k, v] for k, v in idle],
            "device_ops": [[k, v] for k, v in device_ops]}


def reduce(path, top=10):
    return reduce_planes(read_planes(path), top=top)


def module_runs(trace, module, device=None):
    """[(start_s, dur_s)] of `module` on one device (the first by name)."""
    name = device or sorted(trace["devices"])[0]
    return trace["devices"][name]["modules"].get(module, [])


def module_counts(trace):
    """{module name: runs} on the first device."""
    name = sorted(trace["devices"])[0]
    return {m: len(r) for m, r in trace["devices"][name]["modules"].items()}
