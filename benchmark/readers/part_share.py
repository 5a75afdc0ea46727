"""A model part's share of an executable's device time: over the traced span,
on the first device, for the executables whose name contains `module`
(`gen_prefill`, `gen_decode`, `gluon_train_step`):

    100 x (self time of the op events whose part is `part`)
        / (sum of the durations of those executables' runs)

The trace bears instruction names and no scope; the program says which part
each compiled instruction belongs to (`telemetry.costs.op_parts`: the
innermost `mx.<part>` scope the op was traced in).  The two are joined here.

**Self time**: an event's duration less the events of the same run that lie
inside it.  `while.3` holds its body's ops, and counting both would double
the loop.  A run of the executable is the outermost event and has no part,
so `"part": null` is what no scope names: ops traced outside every scope,
containers' own time, and the time of a run in which no op ran.  The shares
of all parts and `null` therefore sum to 100.

**Which executable is which module**: two prefill buckets are two modules of
one role whose instruction numbers differ (`while.140`, `while.102`).  A
traced module is the executable whose instruction names cover all of the
module's op names.  Where several cover them (two buckets that compiled to
the same names), they must agree on the part of every one of those ops.

Nothing to read (None, the metric is left out): a program without `op_parts`
(a commit before the scopes), no run of such an executable in the span, a
module that no executable covers or that two cover and name differently, or
an executable whose text has no scope at all (`stale`: it was loaded from a
compile cache filled before the program had scopes)."""


def self_times(events, runs):
    """{op name: seconds of self time} from `events` [(start, dur, name)] of
    one module and its `runs` [(start, dur)]; the runs' own self time is
    under the name None."""
    eps = 1e-12
    todo = sorted([(s, -d, None) for s, d in runs]
                  + [(s, -d, name) for s, d, name in events],
                  key=lambda e: (e[0], e[1], e[2] is not None))
    out, stack = {}, []                 # stack of [end, name]
    for start, neg, name in todo:
        end = start - neg
        while stack and stack[-1][0] <= start + eps:
            stack.pop()
        if stack:                       # inside the event on top: not its own
            inside = min(end, stack[-1][0]) - start
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) - inside
        elif name is not None:
            continue                    # an op outside every run of its module
        out[name] = out.get(name, 0.0) + (end - start)
        stack.append([end, name])
    return out


def _parts_of(names, entries):
    """{op name: part} from the one entry of `op_parts` that covers `names`
    (several, where they agree); None where there is none."""
    found = [e for e in entries if names <= e["instructions"].keys()]
    if not found or any(e["stale"] for e in found):
        return None
    maps = [{n: e["instructions"][n] for n in names} for e in found]
    return maps[0] if all(m == maps[0] for m in maps[1:]) else None


def by_part(trace, role, entries):
    """({part or None: seconds}, seconds of the runs) of the modules whose
    name contains `role`, on the first device; None where a module cannot
    be told."""
    dev = trace["devices"][sorted(trace["devices"])[0]]
    seconds, total = {}, 0.0
    for module, runs in dev["modules"].items():
        if role not in module:
            continue
        events = [(s, d, name) for name, evs in dev["ops"].items()
                  for s, d, owner in evs if owner == module]
        parts = _parts_of({name for _, _, name in events}, entries)
        if parts is None:
            return None
        for name, t in self_times(events, runs).items():
            part = parts.get(name)
            seconds[part] = seconds.get(part, 0.0) + t
        total += sum(d for _, d in runs)
    return (seconds, total) if total > 0 else None


def read(spec, record, result):
    tr = record.get("trace")
    try:
        from incubator_mxnet_tpu.telemetry.costs import op_parts
    except ImportError:
        return None
    if not tr:
        return None
    role = spec["module"]
    memo = record.setdefault("part_seconds", {})
    if role not in memo:
        memo[role] = by_part(tr, role, op_parts(role))
    if memo[role] is None:
        return None
    seconds, total = memo[role]
    if spec["part"] not in seconds:     # the executable has no such part
        return None
    return 100.0 * seconds[spec["part"]] / total
