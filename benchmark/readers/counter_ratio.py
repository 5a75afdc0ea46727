"""One program counter over another: 100 x or `times` x numerator /
denominator, both read as they stand when the run is over.  The driver
snapshots a fixed list of counters at the window's borders and these are not
on it, so the ratio is over the whole run (the fill before the window and
the drain after it are the same traffic); a ratio of two sums over the same
steps does not depend on how many steps there were.  `times_config` names a
key of the configuration to multiply by instead.  Nothing to read where the
program has no such counters."""


def read(spec, record, result):
    from incubator_mxnet_tpu.monitor import events
    num = events.get(spec["numerator"])
    den = events.get(spec["denominator"])
    if not num or not den:
        return None
    times = spec.get("times", 1.0)
    if "times_config" in spec:
        times = float(record["config"][spec["times_config"]])
    return times * num / den
