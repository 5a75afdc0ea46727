"""A program counter's change over the window (first of `counters` that the
driver snapshotted)."""


def read(spec, record, result):
    o, c = record.get("counters_open"), record.get("counters_close")
    for name in spec["counters"]:
        if o and name in o:
            return float(c[name] - o[name])
    return None
