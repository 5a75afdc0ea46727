"""A quantile of the client's own per-request samples (the driver's record)."""
import harness


def read(spec, record, result):
    xs = record.get(spec["samples"])
    if not xs:
        return None
    return harness.quantile(xs, float(spec["q"]))
