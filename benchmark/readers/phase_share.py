"""The share of one phase's time that another phase does not cover, over
the window: 100 x (sum of `whole` - sum of `less`) / sum of `whole`.  The
`whole` rows are those that start in [t_open, t_close]; the `less` rows
are their children.  For the engine: the part of its ticks in which it
was not waiting for the device."""
import harness


def read(spec, record, result):
    t_open, t_close = record.get("t_open"), record.get("t_close")
    if t_open is None or t_close is None:
        return None
    named = harness.load_module("readers", "phase_rows").named
    whole = named(spec["whole"], t_open, t_close)
    total = sum(r[2] - r[1] for r in whole)
    if total <= 0:
        return None
    ids = {r[3] for r in whole}
    less = sum(r[2] - r[1] for r in named(spec["less"], t_open)
               if r[4] in ids)
    return 100.0 * (total - less) / total
