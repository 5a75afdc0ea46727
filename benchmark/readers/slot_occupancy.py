"""Live slots over all slots, averaged over the decode steps of the window:
the program's counters `gen.tokens` (live slots summed over steps) and
`gen.steps`."""


def read(spec, record, result):
    if record.get("kind") != "serve":
        return None
    o, c = record["counters_open"], record["counters_close"]
    steps = c["gen.steps"] - o["gen.steps"]
    if steps <= 0:
        return None
    live = c["gen.tokens"] - o["gen.tokens"]
    return 100.0 * live / (steps * record["system_info"]["slots"])
