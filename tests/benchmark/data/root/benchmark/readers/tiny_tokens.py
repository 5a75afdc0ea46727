"""A reader added as a file of its own: tokens the client saw (test)."""


def read(spec, record, result):
    toks = record.get("tokens_end")
    return float(sum(toks.values())) if toks else None
