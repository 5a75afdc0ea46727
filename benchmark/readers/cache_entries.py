"""Executables the run added to the persistent compile cache (0 once a
checkout is warm)."""


def read(spec, record, result):
    v = record.get("cache_entries_added")
    return None if v is None else float(v)
