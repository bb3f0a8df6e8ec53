"""Megabytes the object store served in the window (table scans and
exchange reads; ``store.stats.bytes_read``), per completed query."""


def read(run):
    return run.bytes_read / 1e6 / run.n if run.n else None
