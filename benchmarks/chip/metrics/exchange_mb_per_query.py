"""Megabytes written to the object store in the window (exchange
partitions, manifests and results; ``store.stats.bytes_written``), per
completed query."""


def read(run):
    return run.bytes_written / 1e6 / run.n if run.n else None
