"""Milliseconds the device ran an operation in the window (union of the
trace's op intervals, averaged over the chips used), per query."""


def read(run):
    if not run.trace or not run.n:
        return None
    return run.trace["busy_s"] * 1e3 / run.n
