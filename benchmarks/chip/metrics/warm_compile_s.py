"""Seconds of the warm-up: the cell's own traffic, run until a whole
pass compiles nothing (host clock)."""


def read(run):
    return run.setup["warm_s"]
