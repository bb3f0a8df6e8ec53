"""Seconds to generate the data from the seed and load it into the
system's object store (host clock)."""


def read(run):
    return run.setup["load_s"]
