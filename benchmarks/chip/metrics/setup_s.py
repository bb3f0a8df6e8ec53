"""Seconds from the start of the process to the start of the window:
imports, device start, data generation and load, and the warm-up with
its compiles."""


def read(run):
    return run.setup["setup_s"]
