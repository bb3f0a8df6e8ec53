"""Median latency, submit to rows received, over every query completed
in the window (host clock)."""


def read(run):
    return run.latency_quantile(5)
