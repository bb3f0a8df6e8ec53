"""Megabytes the program pulled back from the device (``d2h_bytes``: each
fragment output's scalars and the rows of its columns that came back),
per completed query. Reads None where no report carries the counter, so
a program that does not count it leaves the metric out."""

import programspans


def read(run):
    has = any("d2h_bytes" in getattr(p, "counters", {})
              for r in run.records if not r["error"]
              for p in r["handle"].stats().pipelines)
    b = programspans.counter_per_query(run, "d2h_bytes") if has else None
    return None if b is None else b / 1e6
