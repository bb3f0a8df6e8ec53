"""Megabytes the program handed to the device in ``from_numpy``
(``h2d_bytes``: every column and the mask, capacity padding included),
per completed query."""

import programspans


def read(run):
    b = programspans.counter_per_query(run, "h2d_bytes")
    return None if b is None else b / 1e6
