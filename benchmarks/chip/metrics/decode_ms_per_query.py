"""Host milliseconds the program counted decoding inside its reads
(``decode_ns``: PAX chunk decompression and the per-column concatenation
of row groups), per completed query. Summed over concurrent fragments,
so thread time: it may exceed the latency."""

import programspans


def read(run):
    ns = programspans.counter_per_query(run, "decode_ns")
    return None if ns is None else ns / 1e6
