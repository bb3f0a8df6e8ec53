"""Host milliseconds in the program's ``fragment.write`` spans (the cast
to the output schema, hash partitioning, PAX encode and PUTs of a
fragment's output, exchange or final), per completed query. Summed over
concurrent fragments, so thread time: it may exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "fragment.write")
