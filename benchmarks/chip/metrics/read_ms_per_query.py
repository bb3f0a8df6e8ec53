"""Host milliseconds in the program's ``fragment.read`` spans (a leaf's
footer and chunk GETs, decode and concatenation; for exchange leaves the
manifest waits and top-ups too), per completed query. Summed over
concurrent fragments, so thread time: it may exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "fragment.read")
