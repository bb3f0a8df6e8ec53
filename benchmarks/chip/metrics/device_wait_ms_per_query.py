"""Host milliseconds in the program's ``fragment.device`` spans (the call
to the fragment's jitted program through ``block_until_ready`` of its
output mask), per completed query. Summed over concurrent fragments, so
thread time: it may exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "fragment.device")
