"""Host milliseconds in the program's ``fragment.d2h`` spans (``to_numpy``:
the mask and columns pulled back and compacted), per completed query.
Summed over concurrent fragments, so thread time: it may exceed the
latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "fragment.d2h")
