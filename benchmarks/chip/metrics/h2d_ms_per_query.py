"""Host milliseconds in the program's ``fragment.h2d`` spans
(``from_numpy``: padding each leaf to its capacity bucket and
``jnp.asarray`` of every column and the mask; a transfer still in flight
finishes inside ``fragment.device``), per completed query. Summed over
concurrent fragments, so thread time: it may exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "fragment.h2d")
