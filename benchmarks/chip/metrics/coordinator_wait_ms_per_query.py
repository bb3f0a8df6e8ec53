"""Host milliseconds the coordinator spent waiting for admission slots,
upstream sources and semi-join filters (``coord.admit``,
``coord.await_source``, ``coord.await_filter``), per completed query.
Summed over the query's concurrent pipelines, so thread time: it may
exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(
        run, "coord.admit", "coord.await_source", "coord.await_filter")
