"""Host milliseconds the coordinator spent re-running fragments whose
simulated runtime made them stragglers (``coord.straggler_rerun``: the
duplicate invocation, run after the fleet), per completed query; a
window without stragglers reads 0. Summed over the query's pipelines,
so thread time: it may exceed the latency."""

import programspans


def read(run):
    return programspans.span_ms_per_query(run, "coord.straggler_rerun")
