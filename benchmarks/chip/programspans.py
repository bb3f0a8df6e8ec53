"""The program's own spans and counters, per completed query.

Each executed pipeline's report (``PipelineReport.span_ms``: host
milliseconds by span name; ``counters``: ``decode_ns``, ``h2d_bytes``) is
read through the query handle the harness keeps with every record. Sums
run over every attempt and over concurrent fragments, so they are thread
time and may exceed the latency. A window without the span reads 0; a
program that keeps no spans reads None, so its line leaves the metric
out.
"""

from __future__ import annotations


def _reports(run) -> list | None:
    reports = [p for r in run.records if not r["error"]
               for p in r["handle"].stats().pipelines if not p.cache_hit]
    if not run.n or not all(hasattr(p, "span_ms") for p in reports):
        return None
    return reports


def span_ms_per_query(run, *names: str) -> float | None:
    """Milliseconds of the named spans per completed query."""
    reports = _reports(run)
    if reports is None:
        return None
    return sum(p.span_ms.get(n, 0.0) for p in reports for n in names) \
        / run.n


def counter_per_query(run, key: str) -> float | None:
    """A counter of the reports (``decode_ns``, ``h2d_bytes``) per
    completed query."""
    reports = _reports(run)
    if reports is None:
        return None
    return sum(p.counters.get(key, 0) for p in reports) / run.n
