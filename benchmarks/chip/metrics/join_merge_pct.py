"""Share of the generic joins' probe rows that took the sort-merge, in %
(``join_merge_rows`` over ``join_merge_rows`` + ``join_search_rows`` of
every pipeline), over the window's completed queries. Reads None where no
report carries the counters, or no generic join ran, so a program that
does not count them leaves the metric out."""


def read(run):
    reports = [p for r in run.records if not r["error"]
               for p in r["handle"].stats().pipelines
               if not p.cache_hit
               and "join_merge_rows" in getattr(p, "counters", {})]
    merged = sum(p.counters["join_merge_rows"] for p in reports)
    total = merged + sum(p.counters["join_search_rows"] for p in reports)
    if not total:
        return None
    return 100.0 * merged / total
