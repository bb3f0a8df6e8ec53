"""Rows the partial-aggregation fragments took in per group they handed
to the exchange (``agg_rows_in`` / ``agg_groups_out`` of every pipeline
that runs ``partial_agg``), over the window's completed queries. Reads
None where no report carries the counters, so a program that does not
count them leaves the metric out."""


def read(run):
    reports = [p for r in run.records if not r["error"]
               for p in r["handle"].stats().pipelines
               if not p.cache_hit and "partial_agg" in getattr(p, "ops", ())
               and "agg_groups_out" in getattr(p, "counters", {})]
    groups = sum(p.counters["agg_groups_out"] for p in reports)
    if not groups:
        return None
    return sum(p.counters["agg_rows_in"] for p in reports) / groups
