"""Mean milliseconds a query spends planning: from the session's PLANNING
to its RUNNING state event (observer, host clock)."""


def read(run):
    ms = [r["plan_ms"] for r in run.records if "plan_ms" in r]
    return sum(ms) / len(ms) if ms else None
