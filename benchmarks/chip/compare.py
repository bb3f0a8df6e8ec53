"""The comparison that decides ``correct``.

A query's answer is its rows as the client received them. Rows are
aligned by the reference's integer columns (group keys, dates, counts),
which must match exactly, as must the column names and the row count;
float columns are judged by their relative error against the reference.
"""

from __future__ import annotations

import numpy as np


def compare(got: dict, want: dict) -> tuple[str | None, float]:
    """(what differs exactly, or None; largest relative error of the
    float columns)."""
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}", 0.0
    n_want = len(next(iter(want.values())))
    n_got = len(next(iter(got.values())))
    if n_got != n_want:
        return f"{n_got} rows, the reference has {n_want}", 0.0
    exact = [c for c in sorted(want) if want[c].dtype.kind in "iub"]
    order_got = np.lexsort([np.asarray(got[c]) for c in exact[::-1]]) \
        if exact else np.arange(n_want)
    order_want = np.lexsort([want[c] for c in exact[::-1]]) \
        if exact else np.arange(n_want)
    worst = 0.0
    for c in sorted(want):
        g = np.asarray(got[c], np.float64)[order_got]
        w = np.asarray(want[c], np.float64)[order_want]
        if c in exact:
            if not np.array_equal(g, w):
                return f"column {c} differs", worst
            continue
        if not np.all(np.isfinite(g)):
            return f"column {c} is not finite", worst
        rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
        worst = max(worst, float(rel.max(initial=0.0)))
    return None, worst


def judge(answers: list[tuple[str, dict]], references: dict[str, dict],
          failed: int, limits: dict) -> dict:
    """Every compared number beside its limit: ``failed`` queries (no
    answer came), ``wrong_answers`` (rows or exact columns differ) and
    ``max_rel_err`` over all float columns of all answers."""
    wrong, worst, first_wrong = 0, 0.0, None
    for query, got in answers:
        diff, err = compare(got, references[query])
        worst = max(worst, err)
        if diff is not None:
            wrong += 1
            first_wrong = first_wrong or f"{query}: {diff}"
    checks = {
        "failed": {"value": failed, "limit": limits["failed"]},
        "wrong_answers": {"value": wrong, "limit": limits["wrong_answers"]},
        "max_rel_err": {"value": worst, "limit": limits["max_rel_err"]},
    }
    if first_wrong:
        checks["wrong_answers"]["first"] = first_wrong
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
