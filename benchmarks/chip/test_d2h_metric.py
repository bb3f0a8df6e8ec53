"""The reader of ``d2h_mb_per_query``, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

No number here is a speed.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import run  # noqa: E402


def _run_of(*pipelines_per_query) -> run.Run:
    def handle(pipes):
        stats = NS(pipelines=pipes)
        return NS(stats=lambda: stats)
    records = [{"query": "q3", "error": None, "handle": handle(p)}
               for p in pipelines_per_query]
    records.append({"query": "q3", "error": "QueryFailedError: x",
                    "handle": None})
    return run.Run(records=records, n=len(pipelines_per_query))


def _report(counters, cache_hit=False):
    return NS(cache_hit=cache_hit, span_ms={"fragment.d2h": 1.0},
              counters=counters)


@pytest.mark.parametrize("counters, want", [
    # a program that keeps spans but does not count D2H bytes: no value
    ({"h2d_bytes": 5_000_000}, None),
    # 3 MB and 1 MB in one query, 0 in the other: 2 MB per query
    ({"h2d_bytes": 5_000_000, "d2h_bytes": 3_000_000}, 2.0),
])
def test_d2h_mb_per_query(counters, want):
    read = run.Cell("sf1-join-shuffle").reader("d2h_mb_per_query")
    other = dict(counters, d2h_bytes=1_000_000) if want else counters
    got = read(_run_of([_report(counters), _report(other)],
                       [_report({"h2d_bytes": 1})]))
    assert got == (None if want is None else pytest.approx(want))
    # a program without spans, and an empty window: no value either
    assert read(_run_of([NS(cache_hit=False)])) is None
    assert read(run.Run(records=[], n=0)) is None
