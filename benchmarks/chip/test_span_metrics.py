"""Tests of the readers of the program's spans and counters, on the CPU
at a tiny scale.

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

SPAN_READERS = {
    "read_ms_per_query": 6.0, "decode_ms_per_query": 2.0,
    "h2d_ms_per_query": 6.0, "h2d_mb_per_query": 1.0,
    "device_wait_ms_per_query": 6.0, "d2h_ms_per_query": 6.0,
    "exchange_write_ms_per_query": 6.0,
    "coordinator_wait_ms_per_query": 9.0,
    "straggler_rerun_ms_per_query": 6.0}


def _run_of(*pipelines_per_query, errors=0) -> run.Run:
    """A Run whose completed queries' handles report these pipelines."""
    def handle(pipes):
        stats = NS(pipelines=pipes)
        return NS(stats=lambda: stats)
    records = [{"query": "q6", "error": None, "handle": handle(p)}
               for p in pipelines_per_query]
    records += [{"query": "q6", "error": "QueryFailedError: x",
                 "handle": None}] * errors
    return run.Run(records=records, n=len(pipelines_per_query))


def _report(span_ms=None, counters=None, cache_hit=False):
    return NS(cache_hit=cache_hit, span_ms=span_ms or {},
              counters=counters or {})


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_readers(metric):
    read = run.Cell("sf1-scan-agg").reader(metric)
    # spans recorded, none of this name: 0, not null
    assert read(_run_of([_report()], [_report()], errors=1)) == 0
    # a program that keeps no spans: no value, and no error
    assert read(_run_of([NS(cache_hit=False)])) is None
    assert read(run.Run(records=[], n=0)) is None
    spans = {"fragment.read": 6.0, "fragment.h2d": 6.0,
             "fragment.device": 6.0, "fragment.d2h": 6.0,
             "fragment.write": 6.0, "coord.admit": 3.0,
             "coord.await_source": 3.0, "coord.await_filter": 3.0,
             "coord.straggler_rerun": 6.0}
    counters = {"decode_ns": 2_000_000, "h2d_bytes": 1_000_000}
    two_pipes = [_report(spans, counters), _report(spans, counters),
                 _report({"fragment.read": 99.0}, cache_hit=True)]
    # two pipelines of one query, one query without spans: per query
    got = read(_run_of(two_pipes, [_report()]))
    assert got == pytest.approx(SPAN_READERS[metric])


@pytest.mark.parametrize("workload", ["sf1-scan-agg", "sf1-join-shuffle"])
def test_traced_run_reports_every_span_metric(workload):
    cell = run.Cell(workload)
    cell.config["scale_factor"] = 0.01
    res = run.run_cell(cell, 2**31 + 11, 2.0, True, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SPAN_READERS) <= set(m)
    assert all(m[k] >= 0 for k in SPAN_READERS)
    # the scan cell writes partial aggregates: its write span is not 0
    for k in ("read_ms_per_query", "decode_ms_per_query",
              "h2d_ms_per_query", "h2d_mb_per_query",
              "device_wait_ms_per_query", "exchange_write_ms_per_query",
              "coordinator_wait_ms_per_query"):
        assert m[k] > 0, k
    # untraced runs keep to the end-to-end metrics
    res = run.run_cell(cell, 2**31 + 11, 1.0, False, 1, log=lambda *_: None)
    assert not set(SPAN_READERS) & set(res["metrics"])
