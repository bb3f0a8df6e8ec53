"""``join_merge_pct`` in the broadcast join cell, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

The cell's harness is copied with the scale factor lowered to 0.01. No
number here is a speed.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402

CELL = "sf1-join-broadcast"


@pytest.fixture
def cell(tmp_path) -> run.Cell:
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    c = run.Cell(CELL, here=chip)
    c.config["scale_factor"] = 0.01
    return c


def test_the_traced_run_counts_the_generic_joins(cell):
    res = run.run_cell(cell, 2**31 + 17, 1.0, True, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    # at SF 0.01 the generic joins are q3's two, each probed by a whole
    # lineitem fragment, which merges; the other joins run the kernel
    assert res["metrics"]["join_merge_pct"]["value"] == pytest.approx(100.0)


def _run_of(*reports) -> run.Run:
    stats = NS(pipelines=list(reports))
    handle = NS(stats=lambda: stats)
    return run.Run(records=[{"query": "q3", "error": None,
                             "handle": handle}], n=1)


def _report(merged, searched, cache_hit=False):
    return NS(cache_hit=cache_hit, counters={"join_merge_rows": merged,
                                             "join_search_rows": searched})


def test_join_merge_pct_reads_the_counters_of_fresh_pipelines():
    read = run.Cell(CELL).reader("join_merge_pct")
    got = read(_run_of(_report(600, 0), _report(0, 200),
                       _report(0, 900, cache_hit=True)))
    assert got == pytest.approx(75.0)
    # no generic join, or a program without the counters: no value
    assert read(_run_of(_report(0, 0))) is None
    assert read(_run_of(NS(cache_hit=False, counters={"d2h_bytes": 8}))) \
        is None
    assert read(run.Run(records=[], n=0)) is None
