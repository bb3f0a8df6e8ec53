"""The Q18 cell's reference, comparison and metric, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

At SF 0.01 only a handful of orders pass QUANTITY 300, so the harness is
copied with the threshold lowered to 270 in both the query text and the
reference, where more than ten orders qualify. No number here is a speed.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import compare  # noqa: E402
import control  # noqa: E402
import run  # noqa: E402
import tpch_data  # noqa: E402

TINY_SF = 0.01
LOWERED = 270
CELL = "sf1-q18-large-volume"


@pytest.fixture
def cell(tmp_path) -> run.Cell:
    """The Q18 cell at SF 0.01 with QUANTITY 270, in a copy of the
    harness."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for name, old, new in (("q18.sql", "> 300", f"> {LOWERED}"),
                           ("q18.py", "QUANTITY = 300",
                            f"QUANTITY = {LOWERED}")):
        path = chip / "queries" / name
        text = path.read_text()
        assert old in text
        path.write_text(text.replace(old, new))
    c = run.Cell(CELL, here=chip)
    c.config["scale_factor"] = TINY_SF
    return c


def test_the_reference_has_rows_to_compare(cell):
    want = cell.reference("q18")(tpch_data.generate(TINY_SF, 41),
                                 np.float64)
    n = len(want["o_orderkey"])
    assert 10 <= n <= 100
    assert len(np.unique(want["o_orderkey"])) == n


def test_the_program_matches_the_reference(cell):
    res = run.run_cell(cell, 2**31 + 41, 1.0, False, 1,
                       log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}


def test_the_traced_run_reports_the_fold(cell):
    res = run.run_cell(cell, 43, 1.0, True, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    # the subquery's partials: 60k lineitems in, one group per order out
    # (the outer aggregation's few rows are aggregated whole)
    fold = res["metrics"]["partial_agg_fold"]["value"]
    assert 3.0 < fold < 5.0


def test_one_qualifying_order_dropped_is_not_correct(cell, monkeypatch):
    from repro.exec import fragment
    orig = fragment.to_numpy

    def drop_one(block):
        out = orig(block)
        if any(c.startswith("_in0_") for c in out) and block.count:
            out = {c: v[1:] for c, v in out.items()}
        return out
    monkeypatch.setattr(fragment, "to_numpy", drop_one)
    res = run.run_cell(cell, 47, 1.0, False, 1, log=lambda *_: None)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_the_bfloat16_control_is_not_correct(cell):
    for seed in (1, 2, 3):
        checks = control.control_checks(cell, seed, TINY_SF)
        assert not compare.passed(checks), checks


def _run_of(*pipelines_per_query) -> run.Run:
    def handle(pipes):
        stats = NS(pipelines=pipes)
        return NS(stats=lambda: stats)
    records = [{"query": "q18", "error": None, "handle": handle(p)}
               for p in pipelines_per_query]
    return run.Run(records=records, n=len(records))


def _report(ops, counters, cache_hit=False):
    return NS(cache_hit=cache_hit, ops=ops, counters=counters)


def test_partial_agg_fold_reads_the_partial_pipelines_only():
    read = run.Cell(CELL).reader("partial_agg_fold")
    partial = ("partial_agg", "scan_table")
    merge = ("project", "filter", "merge_agg", "scan_exchange")
    got = read(_run_of(
        [_report(partial, {"agg_rows_in": 600, "agg_groups_out": 150}),
         _report(merge, {"agg_rows_in": 150, "agg_groups_out": 3}),
         _report(partial, {"agg_rows_in": 600, "agg_groups_out": 150},
                 cache_hit=True)],
        [_report(partial, {"agg_rows_in": 300, "agg_groups_out": 150})]))
    assert got == pytest.approx(900 / 300)
    # a program without the counters, or without op names: no value
    assert read(_run_of([_report(partial, {"d2h_bytes": 8})])) is None
    assert read(_run_of([NS(cache_hit=False, counters={})])) is None
    assert read(run.Run(records=[], n=0)) is None


def test_the_new_entries_are_well_formed():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == "tpch-sf1-bcast-scaled"
    assert cells["sf1-join-broadcast"]["config"] == "tpch-sf1"
    cfg = run.Cell(CELL).config
    assert cfg["planner"] == {"broadcast_threshold_bytes": 16777}
    assert 16777 == (16 << 20) * 1 // 1000
