"""Tests of the chip benchmark's harness, on the CPU at a tiny scale.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

They drive ``run.run_cell`` past the harness's look for a chip (Pallas
kernels run in interpret mode), so no number here is a speed.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import compare  # noqa: E402
import control  # noqa: E402
import peaks  # noqa: E402
import run  # noqa: E402
import tracereduce  # noqa: E402

TINY_SF = 0.01


def _tiny(workload: str) -> run.Cell:
    cell = run.Cell(workload)
    cell.config["scale_factor"] = TINY_SF
    return cell


# -- peaks and kernel cost ---------------------------------------------------

def test_peak_table_has_v5e_and_refuses_unknown_kinds():
    p = peaks.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_kernel_cost_counts_bytes_from_hlo_shapes():
    hlo = ("%custom-call.3 = (f32[8,128]{1,0}) custom-call(f32[8192,128]"
           "{1,0} %p0, s32[8192,128]{1,0} %p1, pred[8192,128]{1,0} %p2), "
           "custom_call_target=\"tpu_custom_call\"")
    arrays = peaks.shapes(hlo)
    assert arrays == [("f32", (8, 128)), ("f32", (8192, 128)),
                      ("s32", (8192, 128)), ("pred", (8192, 128))]
    b, ops = peaks.kernel_cost("filter_agg", arrays)
    assert b == 8 * 128 * 4 + 8192 * 128 * (4 + 4 + 1)
    assert ops == 8 * 128 + 3 * 8192 * 128
    t, bound = peaks.roofline_s("filter_agg", arrays, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(b / 819e9)
    _, sort_ops = peaks.kernel_cost("topk", [("f32", (1024,))])
    assert sort_ops == 512 * 10 * 11 // 2 * 2


# -- trace reduction ---------------------------------------------------------

def _recorded_planes():
    with gzip.open(HERE / "testdata" / "trace_small.json.gz", "rt") as f:
        return tracereduce.from_excerpt(json.load(f))


def test_union_and_gaps():
    busy, gaps = tracereduce.union([(0, 10), (5, 20), (30, 40), (50, 200)],
                                   0, 100)
    assert busy == 20 + 10 + 50
    assert gaps == [(20, 30), (40, 50)]


def test_phase_names_the_innermost_span():
    phases = [("q1:query", 0, 100), ("q1:pipeline 0 groupby_onehot", 10, 50),
              ("q1:final fetch", 60, 100)]
    assert tracereduce.phase_at(phases, 20) == \
        "q1:pipeline 0 groupby_onehot"
    assert tracereduce.phase_at(phases, 55) == "q1:query"
    assert tracereduce.phase_at(phases, 150) == "between queries"


def test_reduction_of_a_recorded_chip_trace():
    """A q6 fragment program as traced on a TPU v5 lite: 200 ops."""
    planes = _recorded_planes()
    assert tracereduce.anchor_ns(planes) is not None
    ops = tracereduce.device_ops(planes)
    assert list(ops) == ["/device:TPU:0"]
    flat = ops["/device:TPU:0"]
    lo = min(o.start_ns for o in flat)
    hi = max(o.start_ns + o.dur_ns for o in flat)
    s = tracereduce.reduce(ops, lo, hi, [("q6:query", lo, hi)],
                           [(lo, hi, "filter_agg")], "TPU v5 lite")
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] * 1e9 <= sum(o.dur_ns for o in flat) + 1
    assert s["kernel_calls"] == {"filter_agg": 7}
    assert s["op_s"]["tpu_custom_call:filter_agg"] == s["kernel_s"][
        "filter_agg"] > 0
    assert {"fusion", "X64SplitLow", "copy-done"} <= set(s["op_s"])
    assert all(name == "q6:query" for name, _ in s["idle_gaps"])
    call = next(o for o in flat if o.kind == "tpu_custom_call")
    assert call.arrays()[0] == ("f32", (1, 8, 128))
    # without a pipeline span the call keeps its target's name
    s = tracereduce.reduce(ops, lo, hi, [], [], "TPU v5 lite")
    assert "tpu_custom_call:tpu_custom_call" in s["op_s"]


# -- the harness, end to end on the CPU --------------------------------------

def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "run.py"), "--workload",
                        "sf1-scan-agg", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_records_every_query_and_is_correct():
    cell = _tiny("sf1-scan-agg")
    res = run.run_cell(cell, 2**31 + 5, 2.0, False, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 10 and res["failed"] == 0
    assert set(res["metrics"]) == {"queries_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    lines = (run.OUT_DIR / "sf1-scan-agg.jsonl").read_text().splitlines()
    rec = json.loads(lines[-1])
    assert {"start_s", "latency_s", "query", "compiles", "fragments",
            "bytes_read"} <= set(rec)


def test_traced_join_run_reports_its_per_layer_metrics():
    cell = _tiny("sf1-join-shuffle")
    res = run.run_cell(cell, 17, 2.0, True, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["exchange_mb_per_query"]["value"] > 0
    assert "latency_p90_s" not in m and "queries_per_s" not in m
    # the CPU has no TPU plane: no device metric is made up
    assert "device_idle_pct" not in m and "busy_s" not in res["device"]


def test_a_new_cell_is_found_by_its_files_alone(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((chip / "configs" / "tpch-sf1.json").read_text())
    cfg.update(name="tiny", scale_factor=TINY_SF)
    (chip / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "two-q6.json").write_text(json.dumps(
        {"name": "two-q6", "loop": "closed",
         "clients": [{"queries": ["q6"]}, {"queries": ["q6", "q14"]}]}))
    (chip / "metrics" / "q6_share_pct.py").write_text(
        "def read(run):\n"
        "    return 100.0 * sum(r['query'] == 'q6' for r in run.records)"
        " / len(run.records)\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": ["scale_factor"], "why": "test"})
    bench["workloads"].append({"name": "tiny.two-q6", "config": "tiny",
                               "traffic": "two-q6", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "q6_share_pct", "unit": "%",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny.two-q6"]})
    # a dotted name with no file of its own reads as its stem does
    bench["end_to_end"].append({"name": "latency_p50_s.tiny", "unit": "s",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny.two-q6"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.Cell("tiny.two-q6", here=chip)
    assert cell.queries == ["q14", "q6"]
    assert cell.sequences(0) == [["q6"], ["q14", "q6"]]
    res = run.run_cell(cell, 9, 2.0, False, 1, log=lambda *_: None)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"queries_per_s", "setup_s",
                                   "q6_share_pct", "latency_p50_s.tiny"}
    assert 50 < res["metrics"]["q6_share_pct"]["value"] < 100


# -- correctness: planted faults and the control -----------------------------

def _alter_answers(monkeypatch):
    from repro.exec import fragment
    orig = fragment.to_numpy

    def altered(block):
        out = orig(block)
        return {c: v * 1.01 if v.dtype.kind == "f" else v
                for c, v in out.items()}
    monkeypatch.setattr(fragment, "to_numpy", altered)


def _drop_half_the_rows(monkeypatch):
    from repro.exec import fragment
    orig = fragment._load_scan_table

    def half(*args, **kw):
        return {c: v[::2] for c, v in orig(*args, **kw).items()}
    monkeypatch.setattr(fragment, "_load_scan_table", half)


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half_the_rows],
                         ids=["answer_altered", "half_rows_left_out"])
@pytest.mark.parametrize("workload", ["sf1-scan-agg", "sf1-join-shuffle"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, workload):
    cell = _tiny(workload)
    fault(monkeypatch)
    res = run.run_cell(cell, 23, 1.0, False, 1, log=lambda *_: None)
    assert not res["correct"]
    assert not compare.passed(res["checks"])


@pytest.mark.parametrize("workload", ["sf1-scan-agg", "sf1-join-shuffle"])
def test_the_bfloat16_control_is_not_correct(workload):
    cell = _tiny(workload)
    for seed in (1, 2, 3):
        checks = control.control_checks(cell, seed, TINY_SF)
        assert not compare.passed(checks)
        assert checks["max_rel_err"]["value"] > \
            10 * cell.config["limits"]["max_rel_err"]
