#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3

The configuration states float32 accumulation for float aggregates. The
control is the plain reference computed one precision lower, in
bfloat16, put in the program's place: its answers go through the same
comparison and limits as the program's rows, on the cell's own data, and
must come out as not correct. One JSON line per seed gives every number
compared beside its limit; the last line gives the smallest
``max_rel_err`` over the seeds (the upper reading of that limit).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import compare  # noqa: E402
import tpch_data  # noqa: E402
from run import Cell  # noqa: E402


def control_checks(cell: Cell, seed: int, scale_factor: float | None = None,
                   ft=ml_dtypes.bfloat16) -> dict:
    """Every compared number of the control on ``seed``'s data."""
    sf = scale_factor or cell.config["scale_factor"]
    data = tpch_data.generate(sf, seed)
    want = {q: cell.reference(q)(data, np.float64) for q in cell.queries}
    got = [(q, cell.reference(q)(data, ft)) for q in cell.queries]
    return compare.judge(got, want, 0, cell.config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    worst = []
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(cell, seed)
        worst.append(checks["max_rel_err"]["value"])
        print(json.dumps({"seed": seed, "correct": compare.passed(checks),
                          "checks": checks}), flush=True)
    print(json.dumps({"workload": cell.name,
                      "min_max_rel_err": min(worst)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
