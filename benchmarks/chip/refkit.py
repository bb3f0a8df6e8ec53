"""Helpers of the plain reference (``queries/<name>.py``).

Every reference computes its money arithmetic and sums in the float type
``ft`` it is given: float64 for the reference, a lower precision for the
control (``control.py``). Keys, dates and counts stay integers.
"""

from __future__ import annotations

import numpy as np


def group_sum(keys: np.ndarray, values: np.ndarray, ft) -> tuple:
    """(distinct keys ascending, per-key sums of ``values`` in ``ft``)."""
    order = np.argsort(keys, kind="stable")
    k = keys[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]]) if len(k) \
        else np.zeros(0, np.int64)
    sums = np.add.reduceat(values[order].astype(ft), starts) if len(k) \
        else np.zeros(0, ft)
    return k[starts], sums


def total(values: np.ndarray, ft) -> np.ndarray:
    """A one-row sum in ``ft``, as float64."""
    return np.asarray([np.sum(values.astype(ft), dtype=ft)], np.float64)


def lookup(keys: np.ndarray, values: np.ndarray, size: int) -> np.ndarray:
    """Dense key → value table (TPC-H keys are 1..n)."""
    table = np.zeros(size + 1, values.dtype)
    table[keys] = values
    return table
