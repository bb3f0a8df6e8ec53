"""Plain reference of TPC-H Q1 (pricing summary report)."""

import numpy as np

from refkit import group_sum
from tpch_data import day, whole


def reference(t, ft) -> dict:
    li = whole(t["lineitem"], ("l_returnflag", "l_linestatus", "l_quantity",
                               "l_extendedprice", "l_discount", "l_tax",
                               "l_shipdate"))
    m = li["l_shipdate"] <= day("1998-12-01") - 90
    flag = li["l_returnflag"][m].astype(np.int64)
    status = li["l_linestatus"][m].astype(np.int64)
    qty, price, disc, tax = (li[c][m].astype(ft) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    one = np.asarray(1, ft)
    disc_price = price * (one - disc)
    key = flag * 2 + status
    g, sum_qty = group_sum(key, qty, ft)
    _, sum_base = group_sum(key, price, ft)
    _, sum_disc_price = group_sum(key, disc_price, ft)
    _, sum_charge = group_sum(key, disc_price * (one + tax), ft)
    _, sum_disc = group_sum(key, disc, ft)
    count = np.bincount(np.searchsorted(g, key), minlength=len(g))
    n = count.astype(ft)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    return {
        "l_returnflag": g // 2, "l_linestatus": g % 2,
        "sum_qty": f64(sum_qty), "sum_base_price": f64(sum_base),
        "sum_disc_price": f64(sum_disc_price),
        "sum_charge": f64(sum_charge),
        "avg_qty": f64(sum_qty / n), "avg_price": f64(sum_base / n),
        "avg_disc": f64(sum_disc / n), "count_order": count.astype(np.int64),
    }
