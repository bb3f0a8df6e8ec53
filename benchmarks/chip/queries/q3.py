"""Plain reference of TPC-H Q3 (shipping priority), BUILDING segment."""

import numpy as np

from refkit import group_sum, lookup
from tpch_data import MKTSEGMENT, day, whole


def reference(t, ft) -> dict:
    cu = whole(t["customer"], ("c_custkey", "c_mktsegment"))
    od = whole(t["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                             "o_shippriority"))
    li = whole(t["lineitem"], ("l_orderkey", "l_extendedprice",
                               "l_discount", "l_shipdate"))
    building = lookup(cu["c_custkey"],
                      cu["c_mktsegment"] == MKTSEGMENT.index("BUILDING"),
                      int(cu["c_custkey"].max()))
    cutoff = day("1995-03-15")
    n_orders = int(od["o_orderkey"].max())
    order_ok = lookup(od["o_orderkey"],
                      building[od["o_custkey"]]
                      & (od["o_orderdate"] < cutoff), n_orders)
    m = order_ok[li["l_orderkey"]] & (li["l_shipdate"] > cutoff)
    price, disc = (li[c][m].astype(ft) for c in ("l_extendedprice",
                                                 "l_discount"))
    keys, revenue = group_sum(li["l_orderkey"][m],
                              price * (np.asarray(1, ft) - disc), ft)
    revenue = np.asarray(revenue, np.float64)
    orderdate = lookup(od["o_orderkey"], od["o_orderdate"], n_orders)[keys]
    shippriority = lookup(od["o_orderkey"], od["o_shippriority"],
                          n_orders)[keys]
    top = np.lexsort((orderdate, -revenue))[:10]
    return {"l_orderkey": keys[top], "revenue": revenue[top],
            "o_orderdate": orderdate[top].astype(np.int64),
            "o_shippriority": shippriority[top].astype(np.int64)}
