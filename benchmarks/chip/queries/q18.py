"""Plain reference of TPC-H Q18 (large volume customer), QUANTITY 300.

The query text leaves ``c_name`` out of its select list (see the
configuration's ``assumed``); every order joins the one customer whose
key it holds, so the customer key is ``o_custkey``."""

import numpy as np

from refkit import group_sum
from tpch_data import whole

QUANTITY = 300


def reference(t, ft) -> dict:
    li = whole(t["lineitem"], ("l_orderkey", "l_quantity"))
    od = whole(t["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                             "o_totalprice"))
    keys, qty = group_sum(li["l_orderkey"], li["l_quantity"], ft)
    qty = np.asarray(qty, np.float64)
    big = np.isin(od["o_orderkey"], keys[qty > QUANTITY])
    okey = od["o_orderkey"][big]
    price = np.asarray(od["o_totalprice"][big].astype(ft), np.float64)
    date = od["o_orderdate"][big].astype(np.int64)
    top = np.lexsort((date, -price))[:100]
    return {"c_custkey": od["o_custkey"][big][top],
            "o_orderkey": okey[top],
            "o_orderdate": date[top],
            "o_totalprice": price[top],
            "col4": qty[np.searchsorted(keys, okey[top])].astype(np.int64)}
