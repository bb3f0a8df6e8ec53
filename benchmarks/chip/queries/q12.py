"""Plain reference of TPC-H Q12 (shipping modes and order priority)."""

import numpy as np

from refkit import lookup
from tpch_data import ORDERPRIORITY, SHIPMODE, day, whole


def reference(t, ft) -> dict:
    od = whole(t["orders"], ("o_orderkey", "o_orderpriority"))
    li = whole(t["lineitem"], ("l_orderkey", "l_shipmode", "l_shipdate",
                               "l_commitdate", "l_receiptdate"))
    modes = [SHIPMODE.index("MAIL"), SHIPMODE.index("SHIP")]
    m = (np.isin(li["l_shipmode"], modes)
         & (li["l_commitdate"] < li["l_receiptdate"])
         & (li["l_shipdate"] < li["l_commitdate"])
         & (li["l_receiptdate"] >= day("1994-01-01"))
         & (li["l_receiptdate"] < day("1995-01-01")))
    prio = lookup(od["o_orderkey"], od["o_orderpriority"],
                  int(od["o_orderkey"].max()))[li["l_orderkey"][m]]
    high = np.isin(prio, [ORDERPRIORITY.index("1-URGENT"),
                          ORDERPRIORITY.index("2-HIGH")])
    mode = li["l_shipmode"][m].astype(np.int64)
    groups = np.unique(mode)
    return {
        "l_shipmode": groups,
        "high_line_count": np.asarray([np.sum(high[mode == g])
                                       for g in groups], np.int64),
        "low_line_count": np.asarray([np.sum(~high[mode == g])
                                      for g in groups], np.int64),
    }
