"""Plain reference of TPC-H Q6 (forecasting revenue change)."""

from refkit import total
from tpch_data import day, whole


def reference(t, ft) -> dict:
    li = whole(t["lineitem"], ("l_shipdate", "l_discount", "l_quantity",
                               "l_extendedprice"))
    m = ((li["l_shipdate"] >= day("1994-01-01"))
         & (li["l_shipdate"] < day("1995-01-01"))
         & (li["l_discount"] >= 0.05) & (li["l_discount"] <= 0.07)
         & (li["l_quantity"] < 24))
    price, disc = (li[c][m].astype(ft) for c in ("l_extendedprice",
                                                 "l_discount"))
    return {"revenue": total(price * disc, ft)}
