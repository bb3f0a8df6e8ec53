"""Plain reference of TPC-H Q14 (promotion effect)."""

import numpy as np

from refkit import lookup
from tpch_data import PTYPE, day, whole


def reference(t, ft) -> dict:
    pa = whole(t["part"], ("p_partkey", "p_type"))
    li = whole(t["lineitem"], ("l_partkey", "l_shipdate",
                               "l_extendedprice", "l_discount"))
    promo_types = [i for i, s in enumerate(PTYPE) if s.startswith("PROMO")]
    promo = lookup(pa["p_partkey"], np.isin(pa["p_type"], promo_types),
                   int(pa["p_partkey"].max()))
    m = ((li["l_shipdate"] >= day("1995-09-01"))
         & (li["l_shipdate"] < day("1995-10-01")))
    price, disc = (li[c][m].astype(ft) for c in ("l_extendedprice",
                                                 "l_discount"))
    rev = price * (np.asarray(1, ft) - disc)
    is_promo = promo[li["l_partkey"][m]]
    promo_rev = np.sum(np.where(is_promo, rev, np.asarray(0, ft)), dtype=ft)
    all_rev = np.sum(rev, dtype=ft)
    return {"promo_revenue": np.asarray(
        [np.asarray(100, ft) * promo_rev / all_rev], np.float64)}
