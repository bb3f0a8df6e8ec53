"""Plain reference of TPC-H Q19 (discounted revenue)."""

import numpy as np

from refkit import lookup, total
from tpch_data import BRAND, CONTAINER, SHIPINSTRUCT, SHIPMODE, whole


def reference(t, ft) -> dict:
    pa = whole(t["part"], ("p_partkey", "p_brand", "p_container", "p_size"))
    li = whole(t["lineitem"], ("l_partkey", "l_quantity", "l_shipmode",
                               "l_shipinstruct", "l_extendedprice",
                               "l_discount"))
    n_parts = int(pa["p_partkey"].max())
    pk = li["l_partkey"]
    brand = lookup(pa["p_partkey"], pa["p_brand"], n_parts)[pk]
    container = lookup(pa["p_partkey"], pa["p_container"], n_parts)[pk]
    size = lookup(pa["p_partkey"], pa["p_size"], n_parts)[pk]
    qty = li["l_quantity"]

    def branch(b, sizes, containers, lo, hi, max_size):
        return ((brand == BRAND.index(b))
                & np.isin(container, [CONTAINER.index(f"{sizes} {c}")
                                      for c in containers])
                & (qty >= lo) & (qty <= hi)
                & (size >= 1) & (size <= max_size))

    m = (branch("Brand#12", "SM", ("CASE", "BOX", "PACK", "PKG"), 1, 11, 5)
         | branch("Brand#23", "MED", ("BAG", "BOX", "PKG", "PACK"), 10, 20,
                  10)
         | branch("Brand#34", "LG", ("CASE", "BOX", "PACK", "PKG"), 20, 30,
                  15))
    m &= np.isin(li["l_shipmode"], [SHIPMODE.index("AIR"),
                                    SHIPMODE.index("REG AIR")])
    m &= li["l_shipinstruct"] == SHIPINSTRUCT.index("DELIVER IN PERSON")
    price, disc = (li[c][m].astype(ft) for c in ("l_extendedprice",
                                                 "l_discount"))
    return {"revenue": total(price * (np.asarray(1, ft) - disc), ft)}
