"""TPC-H data for the benchmark, made from the run's seed.

The generator is the benchmark's own copy of the program's dbgen-shaped
generator (``repro.data.tpch``): the same distributions, cross-table
dependencies, partition layout and per-(seed, table, partition) random
streams, so a run loads exactly the data a user gets from
``session.ensure_tpch(sf, seed=seed)``. The copy keeps the tables as NumPy
columns for the plain reference (``queries/*.py``), which must never read
data back from the system under test.

Dictionary-encoded columns hold codes into the dictionaries below; the
reference and the system agree on them because the benchmark writes the
dictionaries into the catalog itself.
"""

from __future__ import annotations

import numpy as np

EPOCH = np.datetime64("1970-01-01")


def day(s: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return int((np.datetime64(s) - EPOCH).astype(int))


CURRENT_DATE = day("1995-06-17")
START_DATE = day("1992-01-01")
END_DATE = day("1998-12-31") - 151

RETURNFLAG = ("A", "N", "R")
LINESTATUS = ("F", "O")
SHIPMODE = ("AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK")
SHIPINSTRUCT = ("COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN")
ORDERPRIORITY = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
ORDERSTATUS = ("F", "O", "P")
MKTSEGMENT = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
BRAND = tuple(f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6))
PTYPE = tuple(f"{a} {b} {c}"
              for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                        "PROMO")
              for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                        "BRUSHED")
              for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER"))
CONTAINER = tuple(f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
                  for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                            "DRUM"))
NATION = ("ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
          "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
          "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
          "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
          "UNITED STATES")
REGION = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
NATION_REGION = (0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                 4, 2, 3, 3, 1)

# (column, kind, dtype, dictionary): kind is num | dict | bytes
I64, I32, F64 = "<i8", "<i4", "<f8"


def _n(c): return (c, "num", I64, None)
def _f(c): return (c, "num", F64, None)
def _d(c): return (c, "num", I32, None)
def _k(c, d): return (c, "dict", I32, d)
def _b(c, w): return (c, "bytes", f"S{w}", None)


SCHEMAS = {
    "lineitem": [
        _n("l_orderkey"), _n("l_partkey"), _n("l_suppkey"),
        _n("l_linenumber"), _f("l_quantity"), _f("l_extendedprice"),
        _f("l_discount"), _f("l_tax"), _k("l_returnflag", RETURNFLAG),
        _k("l_linestatus", LINESTATUS), _d("l_shipdate"),
        _d("l_commitdate"), _d("l_receiptdate"),
        _k("l_shipinstruct", SHIPINSTRUCT), _k("l_shipmode", SHIPMODE),
        _b("l_comment", 20)],
    "orders": [
        _n("o_orderkey"), _n("o_custkey"), _k("o_orderstatus", ORDERSTATUS),
        _f("o_totalprice"), _d("o_orderdate"),
        _k("o_orderpriority", ORDERPRIORITY), _b("o_clerk", 15),
        _n("o_shippriority"), _b("o_comment", 20)],
    "customer": [
        _n("c_custkey"), _b("c_name", 18), _b("c_address", 20),
        _n("c_nationkey"), _b("c_phone", 15), _f("c_acctbal"),
        _k("c_mktsegment", MKTSEGMENT), _b("c_comment", 20)],
    "part": [
        _n("p_partkey"), _b("p_name", 30), _b("p_mfgr", 14),
        _k("p_brand", BRAND), _k("p_type", PTYPE), _n("p_size"),
        _k("p_container", CONTAINER), _f("p_retailprice"),
        _b("p_comment", 14)],
    "supplier": [
        _n("s_suppkey"), _b("s_name", 18), _b("s_address", 20),
        _n("s_nationkey"), _b("s_phone", 15), _f("s_acctbal"),
        _b("s_comment", 20)],
    "partsupp": [
        _n("ps_partkey"), _n("ps_suppkey"), _n("ps_availqty"),
        _f("ps_supplycost"), _b("ps_comment", 20)],
    "nation": [
        _n("n_nationkey"), _k("n_name", NATION), _n("n_regionkey"),
        _b("n_comment", 20)],
    "region": [
        _n("r_regionkey"), _k("r_name", REGION), _b("r_comment", 20)],
}


def _retail_price(partkey: np.ndarray) -> np.ndarray:
    return (90000 + (partkey % 20001) + 100 * (partkey % 1000)) / 100.0


def _rand_bytes(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    letters = rng.integers(65, 91, size=(n, width), dtype=np.uint8)
    return letters.view(f"S{width}").reshape(n)


def customers(sf: float) -> int: return max(int(150_000 * sf), 32)
def orders_count(sf: float) -> int: return customers(sf) * 10
def parts(sf: float) -> int: return max(int(200_000 * sf), 64)
def suppliers(sf: float) -> int: return max(int(10_000 * sf), 8)


def n_partitions(sf: float) -> int:
    """Orders/lineitem partition files: about 250k orders each."""
    return max(1, int(np.ceil(orders_count(sf) / 250_000)))


def orders_partition(sf: float, part: int, n_parts: int,
                     seed: int) -> dict[str, dict[str, np.ndarray]]:
    """Orders rows [lo, hi) of the whole table, and their lineitems."""
    total = orders_count(sf)
    lo = part * total // n_parts
    hi = (part + 1) * total // n_parts
    n = hi - lo
    rng = np.random.default_rng((seed, 1, part))
    okey = np.arange(lo + 1, hi + 1, dtype=np.int64)
    odate = rng.integers(START_DATE, END_DATE + 1, n).astype(np.int32)
    lines = rng.integers(1, 8, n)
    orders = {
        "o_orderkey": okey,
        "o_custkey": rng.integers(1, customers(sf) + 1, n, dtype=np.int64),
        "o_orderstatus": np.zeros(n, np.int32),
        "o_totalprice": np.zeros(n),
        "o_orderdate": odate,
        "o_orderpriority": rng.integers(0, len(ORDERPRIORITY), n,
                                        dtype=np.int32),
        "o_clerk": _rand_bytes(rng, n, 15),
        "o_shippriority": np.zeros(n, dtype=np.int64),
        "o_comment": _rand_bytes(rng, n, 20),
    }
    m = int(lines.sum())
    li_order = np.repeat(np.arange(n), lines)
    l_odate = odate[li_order].astype(np.int64)
    pk = rng.integers(1, parts(sf) + 1, m, dtype=np.int64)
    qty = rng.integers(1, 51, m).astype(np.float64)
    shipdate = (l_odate + rng.integers(1, 122, m)).astype(np.int32)
    commitdate = (l_odate + rng.integers(30, 91, m)).astype(np.int32)
    receiptdate = (shipdate.astype(np.int64)
                   + rng.integers(1, 31, m)).astype(np.int32)
    returned = receiptdate <= CURRENT_DATE
    rflag = np.where(returned, rng.integers(0, 2, m) * 2,
                     np.int64(1)).astype(np.int32)
    lstatus = (shipdate > CURRENT_DATE).astype(np.int32)
    eprice = qty * _retail_price(pk)
    lineitem = {
        "l_orderkey": okey[li_order],
        "l_partkey": pk,
        "l_suppkey": rng.integers(1, suppliers(sf) + 1, m, dtype=np.int64),
        "l_linenumber": (np.arange(m, dtype=np.int64)
                         - np.repeat(np.cumsum(lines) - lines, lines) + 1),
        "l_quantity": qty,
        "l_extendedprice": eprice,
        "l_discount": rng.integers(0, 11, m) / 100.0,
        "l_tax": rng.integers(0, 9, m) / 100.0,
        "l_returnflag": rflag,
        "l_linestatus": lstatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), m,
                                       dtype=np.int32),
        "l_shipmode": rng.integers(0, len(SHIPMODE), m, dtype=np.int32),
        "l_comment": _rand_bytes(rng, m, 20),
    }
    price = eprice * (1 + lineitem["l_tax"]) * (1 - lineitem["l_discount"])
    orders["o_totalprice"] = np.bincount(li_order, weights=price,
                                         minlength=n)
    all_f = np.bincount(li_order, weights=(lstatus == 0), minlength=n) \
        == lines
    all_o = np.bincount(li_order, weights=(lstatus == 1), minlength=n) \
        == lines
    orders["o_orderstatus"] = np.where(
        all_f, 0, np.where(all_o, 1, 2)).astype(np.int32)
    return {"orders": orders, "lineitem": lineitem}


def single_tables(sf: float, seed: int) -> dict[str, dict[str, np.ndarray]]:
    """The six tables that are one file each."""
    nc, np_, ns = customers(sf), parts(sf), suppliers(sf)
    rng = np.random.default_rng((seed, 2))
    customer = {
        "c_custkey": np.arange(1, nc + 1, dtype=np.int64),
        "c_name": _rand_bytes(rng, nc, 18),
        "c_address": _rand_bytes(rng, nc, 20),
        "c_nationkey": rng.integers(0, 25, nc, dtype=np.int64),
        "c_phone": _rand_bytes(rng, nc, 15),
        "c_acctbal": rng.integers(-99999, 1000000, nc) / 100.0,
        "c_mktsegment": rng.integers(0, len(MKTSEGMENT), nc, dtype=np.int32),
        "c_comment": _rand_bytes(rng, nc, 20),
    }
    rng = np.random.default_rng((seed, 3))
    pk = np.arange(1, np_ + 1, dtype=np.int64)
    part = {
        "p_partkey": pk,
        "p_name": _rand_bytes(rng, np_, 30),
        "p_mfgr": _rand_bytes(rng, np_, 14),
        "p_brand": rng.integers(0, len(BRAND), np_, dtype=np.int32),
        "p_type": rng.integers(0, len(PTYPE), np_, dtype=np.int32),
        "p_size": rng.integers(1, 51, np_, dtype=np.int64),
        "p_container": rng.integers(0, len(CONTAINER), np_, dtype=np.int32),
        "p_retailprice": _retail_price(pk),
        "p_comment": _rand_bytes(rng, np_, 14),
    }
    rng = np.random.default_rng((seed, 4))
    supplier = {
        "s_suppkey": np.arange(1, ns + 1, dtype=np.int64),
        "s_name": _rand_bytes(rng, ns, 18),
        "s_address": _rand_bytes(rng, ns, 20),
        "s_nationkey": rng.integers(0, 25, ns, dtype=np.int64),
        "s_phone": _rand_bytes(rng, ns, 15),
        "s_acctbal": rng.integers(-99999, 1000000, ns) / 100.0,
        "s_comment": _rand_bytes(rng, ns, 20),
    }
    rng = np.random.default_rng((seed, 5))
    nps = np_ * 4
    partsupp = {
        "ps_partkey": np.repeat(pk, 4),
        "ps_suppkey": rng.integers(1, ns + 1, nps, dtype=np.int64),
        "ps_availqty": rng.integers(1, 10000, nps, dtype=np.int64),
        "ps_supplycost": rng.integers(100, 100001, nps) / 100.0,
        "ps_comment": _rand_bytes(rng, nps, 20),
    }
    rng = np.random.default_rng((seed, 6))
    nation = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": np.arange(25, dtype=np.int32),
        "n_regionkey": np.asarray(NATION_REGION, dtype=np.int64),
        "n_comment": _rand_bytes(rng, 25, 20),
    }
    rng = np.random.default_rng((seed, 7))
    region = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": np.arange(5, dtype=np.int32),
        "r_comment": _rand_bytes(rng, 5, 20),
    }
    return {"customer": customer, "part": part, "supplier": supplier,
            "partsupp": partsupp, "nation": nation, "region": region}


def generate(sf: float, seed: int) -> dict[str, list[dict[str, np.ndarray]]]:
    """Every table as its list of partition files' columns."""
    n_parts = n_partitions(sf)
    out: dict[str, list] = {"orders": [], "lineitem": []}
    for p in range(n_parts):
        part = orders_partition(sf, p, n_parts, seed)
        out["orders"].append(part["orders"])
        out["lineitem"].append(part["lineitem"])
    for name, cols in single_tables(sf, seed).items():
        out[name] = [cols]
    return out


def whole(partitions: list[dict[str, np.ndarray]],
          columns: tuple[str, ...]) -> dict[str, np.ndarray]:
    """The named columns of a table, its partitions concatenated."""
    return {c: np.concatenate([p[c] for p in partitions]) for c in columns}


def load(store, data: dict[str, list[dict[str, np.ndarray]]], sf: float,
         row_group_rows: int):
    """Write ``data`` into the system's object store as SPAX partition
    files, the layout ``repro.data.tpch.generate_tpch`` writes, and
    return the catalog that registers them (with the per-column min/max
    hints the planner's selectivity estimator reads)."""
    from repro.data.catalog import Catalog, TableMeta
    from repro.storage.pax import ColumnSpec, write_pax

    prefix = f"tpch/sf{sf:g}"
    catalog = Catalog()
    for table, partitions in data.items():
        schema = [ColumnSpec(n, k, d, dic) for n, k, d, dic in SCHEMAS[table]]
        files, rows, nbytes, stats = [], 0, 0, {}
        for i, cols in enumerate(partitions):
            key = f"{prefix}/{table}/part-{i:05d}.spax"
            blob = write_pax(cols, schema, row_group_rows)
            store.put(key, blob)
            files.append(key)
            rows += len(next(iter(cols.values())))
            nbytes += len(blob)
            for c in schema:
                if c.kind == "bytes" or not len(cols[c.name]):
                    continue
                lo, hi = cols[c.name].min().item(), cols[c.name].max().item()
                if c.name in stats:
                    lo, hi = min(lo, stats[c.name][0]), max(hi,
                                                            stats[c.name][1])
                stats[c.name] = (lo, hi)
        catalog.add(TableMeta(table, schema, files, rows, nbytes, stats))
    catalog.save(store, f"{prefix}/catalog")
    return catalog
