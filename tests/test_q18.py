"""TPC-H Q18 (Large Volume Customer) on the normal path: HAVING, an IN
subquery decorrelated into a join on a derived aggregate, byte-string
columns through the fragment program, and the aggregation counters.

At SF 0.01 (the session fixture, seed 0) only 3 of the 15,000 orders
pass the specification's QUANTITY 300, so the tests lower the threshold
to 270, where 13 orders qualify: enough rows that a lost, duplicated or
misjoined order shows, through the same plan."""

import numpy as np
import pytest

from repro.core import CoordinatorConfig, FaasPlatform, QueryCoordinator
from repro.exec.batch import Block, compact, from_numpy, to_numpy
from repro.sql import oracle
from repro.sql.logical import Binder, BindError
from repro.sql.parser import parse
from repro.sql.physical import PlannerConfig
from repro.sql.queries import TPCH_Q18
from repro.sql.rules import optimize

THRESHOLD = 270


def _q18(threshold: int) -> str:
    assert "> 300" in TPCH_Q18
    return TPCH_Q18.replace("> 300", f"> {threshold}")


def _run(store, catalog, sql, broadcast_threshold_bytes=150_000):
    cfg = CoordinatorConfig(use_result_cache=False, planner=PlannerConfig(
        bytes_per_worker=250_000, exchange_partitions=3,
        broadcast_threshold_bytes=broadcast_threshold_bytes))
    coord = QueryCoordinator(store, catalog, platform=FaasPlatform(seed=1),
                             config=cfg)
    res = coord.execute_sql(sql)
    return res.fetch(store), res


def _numpy_q18(t, threshold: int) -> dict:
    """Clause 2.4.18 in plain NumPy, on the same tables."""
    li, od, cu = t["lineitem"], t["orders"], t["customer"]
    keys, inv = np.unique(li["l_orderkey"], return_inverse=True)
    qty = np.bincount(inv, weights=li["l_quantity"])
    big = np.isin(od["o_orderkey"], keys[qty > threshold])
    o = {c: od[c][big] for c in ("o_orderkey", "o_custkey", "o_orderdate",
                                  "o_totalprice")}
    by_cust = np.argsort(cu["c_custkey"])
    at = by_cust[np.searchsorted(cu["c_custkey"][by_cust], o["o_custkey"])]
    rows = {"c_name": cu["c_name"][at], "c_custkey": cu["c_custkey"][at],
            "o_orderkey": o["o_orderkey"],
            "o_orderdate": o["o_orderdate"],
            "o_totalprice": o["o_totalprice"],
            "col5": qty[np.searchsorted(keys, o["o_orderkey"])]}
    top = np.lexsort((rows["o_orderdate"], -rows["o_totalprice"]))[:100]
    return {c: v[top] for c, v in rows.items()}


def _oracle(catalog, tables, sql):
    plan, _ = Binder(catalog).bind(parse(sql))
    return oracle.run(optimize(plan), tables)


def _same_rows(got: dict, want: dict, what: str) -> None:
    assert set(got) == set(want), what
    og, ow = np.argsort(got["o_orderkey"]), np.argsort(want["o_orderkey"])
    for c in want:
        g, w = np.asarray(got[c])[og], np.asarray(want[c])[ow]
        if c == "o_totalprice":
            np.testing.assert_allclose(g, w, rtol=1e-12, err_msg=what)
        else:
            assert np.array_equal(g, w), f"{what}: column {c}"


@pytest.mark.parametrize("broadcast", [150_000, 1],
                         ids=["broadcast", "shuffle"])
def test_q18_matches_numpy_and_oracle(tpch_store, tpch_tables, broadcast):
    store, catalog = tpch_store
    sql = _q18(THRESHOLD)
    got, _ = _run(store, catalog, sql, broadcast)
    want = _numpy_q18(tpch_tables, THRESHOLD)
    assert len(want["o_orderkey"]) >= 10
    assert got["c_name"].dtype == np.dtype("S18")
    _same_rows(got, want, "program vs numpy")
    _same_rows(_oracle(catalog, tpch_tables, sql), want, "oracle vs numpy")
    # the answer's order is the query's: price down, then date up
    price = np.asarray(got["o_totalprice"])
    assert np.all(price[:-1] >= price[1:])


def test_q18_no_order_qualifies(tpch_store, tpch_tables):
    store, catalog = tpch_store
    sql = _q18(100_000)
    got, _ = _run(store, catalog, sql)
    assert all(len(v) == 0 for v in got.values())
    assert len(got) == 6
    assert all(len(v) == 0
               for v in _oracle(catalog, tpch_tables, sql).values())


def test_q18_plan_cuts_lineitem_before_any_exchange(tpch_store):
    """The derived side joins ``orders`` first, so ``lineitem`` meets a
    broadcast build of the qualifying orders and only the joined rows
    reach an exchange: every joining pipeline hands on under 1% of
    ``lineitem``'s rows."""
    store, catalog = tpch_store
    _, res = _run(store, catalog, _q18(THRESHOLD))
    n_lineitem = catalog.table("lineitem").rows
    joins = [p for p in res.stats.pipelines if "join" in p.ops]
    assert len(joins) >= 2
    for p in joins:
        assert p.rows_out < 0.01 * n_lineitem, p.ops
        # the outer aggregation's input is estimated at a few hundred
        # rows: no partials sort the probe's capacity, one merge takes
        # the rows whole
        assert "partial_agg" not in p.ops


@pytest.mark.parametrize("sql, words", [
    # correlated: the subquery reads the outer query's o_custkey
    ("select o_orderkey from orders where o_orderkey in (select l_orderkey"
     " from lineitem where l_suppkey = o_custkey group by l_orderkey)",
     "correlated"),
    # not grouped: l_orderkey repeats, so a join would repeat outer rows
    ("select o_orderkey from orders where o_orderkey in (select l_orderkey"
     " from lineitem where l_quantity > 40)", "non-grouped"),
    # grouped, but not on the selected key alone
    ("select o_orderkey from orders where o_orderkey in (select l_orderkey"
     " from lineitem group by l_orderkey, l_partkey)", "non-grouped"),
    ("select o_orderkey from orders where o_totalprice > 1 or o_orderkey in"
     " (select l_orderkey from lineitem group by l_orderkey)", "OR"),
])
def test_in_subquery_rejected(tpch_store, sql, words):
    _, catalog = tpch_store
    with pytest.raises(BindError, match=words):
        Binder(catalog).bind(parse(sql))


def test_having_filters_groups(tpch_store, tpch_tables):
    store, catalog = tpch_store
    sql = ("select o_custkey, count(*) as n from orders group by o_custkey"
           " having count(*) >= 15 order by o_custkey")
    got, _ = _run(store, catalog, sql)
    keys, n = np.unique(tpch_tables["orders"]["o_custkey"],
                        return_counts=True)
    assert np.array_equal(got["o_custkey"], keys[n >= 15])
    assert np.array_equal(got["n"], n[n >= 15])
    assert len(keys[n >= 15]) > 0


def test_q10_groups_and_returns_byte_strings(tpch_store, tpch_tables):
    """TPC-H Q10 groups by and returns customer text columns."""
    store, catalog = tpch_store
    sql = """
    select c_custkey, c_name,
        sum(l_extendedprice * (1 - l_discount)) as revenue,
        c_acctbal, c_address, c_phone, c_comment
    from customer, orders, lineitem
    where c_custkey = o_custkey
        and l_orderkey = o_orderkey
        and o_orderdate >= date '1993-10-01'
        and o_orderdate < date '1993-10-01' + interval '3' month
        and l_returnflag = 'R'
    group by c_custkey, c_name, c_acctbal, c_phone, c_address, c_comment
    order by revenue desc
    limit 20
    """
    got, _ = _run(store, catalog, sql)
    want = _oracle(catalog, tpch_tables, sql)
    assert len(want["c_custkey"]) == 20
    for c in ("c_custkey", "c_name", "c_address", "c_phone", "c_comment",
              "c_acctbal"):
        assert np.array_equal(got[c], want[c]), c
    np.testing.assert_allclose(got["revenue"], want["revenue"], rtol=1e-9)


@pytest.mark.parametrize("width", [15, 18, 20])
def test_bytes_column_round_trip(width):
    rng = np.random.default_rng(width)
    n = 300
    raw = rng.integers(0, 256, (n, width), dtype=np.uint8)
    raw[:, -1] = rng.integers(1, 256, n)     # no trailing NUL to strip
    text = np.frombuffer(raw.tobytes(), f"S{width}")
    keys = np.arange(n)
    block = from_numpy({"t": text, "k": keys})
    assert all(v.dtype == np.int32 for c, v in block.columns.items()
               if c != "k")
    assert len(block.columns) == 1 + -(-width // 4)
    cols, count = compact(block.columns, block.mask & (block.columns["k"]
                                                        % 7 != 3))
    out = to_numpy(Block(cols, count=int(count)))
    keep = keys % 7 != 3
    assert out["t"].dtype == text.dtype
    assert np.array_equal(out["t"], text[keep])
    assert np.array_equal(out["k"], keys[keep])
    # an empty prefix comes back empty, of the same type
    none = to_numpy(Block(cols, count=0))
    assert none["t"].dtype == text.dtype and len(none["t"]) == 0


def test_aggregation_counters(tpch_store, tpch_tables):
    """Every aggregating fragment counts the rows it took in and the
    groups it handed on; the partial ones give the fold."""
    store, catalog = tpch_store
    _, res = _run(store, catalog, _q18(THRESHOLD))
    pipes = {p.pid: p for p in res.stats.pipelines}
    sub = [p for p in pipes.values()
           if p.ops == ("partial_agg", "scan_table")]
    assert len(sub) == 1
    n_orders = len(np.unique(tpch_tables["lineitem"]["l_orderkey"]))
    assert sub[0].counters["agg_rows_in"] == catalog.table("lineitem").rows
    # fragments hold whole orders, so no group is split across them
    assert sub[0].counters["agg_groups_out"] == n_orders
    merge = [p for p in pipes.values()
             if "merge_agg" in p.ops and "filter" in p.ops]
    assert len(merge) == 1
    assert merge[0].counters["agg_rows_in"] == n_orders
    assert merge[0].counters["agg_groups_out"] == 13
    joins = [p for p in pipes.values()
             if "join" in p.ops and "partial_agg" not in p.ops
             and "merge_agg" not in p.ops]
    assert joins and all(p.counters["agg_rows_in"] == 0 for p in joins)
