"""Kernel dispatch layer: pattern match, kernel/jnp parity, clean fallback."""

import numpy as np
import pytest

from repro.api import connect
from repro.core import CoordinatorConfig, FaasPlatform, QueryCoordinator
from repro.exec import lower
from repro.exec.batch import from_numpy
from repro.exec.fragment import _build, fn_cache_stats
from repro.sql import oracle
from repro.sql.logical import Binder
from repro.sql.parser import parse
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.sql.rules import optimize

CFG = CoordinatorConfig(planner=PlannerConfig(
    bytes_per_worker=250_000, broadcast_threshold_bytes=150_000,
    exchange_partitions=3), use_result_cache=False)


def _plan(store, catalog, sql, cfg=CFG):
    coord = QueryCoordinator(store, catalog,
                             platform=FaasPlatform(seed=1), config=cfg)
    return coord.plan_sql(sql)


def _scan_pipeline(plan):
    return next(p for p in plan.pipelines.values() if p.scan_units)


def _oracle(catalog, tables, sql):
    lqp, _ = Binder(catalog).bind(parse(sql))
    return oracle.run(optimize(lqp), tables)


# -- pattern matching ---------------------------------------------------------

def test_q6_matches_filter_agg(tpch_store):
    store, catalog = tpch_store
    p = _scan_pipeline(_plan(store, catalog, QUERIES["q6"]))
    assert p.kernel == "filter_agg"
    assert lower.match_kernel(p.op) == "filter_agg"


def test_q1_matches_groupby_onehot(tpch_store):
    store, catalog = tpch_store
    p = _scan_pipeline(_plan(store, catalog, QUERIES["q1"]))
    assert p.kernel == "groupby_onehot"


def test_q12_matches_join_probe_agg(tpch_store):
    store, catalog = tpch_store
    plan = _plan(store, catalog, QUERIES["q12"])
    p = next(p for p in plan.pipelines.values()
             if p.kernel == "join_probe_agg")
    assert p.kernel_miss_reason is None
    assert p.kernel_roofline["resident_rows"] >= 128


def test_grouped_minmax_matches_segmented_kernel(tpch_store):
    store, catalog = tpch_store
    sql = ("select l_returnflag, min(l_quantity) as mq, "
           "max(l_tax) as mt from lineitem group by l_returnflag")
    p = _scan_pipeline(_plan(store, catalog, sql))
    assert p.kernel == "segmented_minmax"
    assert lower.lower_fragment(p.op) is not None


_SQL_SORT = ("select l_orderkey, sum(l_quantity) as s, count(*) as c "
             "from lineitem group by l_orderkey")


def test_groupby_nondict_matches_sort_agg(tpch_store):
    store, catalog = tpch_store
    sql = _SQL_SORT
    # On interpreted (non-TPU) backends the compute-bound bitonic sort
    # kernel loses to jnp — dispatch declines with a named reason.
    p = _scan_pipeline(_plan(store, catalog, sql))
    assert p.kernel is None
    assert p.kernel_miss_reason == "interpret_cost"
    with lower.interpret_gate_disabled():
        p = _scan_pipeline(_plan(store, catalog, sql))
        assert p.kernel == "sort_agg"    # no dict sizes → sort strategy


def test_q3_final_matches_topk(tpch_store):
    store, catalog = tpch_store
    plan = _plan(store, catalog, QUERIES["q3"])
    p = next(p for p in plan.pipelines.values() if p.op["t"] == "final")
    assert p.kernel is None
    assert p.kernel_miss_reason == "interpret_cost"
    with lower.interpret_gate_disabled():
        plan = _plan(store, catalog, QUERIES["q3"])
        p = next(p for p in plan.pipelines.values()
                 if p.op["t"] == "final")
        assert p.kernel == "topk"
        m, miss = lower.match_fragment_ex(p.op)
    assert miss is None and m.limit == 10
    assert m.sort_keys and m.sort_keys[0][1]     # revenue desc


def test_miss_reasons_name_the_blocker(tpch_store):
    store, catalog = tpch_store
    plan = _plan(store, catalog,
                 "select l_orderkey, l_quantity from lineitem "
                 "where l_quantity < 3")
    reasons = [p.kernel_miss_reason for p in plan.pipelines.values()]
    assert all(p.kernel is None for p in plan.pipelines.values())
    assert any("no fusible root" in r for r in reasons if r)
    assert lower.kernel_miss_reason(
        {"t": "final", "sort_keys": [], "limit": None,
         "child": {"t": "scan_exchange"}}) == \
        "final lacks ORDER BY + LIMIT (no top-k)"


def test_disabled_scope_skips_annotation_and_lowering(tpch_store):
    store, catalog = tpch_store
    with lower.disabled():
        p = _scan_pipeline(_plan(store, catalog, QUERIES["q6"]))
        assert p.kernel is None
    assert lower.enabled()


@pytest.mark.parametrize("sql,final", [(_SQL_SORT, False),
                                       (QUERIES["q3"], True)])
def test_resident_kernel_runs_only_within_its_cap(sql, final, tpch_store):
    """Past the resident cap a sort kernel's program takes its XLA
    fallback, and the fragment must not report the kernel."""
    store, catalog = tpch_store
    with lower.interpret_gate_disabled():
        plan = _plan(store, catalog, sql)
        op = next(p.op for p in plan.pipelines.values()
                  if (p.op["t"] == "final") == final and p.kernel)
        lowered = lower.lower_fragment(op)
    cap = lowered.tiling.resident_rows
    assert lowered.runs_kernel({"in0": cap})
    assert not lowered.runs_kernel({"in0": 2 * cap})
    assert not lowered.runs_kernel({"in0": 3 * 1024})   # not a power of 2


def test_engine_reports_kernels_that_ran_and_how(tpch_store):
    store, catalog = tpch_store
    with connect(store, catalog, config=CFG) as session:
        res = session.sql(QUERIES["q6"])
    p = next(p for p in res.stats.pipelines if p.kernel)
    assert p.kernel_runs == {"filter_agg": p.kernel_fragments}
    # no TPU here: every kernel that ran was interpreted
    assert p.kernel_interpreted == p.kernel_fragments > 0
    assert all(not q.kernel_runs for q in res.stats.pipelines if q is not p)


# -- block-level parity across capacity buckets -------------------------------

_SQLS = {
    "q6": QUERIES["q6"],                           # filter_agg
    "q1": QUERIES["q1"],                           # groupby_onehot
    "minmax": ("select l_returnflag, min(l_quantity) as mq, "
               "max(l_tax) as mt from lineitem "
               "where l_quantity < 30 group by l_returnflag"),
    "sortagg": ("select l_orderkey, sum(l_quantity) as s, "
                "count(*) as c, min(l_extendedprice) as m "
                "from lineitem group by l_orderkey"),
}


@pytest.mark.parametrize("qname,n_rows", [
    ("q6", 900), ("q6", 3000), ("q6", 12000),     # caps 1024/4096/16384
    ("q1", 900), ("q1", 3000), ("q1", 12000),
    ("minmax", 900), ("minmax", 3000), ("minmax", 12000),
    ("sortagg", 900), ("sortagg", 3000), ("sortagg", 12000),
])
def test_lowered_matches_generic_per_capacity(qname, n_rows, tpch_store,
                                              tpch_tables):
    store, catalog = tpch_store
    with lower.interpret_gate_disabled():
        p = _scan_pipeline(_plan(store, catalog, _SQLS[qname]))
        lowered = lower.lower_fragment(p.op)
    assert lowered is not None and lowered.kernel == p.kernel
    leaves: list = []
    generic = _build(p.op, leaves)
    (leaf_id, leaf_op), = lowered.leaves
    assert leaves[0][1] is leaf_op

    li = tpch_tables["lineitem"]
    cols = {c: li[c][:n_rows] for c in leaf_op["columns"]}
    blk = from_numpy(cols)
    blocks = {leaf_id: (blk.columns, blk.mask)}

    k_cols, k_mask = lowered.fn(blocks)
    g_cols, g_mask = generic(blocks)
    assert set(k_cols) == set(g_cols)
    np.testing.assert_array_equal(np.asarray(k_mask), np.asarray(g_mask))
    for name in g_cols:
        np.testing.assert_allclose(
            np.asarray(k_cols[name], np.float64),
            np.asarray(g_cols[name], np.float64),
            rtol=1e-12, atol=1e-12, err_msg=f"{qname}.{name}@{n_rows}")


@pytest.mark.parametrize("n_probe", [900, 3000, 12000])
def test_join_probe_block_parity(n_probe, tpch_store, tpch_tables):
    """Fused join-probe+agg vs the generic jnp join chain, two leaves,
    swept across probe capacity buckets."""
    store, catalog = tpch_store
    plan = _plan(store, catalog, QUERIES["q12"])
    p = next(p for p in plan.pipelines.values()
             if p.kernel == "join_probe_agg")
    lowered = lower.lower_fragment(p.op)
    g_leaves: list = []
    generic = _build(p.op, g_leaves)

    jop = p.op
    while jop["t"] != "join":
        jop = jop["child"] if "child" in jop else jop["probe"]
    li, orders = tpch_tables["lineitem"], tpch_tables["orders"]
    build_names = [jop["build_key"]] + [c for c in jop["payload"]
                                        if c in orders]

    def leaf_block(leaf_op):
        if leaf_op["t"] == "scan_table":
            cols = {c: li[c][:n_probe] for c in leaf_op["columns"]}
        else:                       # build-side exchange scan
            cols = {c: orders[c][:1500] for c in build_names}
        return from_numpy(cols)

    k_blocks, g_blocks = {}, {}
    for leaf_id, leaf_op in lowered.leaves:
        blk = leaf_block(leaf_op)
        k_blocks[leaf_id] = (blk.columns, blk.mask)
        gid = next(i for i, op in g_leaves if op is leaf_op)
        g_blocks[gid] = (blk.columns, blk.mask)

    k_cols, k_mask = lowered.fn(k_blocks)
    g_cols, g_mask = generic(g_blocks)
    assert set(k_cols) == set(g_cols)
    np.testing.assert_array_equal(np.asarray(k_mask), np.asarray(g_mask))
    for name in g_cols:
        np.testing.assert_allclose(
            np.asarray(k_cols[name], np.float64),
            np.asarray(g_cols[name], np.float64),
            rtol=1e-12, atol=1e-12, err_msg=f"q12.{name}@{n_probe}")


@pytest.mark.parametrize("n_rows", [900, 3000, 12000])
def test_topk_block_parity(n_rows, tpch_store):
    """Fused top-k vs generic passthrough + host sort/limit: after the
    coordinator's final-stage host ops both paths must agree exactly."""
    store, catalog = tpch_store
    with lower.interpret_gate_disabled():
        plan = _plan(store, catalog, QUERIES["q3"])
        p = next(q for q in plan.pipelines.values()
                 if q.op["t"] == "final")
        assert p.kernel == "topk"
        m, _ = lower.match_fragment_ex(p.op)
        lowered = lower.lower_fragment(p.op)
    g_leaves: list = []
    generic = _build(p.op["child"], g_leaves)

    rng = np.random.default_rng(7)
    cols = {name: rng.integers(0, 50, n_rows).astype(np.float64)
            if desc else rng.integers(0, 50, n_rows)
            for name, desc in m.sort_keys}
    cols["carry"] = rng.integers(0, 10_000, n_rows)
    blk = from_numpy(cols)
    blocks = {"in0": (blk.columns, blk.mask)}

    def host_final(out_cols, out_mask):
        keep = np.asarray(out_mask)
        named = {c: np.asarray(v)[keep] for c, v in out_cols.items()}
        order = np.lexsort([-named[k] if desc else named[k]
                            for k, desc in reversed(m.sort_keys)])
        return {c: v[order][:m.limit] for c, v in named.items()}

    k_out = host_final(*lowered.fn(blocks))
    g_out = host_final(*generic(blocks))
    assert set(k_out) == set(g_out)
    for name in g_out:
        np.testing.assert_array_equal(k_out[name], g_out[name],
                                      err_msg=f"topk.{name}@{n_rows}")


# -- roofline-driven tiling ---------------------------------------------------

def test_roofline_tilings_are_pow2_and_fit_budget():
    from repro.analysis import roofline
    budget = roofline.vmem_budget_bytes()
    grid = [
        roofline.filter_agg_tiling(n_cols=6, n_aggs=2),
        roofline.groupby_tiling("groupby_onehot", n_cols=8, n_aggs=4,
                                n_groups=12),
        roofline.groupby_tiling("segmented_minmax", n_cols=4, n_aggs=2,
                                n_groups=6),
        roofline.join_probe_tiling(n_cols=7, n_payload=2, n_aggs=3,
                                   n_groups=14),
    ]
    resident = [
        roofline.resident_sort_tiling("sort_agg", n_arrays=6),
        roofline.resident_sort_tiling("topk", n_arrays=5),
    ]
    for t in grid:
        assert 128 <= t.block_rows <= 8192, t
    for t in resident:
        # fully-resident kernels: capacity cap IS the block
        assert t.block_rows == t.resident_rows, t
        assert t.vmem_bytes <= budget, t
    for t in grid + resident:
        assert t.block_rows & (t.block_rows - 1) == 0, t
        assert t.resident_rows & (t.resident_rows - 1) == 0, t
        assert t.vmem_bytes <= 2 * budget, t
        assert t.dominant in ("compute", "memory")
        assert t.key == (t.kernel, t.block_rows, t.resident_rows)
    # deterministic: same shape → identical tiling (cache keys depend on it)
    again = roofline.filter_agg_tiling(n_cols=6, n_aggs=2)
    assert again == grid[0]
    # the one-hot group cap is the roofline's, not a hand constant
    cap = roofline.onehot_group_capacity(1)
    op = {"t": "partial_agg", "strategy": "direct", "group_cols": ["g"],
          "sizes": [2 * cap],
          "aggs": [["s", "sum", {"t": "col", "name": "v"}]],
          "child": {"t": "scan_table", "table": "t", "columns": ["g", "v"],
                    "zone_preds": []}}
    assert lower.match_fragment(op) is None
    assert str(cap) in lower.kernel_miss_reason(op)


def test_hardware_table_is_keyed_by_device_kind(monkeypatch):
    from repro.launch import mesh

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(mesh.jax, "devices", lambda: [Dev()])
    assert mesh.target_hardware() is mesh.HARDWARE["TPU v5 lite"]
    Dev.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        mesh.target_hardware()
    # without a TPU the kernels are tiled for the chip they model
    Dev.platform = "cpu"
    assert mesh.target_hardware() is mesh.HARDWARE[mesh.MODELED_KIND]


def test_tiling_joins_compiled_cache_key(tpch_store):
    store, catalog = tpch_store
    p = _scan_pipeline(_plan(store, catalog, QUERIES["q6"]))
    kernel, tkey, _ = lower.dispatch_signature(p.op)
    lowered = lower.lower_fragment(p.op)
    assert kernel == "filter_agg" == lowered.kernel
    assert tkey == lowered.tiling.key
    assert p.kernel_roofline["block_rows"] == lowered.tiling.block_rows


# -- end-to-end engine parity -------------------------------------------------

@pytest.mark.parametrize("qname", ["q1", "q6", "q3"])
def test_engine_kernel_path_matches_jnp_and_oracle(qname, tpch_store,
                                                   tpch_tables):
    store, catalog = tpch_store
    # gate bypass: q3's only fused op is the top-k final, which the
    # interpret-cost gate declines on CPU — parity still needs to run it
    with connect(store, catalog, config=CFG) as session, \
            lower.interpret_gate_disabled():
        fused = session.sql(QUERIES[qname])
        scan = next(p for p in fused.stats.pipelines
                    if p.kernel)
        assert scan.kernel_fragments == scan.n_fragments
        got_fused = fused.fetch(store)
        with lower.disabled():
            got_jnp = session.sql(QUERIES[qname]).fetch(store)
    want = _oracle(catalog, tpch_tables, QUERIES[qname])
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got_fused[k], np.float64),
            np.asarray(want[k], np.float64), rtol=1e-9, atol=1e-9,
            err_msg=f"{qname}.{k} (fused vs oracle)")
        np.testing.assert_allclose(
            np.asarray(got_fused[k], np.float64),
            np.asarray(got_jnp[k], np.float64), rtol=1e-12, atol=1e-12,
            err_msg=f"{qname}.{k} (fused vs jnp)")


@pytest.mark.parametrize("qname", ["q12", "q14", "q19"])
def test_join_queries_run_on_fused_kernels(qname, tpch_store, tpch_tables):
    """TPC-H joins beyond Q1/Q6 now execute fused fragments, and the
    fused path agrees with the jnp fallback and the oracle."""
    store, catalog = tpch_store
    with connect(store, catalog, config=CFG) as session:
        res = session.sql(QUERIES[qname])
        assert sum(p.kernel_fragments for p in res.stats.pipelines) > 0
        got = res.fetch(store)
        with lower.disabled():
            got_jnp = session.sql(QUERIES[qname]).fetch(store)
    want = _oracle(catalog, tpch_tables, QUERIES[qname])
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64),
            np.asarray(want[k], np.float64), rtol=1e-9, atol=1e-9,
            err_msg=f"{qname}.{k} (fused vs oracle)")
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64),
            np.asarray(got_jnp[k], np.float64), rtol=1e-9, atol=1e-9,
            err_msg=f"{qname}.{k} (fused vs jnp)")


def test_unmatched_plan_falls_back_cleanly(tpch_store, tpch_tables):
    sql = ("select l_orderkey, l_quantity from lineitem "
           "where l_quantity < 3")
    store, catalog = tpch_store
    with connect(store, catalog, config=CFG) as session:
        res = session.sql(sql)
        assert all(p.kernel_fragments == 0 for p in res.stats.pipelines)
        got = res.fetch(store)
    want = _oracle(catalog, tpch_tables, sql)
    order_g = np.lexsort((got["l_quantity"], got["l_orderkey"]))
    order_w = np.lexsort((want["l_quantity"], want["l_orderkey"]))
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64)[order_g],
            np.asarray(want[k], np.float64)[order_w])


def test_compiled_program_cache_shared_across_queries(tpch_store):
    store, catalog = tpch_store
    with connect(store, catalog, config=CFG) as session:
        session.sql(QUERIES["q6"])
        before = fn_cache_stats()
        session.sql(QUERIES["q6"])          # same plan → cached programs
        after = fn_cache_stats()
    assert after["hits"] > before["hits"]
    assert after["misses"] == before["misses"]
    assert after["entries"] == before["entries"]
