"""Barrier-free pipelined execution (incremental exchange manifests).

Covers the PR's invariants end to end:

  * row parity — pipelined mode returns *exactly* the barrier rows for
    every TPC-H query under every shuffle strategy;
  * the partial-manifest protocol (begin / publish / all-submitted gate
    / seal / abort / fresh reset) and its staleness floor;
  * a straggling producer must not gate the consumer's first byte —
    the consumer's sim window opens before the slowest producer ends;
  * result-cache TTL expiry and age/cost-aware capacity eviction;
  * deadline-aware queue ordering (tightest *feasible* deadline first);
  * pilot-scan selectivity probes and EXPLAIN ANALYZE surfacing.
"""

import threading
import time

import numpy as np
import pytest

from repro.api import CoordinatorConfig, connect
from repro.core.engine import QueryEngine
from repro.core.platform import FaasPlatform, FaultPlan
from repro.core.registry import ResultRegistry, partitions_ready
from repro.data.catalog import Catalog, TableMeta
from repro.service.admission import deadline_order
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.storage import ColumnSpec, ObjectStore, write_pax

PLANNER = PlannerConfig(bytes_per_worker=250_000,
                        broadcast_threshold_bytes=150_000,
                        exchange_partitions=3)

FACT_SCHEMA = [
    ColumnSpec("f_key", "num", "<i8"),
    ColumnSpec("f_grp", "num", "<i8"),
    ColumnSpec("f_val", "num", "<f8"),
]


def _config(planner, *, pipelined, adaptive=False):
    return CoordinatorConfig(planner=planner, use_result_cache=False,
                             adaptive=adaptive, pipelined=pipelined)


def _run(store, catalog, sql, *, pipelined, planner=PLANNER,
         platform=None, adaptive=False, registry=None):
    cfg = _config(planner, pipelined=pipelined, adaptive=adaptive)
    kwargs = {"platform": platform} if platform is not None \
        else {"quota": 1000}
    with connect(store, catalog, config=cfg, registry=registry,
                 **kwargs) as session:
        res = session.submit(sql).result(timeout=300)
        cols = res.fetch(store)
    return cols, res.stats


def _sorted_rows(cols):
    keys = sorted(cols)
    arrs = [np.asarray(cols[k], np.float64) for k in keys]
    order = np.lexsort(arrs)
    return {k: a[order] for k, a in zip(keys, arrs)}


def _assert_same_rows(a, b, ctx=""):
    sa, sb = _sorted_rows(a), _sorted_rows(b)
    assert sorted(sa) == sorted(sb), ctx
    for k in sa:
        np.testing.assert_allclose(sa[k], sb[k], rtol=1e-9, atol=1e-9,
                                   err_msg=f"{ctx} :: {k}")


DIM_SCHEMA = [
    ColumnSpec("d_key", "num", "<i8"),
    ColumnSpec("d_x", "num", "<i8"),
]
# the binder requires FK→PK joins; register the dim PK
import repro.sql.logical as _logical  # noqa: E402
_logical.PRIMARY_KEYS.setdefault("adim", "d_key")


def _make_fact(rows=4000, n_parts=4, groups=6, dim_rows=50, seed=0):
    rng = np.random.default_rng(seed)
    fact = {
        "f_key": rng.integers(0, dim_rows, rows).astype(np.int64),
        "f_grp": rng.integers(0, groups, rows).astype(np.int64),
        "f_val": np.round(rng.normal(0, 10, rows), 3),
    }
    dim = {
        "d_key": np.arange(dim_rows, dtype=np.int64),
        "d_x": rng.integers(0, 5, dim_rows).astype(np.int64),
    }
    store = ObjectStore(tier="local", seed=seed)
    catalog = Catalog()
    files = []
    for p in range(n_parts):
        sel = slice(p * rows // n_parts, (p + 1) * rows // n_parts)
        key = f"db/afact/part-{p:05d}.spax"
        store.put(key, write_pax({k: v[sel] for k, v in fact.items()},
                                 FACT_SCHEMA))
        files.append(key)
    catalog.add(TableMeta("afact", FACT_SCHEMA, files, rows, 400_000))
    store.put("db/adim/part-00000.spax", write_pax(dim, DIM_SCHEMA))
    catalog.add(TableMeta("adim", DIM_SCHEMA,
                          ["db/adim/part-00000.spax"], dim_rows, 300_000))
    return store, catalog


class _AdmitBeforePublish(ResultRegistry):
    """Publishes a producer's complete entry only once a consumer has
    passed the admission gate of its partial stream. Without it the
    partial path is a race: a producer that publishes before its
    consumer's thread reaches the gate hands the consumer the complete
    entry, and the consumer runs as behind a barrier."""

    def __init__(self, store, consumed):
        super().__init__(store)
        self._admitted = {sem: threading.Event() for sem in consumed}

    def await_source_ready(self, sem_hash, **kw):
        entry = super().await_source_ready(sem_hash, **kw)
        if kw.get("stream", "partial") == "partial" \
                and sem_hash in self._admitted:
            self._admitted[sem_hash].set()
        return entry

    def publish(self, sem_hash, **kw):
        if sem_hash in self._admitted:
            self._admitted[sem_hash].wait(timeout=60)
        super().publish(sem_hash, **kw)


# -- tentpole: pipelined ≡ barrier on TPC-H × every shuffle strategy ----------

@pytest.mark.parametrize("strategy", ["direct", "combining",
                                      "multilevel"])
@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_pipelined_matches_barrier_rows_tpch(tpch_store, qname,
                                             strategy):
    store, catalog = tpch_store
    planner = PlannerConfig(bytes_per_worker=250_000,
                            broadcast_threshold_bytes=150_000,
                            exchange_partitions=3,
                            exchange_strategy=strategy)
    barrier_cols, _ = _run(store, catalog, QUERIES[qname],
                           pipelined=False, planner=planner)
    plan = QueryEngine(store, catalog,
                       config=_config(planner, pipelined=True)
                       ).plan_sql(QUERIES[qname])
    consumed = {plan.pipelines[d].sem_hash
                for p in plan.pipelines.values() for d in p.deps}
    piped_cols, piped_stats = _run(
        store, catalog, QUERIES[qname], pipelined=True, planner=planner,
        registry=_AdmitBeforePublish(store, consumed))
    _assert_same_rows(barrier_cols, piped_cols, f"{qname}/{strategy}")
    # multi-pipeline plans must actually have exercised partial-input
    # admission, not silently fallen back to barrier resolution
    if len(piped_stats.pipelines) > 1:
        assert any(r.pipelined for r in piped_stats.pipelines), qname


# -- partial-manifest protocol ------------------------------------------------

def test_partial_manifest_protocol():
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store)
    key = reg.begin_partial("s1", n_producers=4, prefix="results/s1")
    assert key == reg.partial_key("s1")
    man = reg.partial_manifest("s1")
    assert man["n_producers"] == 4 and man["done"] == {}

    # half the fleet lands: the 0.5 admission gate only opens once the
    # whole fleet is *submitted* (deadlock-freedom), then stays open
    reg.publish_partial("s1", 0, {"rows": 10})
    reg.publish_partial("s1", 1, {"rows": 12})
    assert not partitions_ready(reg.partial_manifest("s1"), 0.5)
    reg.mark_all_submitted("s1", 4)
    assert partitions_ready(reg.partial_manifest("s1"), 0.5)
    assert not partitions_ready(reg.partial_manifest("s1"), 0.9)

    # a reassignment split grows the fleet past the plan
    reg.publish_partial("s1", 2, {"rows": 9})
    reg.publish_partial("s1", 3, {"rows": 9})
    reg.publish_partial("s1", 4, {"rows": 1}, n_producers=5)
    reg.finish_partial("s1", n_producers=5)
    man = reg.partial_manifest("s1")
    assert man["complete"] and man["n_producers"] == 5
    assert partitions_ready(man, 1.0)


def test_admission_fraction_cost_model():
    from repro.core.cost import CostModel
    # no observations → the seed's 0.5 constant
    assert CostModel.pipeline_admission_fraction([]) == 0.5
    # uniform fleet: the k-statistic is the same instant for every k,
    # so late admission avoids pure top-up overhead
    assert CostModel.pipeline_admission_fraction([1.0, 1.0, 1.0, 1.0]) == 1.0
    # one straggler: admit at 3/4 and overlap its tail
    assert CostModel.pipeline_admission_fraction([1.0, 1.0, 1.0, 5.0]) == 0.75


def test_partitions_ready_auto_fraction_from_wall_s():
    """fraction=None derives the gate from landed producer wall clocks;
    without wall_s observations it falls back to the 0.5 constant."""
    from repro.core.cost import CostModel
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store)
    reg.begin_partial("s2", n_producers=4, prefix="results/s2")
    reg.mark_all_submitted("s2", 4)
    reg.publish_partial("s2", 0, {"rows": 1, "wall_s": 1.0})
    reg.publish_partial("s2", 1, {"rows": 1, "wall_s": 1.0})
    # uniform walls so far → model wants the full fleet
    assert not partitions_ready(reg.partial_manifest("s2"), None,
                                cost_model=CostModel)
    reg.publish_partial("s2", 2, {"rows": 1, "wall_s": 1.0})
    reg.publish_partial("s2", 3, {"rows": 1, "wall_s": 1.0})
    assert partitions_ready(reg.partial_manifest("s2"), None,
                            cost_model=CostModel)

    reg.begin_partial("s3", n_producers=4, prefix="results/s3")
    reg.mark_all_submitted("s3", 4)
    reg.publish_partial("s3", 0, {"rows": 1})
    reg.publish_partial("s3", 1, {"rows": 1})
    assert partitions_ready(reg.partial_manifest("s3"), None,
                            cost_model=CostModel)


def test_topup_read_cost_from_manifest_info():
    """Top-up ordering reads per-partition byte costs off the partial
    manifest; absent or malformed info prices as zero (read last)."""
    from repro.exec.fragment import _read_cost
    assert _read_cost({"bytes": 512}) == 512
    assert _read_cost({"rows": 9}) == 0
    assert _read_cost(None) == 0
    assert _read_cost("junk") == 0


def test_begin_partial_resets_aborted_stream():
    """A re-claimant of a failed execution must not inherit the dead
    owner's poison flag — begin_partial writes the stream fresh, only
    the version survives."""
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store)
    reg.begin_partial("s2", n_producers=3, prefix="results/s2")
    reg.publish_partial("s2", 0, {"rows": 5})
    reg.abort_partial("s2")
    assert reg.partial_manifest("s2")["aborted"]
    v = reg.partial_manifest("s2")["version"]

    reg.begin_partial("s2", n_producers=2, prefix="results/s2")
    man = reg.partial_manifest("s2")
    assert not man["aborted"] and man["done"] == {}
    assert man["n_producers"] == 2 and man["version"] == v + 1


def test_await_source_ready_rejects_stale_complete_entry():
    """The freshness floor: a complete entry published by an *earlier*
    query (possibly under a different fleet layout) is ignored when the
    producer is re-executing — the live partial stream decides."""
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store)
    reg.register("s3", prefix="results/s3", n_fragments=8,
                 partitioning={"kind": "single"}, schema=[])
    floor = time.time()

    # without a floor the stale entry is returned immediately
    assert reg.await_source_ready(
        "s3", fraction=0.5, timeout_s=0.2)["n_fragments"] == 8

    # with the floor it is not: the fresh partial stream gates instead
    reg.begin_partial("s3", n_producers=2, prefix="results/s3")
    with pytest.raises(TimeoutError):
        reg.await_source_ready("s3", fraction=0.5, timeout_s=0.2,
                               min_published_at=floor)
    reg.publish_partial("s3", 0, {"rows": 3})
    reg.mark_all_submitted("s3", 2)
    assert reg.await_source_ready("s3", fraction=0.5, timeout_s=0.2,
                                  min_published_at=floor) is None

    # re-publish (the re-execution's barrier entry) passes the floor
    reg.register("s3", prefix="results/s3", n_fragments=2,
                 partitioning={"kind": "single"}, schema=[])
    entry = reg.await_source_ready("s3", fraction=0.5, timeout_s=0.2,
                                   min_published_at=floor)
    assert entry["n_fragments"] == 2


def test_aborted_stream_raises_for_waiters():
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store)
    reg.begin_partial("s4", n_producers=2, prefix="results/s4")
    reg.abort_partial("s4")
    with pytest.raises(RuntimeError):
        reg.await_source_ready("s4", fraction=0.5, timeout_s=0.2)


# -- straggler: slowest producer must not gate consumer first byte ------------

def test_straggler_does_not_gate_consumer_start():
    store, catalog = _make_fact()
    sql = ("select f_grp, sum(f_val) as s from afact "
           "group by f_grp order by f_grp")
    planner = PlannerConfig(bytes_per_worker=80_000,
                            broadcast_threshold_bytes=150_000,
                            exchange_partitions=3)
    # fragment 0 of the scan fleet straggles ×50 in sim time; straggler
    # re-triggering is defeated by straggling every attempt of it
    faults = FaultPlan(straggle_fragments=tuple(
        (0, 0, a) for a in range(0, 300)), straggler_factor=50.0)

    b_cols, b_stats = _run(store, catalog, sql, pipelined=False,
                           planner=planner,
                           platform=FaasPlatform(seed=0, faults=faults))
    p_cols, p_stats = _run(store, catalog, sql, pipelined=True,
                           planner=planner,
                           platform=FaasPlatform(seed=0, faults=faults))
    _assert_same_rows(b_cols, p_cols, "straggler")

    producers = {r.pid: r for r in p_stats.pipelines}
    consumers = [r for r in p_stats.pipelines if r.pipelined]
    assert consumers, "no pipeline consumed partial input"
    scan = producers[0]
    for c in consumers:
        # first byte strictly before the straggler-dominated finish
        assert c.sim_start_s < scan.sim_end_s, (c.pid, c.sim_start_s,
                                                scan.sim_end_s)
    # overlapping the straggler tail beats the barrier (stage-serial)
    # schedule of the *same* observed runtimes — cross-run latencies
    # are not comparable (each platform draws its own start jitter)
    serial = sum(r.sim_s for r in p_stats.pipelines if not r.cache_hit)
    assert p_stats.sim_latency_s < serial


# -- result cache: TTL + age/cost-aware eviction ------------------------------

def _entry(reg, sem, cents):
    reg.register(sem, prefix=f"results/{sem}", n_fragments=1,
                 partitioning={"kind": "single"}, schema=[],
                 cost_cents=cents)


def test_result_cache_ttl_expiry():
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store, result_ttl_s=0.05)
    _entry(reg, "t1", 1.0)
    assert reg.lookup("t1") is not None
    time.sleep(0.08)
    assert reg.lookup("t1") is None          # lazily expired
    assert reg.evictions == 1


def test_result_cache_capacity_eviction_prefers_cheap_old():
    store = ObjectStore(tier="local", seed=0)
    reg = ResultRegistry(store, max_entries=2)
    _entry(reg, "old-cheap", 0.001)
    time.sleep(0.02)
    _entry(reg, "old-costly", 100.0)
    time.sleep(0.02)
    _entry(reg, "new", 0.001)                # capacity hit: one evicted
    assert reg.lookup("old-cheap") is None   # lowest cost/age score
    assert reg.lookup("old-costly") is not None
    assert reg.lookup("new") is not None
    assert reg.evictions == 1


# -- deadline-aware queue ordering --------------------------------------------

class _Q:
    def __init__(self, rid, tenant, deadline_s, submitted_at):
        self.request_id = rid
        self.tenant = tenant
        self.deadline_s = deadline_s
        self.submitted_at = submitted_at


def test_deadline_order_feasible_first():
    est = {"fast": 1.0, "slow": 50.0}.get
    qs = [
        _Q("a", "fast", None, 0.0),      # FIFO band
        _Q("b", "fast", 10.0, 1.0),      # feasible, loose deadline
        _Q("c", "slow", 5.0, 2.0),       # infeasible: est 50 > 5
        _Q("d", "fast", 2.0, 3.0),       # feasible, tightest deadline
        _Q("e", "new", 4.0, 4.0),        # no estimate → optimistic
        _Q("f", None, None, 0.5),        # FIFO band, older than nothing
    ]
    got = [q.request_id for q in deadline_order(qs, est)]
    # tightest feasible deadlines, then FIFO no-deadline, then infeasible
    assert got == ["d", "e", "b", "a", "f", "c"]


def test_deadline_order_infeasible_never_displaces():
    est = lambda t: 100.0   # noqa: E731 - everything infeasible
    qs = [_Q("x", "t", 1.0, 0.0), _Q("y", "t", None, 1.0)]
    got = [q.request_id for q in deadline_order(qs, est)]
    assert got == ["y", "x"]    # the lost SLO yields to the FIFO band


# -- pilot scan + EXPLAIN ANALYZE ---------------------------------------------

def test_pilot_scan_calibrates_selectivity():
    """An *uncalibrated* filter→scan fleet is preceded by a one-unit
    probe whose observed selectivity corrects the stage's row estimate
    and lands in the calibration store — so the second run of the same
    filter signature probes nothing."""
    store, catalog = _make_fact(rows=8000, n_parts=8)
    # join probe side: a pure filter→scan pipeline (a grouped-agg scan
    # pipeline measures post-aggregation rows, so it is never probed)
    sql = ("select d_x, count(*) as n from afact, adim "
           "where f_key = d_key and f_val > 25 group by d_x order by d_x")
    cfg = CoordinatorConfig(planner=PlannerConfig(
        bytes_per_worker=40_000, broadcast_threshold_bytes=1,
        exchange_partitions=3), use_result_cache=False, adaptive=True,
        pipelined=True)
    with connect(store, catalog, config=cfg, quota=1000) as session:
        res = session.submit(sql).result(timeout=300)
        pilots = [a for p in res.stats.pipelines for a in p.adaptations
                  if a["kind"] == "pilot_scan"]
        assert pilots and 0.0 <= pilots[0]["selectivity"] <= 1.0
        assert pilots[0]["unit_rows"] > 0
        # calibrated now: the repeat run must skip the probe
        res2 = session.submit(sql).result(timeout=300)
        again = [a for p in res2.stats.pipelines for a in p.adaptations
                 if a["kind"] == "pilot_scan"]
        assert not again


def test_explain_analyze_shows_pipelined_window(tpch_store):
    store, catalog = tpch_store
    cfg = CoordinatorConfig(planner=PLANNER, use_result_cache=False,
                            pipelined=True)
    with connect(store, catalog, config=cfg, quota=1000) as session:
        text = session.submit(QUERIES["q3"]).explain_analyze(timeout=300)
    assert "pipelined: window" in text
    assert "pilot-K" in text
