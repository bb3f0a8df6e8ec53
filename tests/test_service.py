"""Query service tier: durable request ledger (CAS transitions, lease
expiry, crash recovery with exactly-one fleet execution), weighted
fair-share admission with cost budgets, multi-query DAGs with shared
subplan dedup, the store-level watch primitive, and SLO deadline →
fleet-sizing plumbing."""

import threading
import time

import numpy as np
import pytest

from repro.api import CoordinatorConfig, FaasPlatform, connect
from repro.core.cost import CostModel
from repro.core.engine import QueryCancelled, QueryEngine
from repro.core.platform import AdmissionController
from repro.data import generate_tpch
from repro.service import (FairShareAdmission, LedgerConflict, QueryService,
                           RequestFailed, RequestLedger, RequestStatus,
                           ServiceHandle, TenantConfig, topological_order,
                           validate_dag)
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.storage import FilesystemBackend, ObjectStore

CFG = CoordinatorConfig(planner=PlannerConfig(
    bytes_per_worker=250_000, broadcast_threshold_bytes=150_000,
    exchange_partitions=3))


def _fresh_db(seed=0, tier="local", n_parts=4):
    store = ObjectStore(tier=tier, seed=seed)
    catalog = generate_tpch(store, sf=0.01, n_parts=n_parts, seed=0)
    return store, catalog


def _service(store, catalog, *, tenants=(), quota=16, lease_ttl_s=30.0,
             start=True):
    platform = FaasPlatform(quota=quota, seed=0)
    session = connect(store, catalog, platform=platform, config=CFG,
                      max_concurrent_queries=4)
    svc = QueryService(session, tenants=tuple(tenants),
                       lease_ttl_s=lease_ttl_s, start=start)
    return svc, session


def _solo_invocations(sql):
    """Worker invocations one clean execution of ``sql`` needs."""
    store, catalog = _fresh_db()
    platform = FaasPlatform(quota=16, seed=0)
    with connect(store, catalog, platform=platform, config=CFG,
                 max_concurrent_queries=4) as session:
        session.sql(sql)
    return platform.invocations


# -- store-level watch primitive (satellite) ----------------------------------

def _watch_store(backend, tmp_path):
    """Memory backend (CV notify path) vs filesystem backend (version
    polling with exponential backoff)."""
    if backend == "fs":
        return ObjectStore(FilesystemBackend(str(tmp_path / "store")),
                           tier="local", seed=0)
    return ObjectStore(tier="local", seed=0)


@pytest.mark.parametrize("backend", ["memory", "fs"])
def test_watch_wakes_on_put(backend, tmp_path):
    store = _watch_store(backend, tmp_path)
    store.put("w/k", b"v1")
    token = store.version("w/k")
    woke = []

    def waiter():
        woke.append(store.watch("w/k", token, timeout_s=10.0))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    store.put("w/k", b"v2")
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert woke and woke[0] != token


@pytest.mark.parametrize("backend", ["memory", "fs"])
def test_watch_observes_create_and_delete(backend, tmp_path):
    store = _watch_store(backend, tmp_path)
    assert store.version("w/absent") is None
    store.put("w/absent", b"x")         # creation: None → token
    assert store.watch("w/absent", None, timeout_s=0.5) is not None
    token = store.version("w/absent")
    store.delete("w/absent")            # deletion: token → None
    assert store.watch("w/absent", token, timeout_s=5.0) is None


def test_watch_timeout_returns_unchanged_token():
    store = ObjectStore(tier="local", seed=0)
    store.put("w/t", b"v")
    token = store.version("w/t")
    t0 = time.monotonic()
    assert store.watch("w/t", token, timeout_s=0.1) == token
    assert time.monotonic() - t0 < 2.0


def test_watch_cancel_check_aborts_wait():
    store = ObjectStore(tier="local", seed=0)
    store.put("w/c", b"v")

    class _Stop(Exception):
        pass

    def cancel():
        raise _Stop

    with pytest.raises(_Stop):
        store.watch("w/c", store.version("w/c"), timeout_s=30.0,
                    cancel_check=cancel)


# -- ledger: CAS transitions --------------------------------------------------

def _ledger(lease_ttl_s=30.0):
    return RequestLedger(ObjectStore(tier="local", seed=0),
                         lease_ttl_s=lease_ttl_s)


def test_ledger_lifecycle_and_versioning():
    led = _ledger()
    e = led.submit("select 1", tenant="t", priority=2, deadline_s=9.0)
    assert e.status is RequestStatus.QUEUED and e.version == 1
    got = led.get(e.request_id)
    assert got.sql == "select 1" and got.tenant == "t"
    assert got.priority == 2 and got.deadline_s == 9.0

    claimed = led.claim(e.request_id, "svc-a")
    assert claimed.status is RequestStatus.ADMITTED
    assert claimed.owner == "svc-a" and claimed.version == 2
    assert claimed.lease_expires > time.time()

    run = led.transition(e.request_id, RequestStatus.RUNNING,
                         if_owner="svc-a")
    assert run.version == 3 and run.started_at is not None

    done = led.transition(e.request_id, RequestStatus.SUCCEEDED,
                          if_owner="svc-a", result={"rows": 1})
    assert done.owner is None and done.finished_at is not None
    assert done.result == {"rows": 1}


def test_ledger_rejects_duplicate_stale_foreign_and_illegal():
    led = _ledger()
    led.submit("q", request_id="r1")
    with pytest.raises(LedgerConflict):        # duplicate id
        led.submit("q2", request_id="r1")
    led.claim("r1", "svc-a")
    with pytest.raises(LedgerConflict):        # stale version
        led.transition("r1", RequestStatus.RUNNING, expected_version=1)
    with pytest.raises(LedgerConflict):        # foreign owner
        led.transition("r1", RequestStatus.RUNNING, if_owner="svc-b")
    with pytest.raises(LedgerConflict):        # illegal: ADMITTED→SUCCEEDED
        led.transition("r1", RequestStatus.SUCCEEDED, if_owner="svc-a")
    led.transition("r1", RequestStatus.RUNNING, if_owner="svc-a")
    led.transition("r1", RequestStatus.SUCCEEDED, if_owner="svc-a")
    with pytest.raises(LedgerConflict):        # terminal states are final
        led.transition("r1", RequestStatus.QUEUED)
    assert led.claim("r1", "svc-b") is None    # claim loses, returns None
    with pytest.raises(LedgerConflict):        # unknown request id
        led.transition("ghost", RequestStatus.CANCELLED)


def test_ledger_double_claim_single_winner():
    led = _ledger()
    led.submit("q", request_id="r")
    wins = [led.claim("r", f"svc-{i}") for i in range(4)]
    assert sum(w is not None for w in wins) == 1
    assert led.get("r").owner == "svc-0"


def test_ledger_lease_expiry_requeues_and_bumps_attempt():
    led = _ledger(lease_ttl_s=0.05)
    led.submit("q", request_id="r")
    led.claim("r", "svc-dead")
    assert led.recover_expired() == []          # lease still live
    time.sleep(0.1)
    recovered = led.recover_expired()
    assert [e.request_id for e in recovered] == ["r"]
    e = led.get("r")
    assert e.status is RequestStatus.QUEUED
    assert e.owner is None and e.attempt == 1
    # renew_lease from the dead owner must now fail
    assert not led.renew_lease("r", "svc-dead")
    # a live owner renewing *before* expiry keeps the entry out of
    # recovery
    led.claim("r", "svc-live")
    assert led.renew_lease("r", "svc-live")
    assert led.recover_expired() == []
    # ...but once the lease lapses, even the original owner is fenced:
    # recovery may already have handed the request to a peer, so a late
    # renewal must not resurrect ownership
    time.sleep(0.1)
    assert not led.renew_lease("r", "svc-live")
    assert [e.request_id for e in led.recover_expired()] == ["r"]
    assert led.get("r").owner is None


def test_ledger_entries_filters_and_orders():
    led = _ledger()
    led.submit("a", request_id="ra", tenant="t1")
    led.submit("b", request_id="rb", tenant="t2")
    led.submit("c", request_id="rc", tenant="t1")
    led.claim("rb", "svc")
    assert [e.request_id for e in led.entries()] == ["ra", "rb", "rc"]
    assert [e.request_id for e in led.entries(tenant="t1")] == ["ra", "rc"]
    assert [e.request_id
            for e in led.entries(status=RequestStatus.ADMITTED)] == ["rb"]


def test_ledger_watch_wakes_handle_waiters():
    led = _ledger()
    led.submit("q", request_id="r")
    token = led.version_token("r")
    led.claim("r", "svc")
    assert led.watch("r", token, timeout_s=5.0) != token


# -- service: end-to-end ------------------------------------------------------

def test_service_executes_and_persists_result(tpch_store):
    store, catalog = tpch_store
    svc, session = _service(store, catalog)
    try:
        h = svc.submit(QUERIES["q6"])
        res = h.result(timeout=300)
        cols = res.fetch(store)
        assert len(cols["revenue"]) == 1
        entry = h.entry()
        assert entry.status is RequestStatus.SUCCEEDED
        assert entry.owner is None and entry.finished_at is not None
        assert entry.result["locations"] or entry.result["cache_hits"]
        # the ledger record alone resolves the data (durable handle)
        h2 = ServiceHandle(h.request_id, svc)
        np.testing.assert_allclose(
            h2.fetch(timeout=10)["revenue"], cols["revenue"])
    finally:
        svc.close()
        session.close()


def test_service_records_failure_and_cancel(tpch_store):
    store, catalog = tpch_store
    svc, session = _service(store, catalog)
    try:
        bad = svc.submit("select no_such_col from lineitem")
        with pytest.raises(RequestFailed):
            bad.result(timeout=120)
        assert bad.entry().error

        # a QUEUED request cancels without ever dispatching
        svc.kill()
        queued = svc.submit(QUERIES["q1"])
        assert queued.cancel()
        with pytest.raises(QueryCancelled):
            queued.result(timeout=10)
    finally:
        svc.close()
        session.close()


# -- service: crash recovery (tentpole acceptance) ----------------------------

@pytest.mark.parametrize("die_at", [RequestStatus.ADMITTED,
                                    RequestStatus.RUNNING])
def test_recovery_of_orphaned_entry_runs_fleet_exactly_once(die_at):
    """An owner that died right after reaching ``die_at`` (before any
    worker ran) leaves an orphan; a fresh service must re-queue it on
    lease expiry and execute it with exactly one fleet's invocations."""
    solo = _solo_invocations(QUERIES["q6"])
    store, catalog = _fresh_db()
    ledger = RequestLedger(store, lease_ttl_s=0.2)
    ledger.submit(QUERIES["q6"], request_id="r")
    ledger.claim("r", "svc-dead")
    if die_at is RequestStatus.RUNNING:
        ledger.transition("r", RequestStatus.RUNNING, if_owner="svc-dead")
    assert ledger.get("r").status is die_at
    time.sleep(0.25)                   # owner never renews: lease expires

    platform = FaasPlatform(quota=16, seed=0)
    session = connect(store, catalog, platform=platform, config=CFG,
                      max_concurrent_queries=4)
    svc = QueryService(session, ledger=ledger, lease_ttl_s=0.2)
    try:
        h = ServiceHandle("r", svc)
        entry = h.wait(timeout=120)
        assert entry.status is RequestStatus.SUCCEEDED
        assert entry.attempt == 1      # the re-queue was recorded
        assert svc.recovered_requests >= 1
        assert platform.invocations == solo    # exactly one execution
        assert len(h.fetch(timeout=30)["revenue"]) == 1
    finally:
        svc.close()
        session.close()


def test_crash_mid_running_second_instance_no_duplicate_fleet():
    """Kill the owning service while its query is RUNNING. The engine's
    published pipeline results make recovery duplicate-free: the second
    instance's re-run is pure cache — the platform sees exactly one
    fleet's worth of invocations across both instances."""
    solo = _solo_invocations(QUERIES["q6"])
    store, catalog = _fresh_db()
    ledger = RequestLedger(store, lease_ttl_s=0.3)
    platform = FaasPlatform(quota=16, seed=0)
    s1 = connect(store, catalog, platform=platform, config=CFG,
                 max_concurrent_queries=4)
    svc1 = QueryService(s1, ledger=ledger, lease_ttl_s=0.3)
    h = svc1.submit(QUERIES["q6"])
    deadline = time.monotonic() + 60
    while h.status() is not RequestStatus.RUNNING \
            and not h.status().terminal and time.monotonic() < deadline:
        time.sleep(0.002)
    pre_kill = h.status()
    svc1.kill()        # process death: no terminal record, lease orphaned
    s1.drain()         # the handed-off engine still finishes + publishes
    time.sleep(0.4)    # ... while the ledger lease quietly expires

    s2 = connect(store, catalog, platform=platform, config=CFG,
                 max_concurrent_queries=4)
    svc2 = QueryService(s2, ledger=ledger, lease_ttl_s=0.3)
    try:
        assert pre_kill is RequestStatus.RUNNING
        entry = h.wait(timeout=120)
        assert entry.status is RequestStatus.SUCCEEDED
        assert entry.owner is None
        assert platform.invocations == solo    # zero duplicate fleet work
        assert s2.registry.claims == 0         # re-run was pure cache
        cols = ServiceHandle(h.request_id, svc2).fetch(timeout=30)
        assert len(cols["revenue"]) == 1
    finally:
        svc2.close()
        s2.close()
        s1.close()


def test_restarted_service_resumes_queued_backlog():
    """A service that dies with QUEUED work leaves a durable backlog a
    fresh instance over the same ledger picks up unprompted."""
    store, catalog = _fresh_db()
    ledger = RequestLedger(store, lease_ttl_s=0.3)
    platform = FaasPlatform(quota=16, seed=0)
    s1 = connect(store, catalog, platform=platform, config=CFG)
    svc1 = QueryService(s1, ledger=ledger, start=False)   # never dispatches
    h = svc1.submit(QUERIES["q6"])
    assert h.status() is RequestStatus.QUEUED
    s1.close()

    s2 = connect(store, catalog, platform=platform, config=CFG,
                 max_concurrent_queries=4)
    svc2 = QueryService(s2, ledger=ledger, lease_ttl_s=0.3)
    try:
        entry = ServiceHandle(h.request_id, svc2).wait(timeout=120)
        assert entry.status is RequestStatus.SUCCEEDED
    finally:
        svc2.close()
        s2.close()
        platform.close()


# -- fair share (tentpole acceptance) -----------------------------------------

def test_fair_share_converges_to_weight_ratio():
    """Two groups flooding an 8-slot quota at weights 3:1 — admitted
    slots converge to the weight ratio within ±20%."""
    adm = AdmissionController(8, shares={"gold": 3.0, "bronze": 1.0})
    stop = threading.Event()

    def flood(group):
        while not stop.is_set():
            got = adm.acquire(1, group=group)
            time.sleep(0.001)
            adm.release(got)

    threads = [threading.Thread(target=flood, args=(g,))
               for g in ("gold", "bronze") for _ in range(8)]
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join()
    admitted = adm.admitted_by_group
    assert admitted["gold"] > 0 and admitted["bronze"] > 0
    ratio = admitted["gold"] / admitted["bronze"]
    assert 3.0 * 0.8 <= ratio <= 3.0 * 1.2, admitted


def test_fair_share_unweighted_waiters_keep_priority_order():
    """Waiters without a registered share fall back to priority+aging
    ordering — the pre-service scheduler is unchanged."""
    adm = AdmissionController(1, shares={"g": 2.0})
    hold = adm.acquire(1)
    order = []
    lock = threading.Lock()

    def take(tag, prio):
        got = adm.acquire(1, priority=prio)
        with lock:
            order.append(tag)
        adm.release(got)

    threads = []
    for tag, prio in (("low", 0), ("high", 5)):
        t = threading.Thread(target=take, args=(tag, prio))
        t.start()
        threads.append(t)
        time.sleep(0.05)        # deterministic arrival order
    adm.release(hold)
    for t in threads:
        t.join()
    assert order[0] == "high"


def test_budget_throttles_then_window_rolls_over():
    """An over-budget tenant is throttled (not admitted) inside the
    window, degraded near the limit, and admissible again after the
    window rolls — throttling is bounded, never starvation."""
    adm = AdmissionController(4)
    fair = FairShareAdmission(adm, (TenantConfig(
        "t", budget_cents=10.0, budget_window_s=0.3,
        degrade_fraction=0.8),))
    assert fair.admissible("t") and not fair.degraded("t")
    fair.charge("t", 9.0)                   # past 80% → degraded
    assert fair.admissible("t") and fair.degraded("t")
    fair.charge("t", 2.0)                   # past 100% → throttled
    assert not fair.admissible("t")
    time.sleep(0.35)                        # window rollover
    assert fair.admissible("t") and not fair.degraded("t")
    st = fair.stats()["t"]
    assert st["throttled_admissions"] >= 1
    assert st["degraded_dispatches"] >= 1
    assert st["lifetime_cents"] == pytest.approx(11.0)
    # unknown / unmetered tenants are never limited
    assert fair.admissible(None) and fair.admissible("ghost")


def test_service_throttles_over_budget_tenant_but_not_forever(tpch_store):
    store, catalog = tpch_store
    svc, session = _service(store, catalog, tenants=(
        TenantConfig("broke", budget_cents=1e-6, budget_window_s=0.5),))
    try:
        svc.admission.charge("broke", 1.0)  # exhaust the window budget
        h = svc.submit(QUERIES["q6"], tenant="broke")
        time.sleep(0.15)
        assert h.status() is RequestStatus.QUEUED     # throttled
        # the next window admits it: throttling is bounded
        entry = h.wait(timeout=300)
        assert entry.status is RequestStatus.SUCCEEDED
        assert svc.stats()["tenants"]["broke"]["throttled_admissions"] >= 1
    finally:
        svc.close()
        session.close()


# -- DAGs (tentpole acceptance) -----------------------------------------------

def test_dag_validation_and_topological_order():
    assert topological_order(3, {}) == [0, 1, 2]
    assert topological_order(3, {2: [0, 1], 1: [0]}) == [0, 1, 2]
    assert topological_order(3, {0: [2], 1: [0]}) == [2, 0, 1]
    assert topological_order(2, {0: [1], 1: [0]}) is None      # cycle
    with pytest.raises(ValueError):
        validate_dag(2, {0: [1], 1: [0]})
    with pytest.raises(ValueError):
        validate_dag(2, {0: [0]})                              # self-dep
    with pytest.raises(ValueError):
        validate_dag(2, {0: [5]})                              # range
    with pytest.raises(ValueError):
        validate_dag(1, {3: []})


def test_dag_respects_depends_on_and_shares_subplans(tpch_store):
    """node1 depends on node0 and contains the same plan: it must start
    only after node0 SUCCEEDED and must not re-execute the shared
    pipelines (cache/dedup hits instead)."""
    store, catalog = tpch_store
    svc, session = _service(store, catalog)
    try:
        handles = svc.submit_dag(
            [QUERIES["q6"], QUERIES["q6"]], {1: [0]})
        e1 = handles[1].wait(timeout=300)
        e0 = handles[0].entry()
        assert e0.status is RequestStatus.SUCCEEDED
        assert e1.status is RequestStatus.SUCCEEDED
        assert e0.dag_id == e1.dag_id
        assert e1.depends_on == [e0.request_id]
        # ordering: the dependent only started after its dependency's
        # terminal record was written
        assert e1.started_at >= e0.finished_at
        # shared subplan materialized exactly once: node1 is all hits
        assert e1.result["cache_hits"] + e1.result["deduped"] >= 1
        np.testing.assert_allclose(
            handles[0].fetch(timeout=10)["revenue"],
            handles[1].fetch(timeout=10)["revenue"])
    finally:
        svc.close()
        session.close()


def test_dag_failed_dependency_fails_dependents(tpch_store):
    store, catalog = tpch_store
    svc, session = _service(store, catalog)
    try:
        handles = svc.submit_dag(
            ["select no_such_col from lineitem", QUERIES["q6"]],
            {1: [0]})
        with pytest.raises(RequestFailed):
            handles[0].result(timeout=120)
        with pytest.raises(RequestFailed):
            handles[1].result(timeout=120)
        assert "dependency" in handles[1].entry().error
    finally:
        svc.close()
        session.close()


# -- SLO deadlines → fleet sizing ---------------------------------------------

def test_stage_latency_budget_splits_remaining_deadline():
    cm = CostModel()
    assert cm.stage_latency_budget(10.0, 0.0, 2) == pytest.approx(5.0)
    assert cm.stage_latency_budget(10.0, 6.0, 2) == pytest.approx(2.0)
    # blown deadline degrades to the floor, never negative
    assert cm.stage_latency_budget(10.0, 20.0, 2) == \
        pytest.approx(0.001 / 2)
    assert cm.stage_latency_budget(10.0, 0.0, 0) == pytest.approx(10.0)


def _scan_fleet(deadline_s=None, fleet_cap=None):
    store, catalog = _fresh_db()
    engine = QueryEngine(
        store, catalog, platform=FaasPlatform(quota=32, seed=0),
        config=CoordinatorConfig(
            planner=PlannerConfig(bytes_per_worker=30_000),
            use_result_cache=False),
        deadline_s=deadline_s, fleet_cap=fleet_cap)
    res = engine.execute_sql("select l_quantity from lineitem")
    return res.stats.pipelines


def test_tight_deadline_escalates_fleet():
    """The same query under a tight SLO deadline must scan with at
    least as many workers as under a loose one."""
    tight = _scan_fleet(deadline_s=0.01)[0].n_fragments
    loose = _scan_fleet(deadline_s=1e6)[0].n_fragments
    assert tight >= loose
    assert tight > 1       # a near-zero budget widens the scan fleet


def test_fleet_cap_clamps_every_pipeline():
    pipelines = _scan_fleet(fleet_cap=1)
    assert all(p.n_fragments == 1 for p in pipelines)
    assert any(a["kind"] == "deadline_fleet"
               for p in pipelines for a in p.adaptations)


def test_feasible_deadline_is_never_missed():
    """A TPC-H mix under a deadline its fleets can meet: the stage
    budgets size every fleet, and no request misses."""
    store, catalog = _fresh_db()
    svc, session = _service(store, catalog, quota=8, tenants=(
        TenantConfig("gold", weight=3.0, deadline_s=10.0),))
    try:
        handles = [svc.submit(QUERIES[q], tenant="gold")
                   for q in ("q1", "q6", "q12", "q14")]
        results = [h.result(timeout=300) for h in handles]
        assert not any(r.deadline_missed for r in results)
        assert svc.stats()["deadline_misses"] == 0
    finally:
        svc.close()
        session.close()


def test_deadline_miss_is_recorded_by_service():
    # fresh store: a result-cache hit would (correctly) meet any SLO
    store, catalog = _fresh_db()
    svc, session = _service(store, catalog, tenants=(
        TenantConfig("slo", deadline_s=1e-9),))    # unmeetable
    try:
        h = svc.submit(QUERIES["q6"], tenant="slo")
        res = h.result(timeout=300)
        assert res.deadline_missed
        assert svc.stats()["deadline_misses"] >= 1
        assert h.entry().deadline_s == 1e-9        # tenant default applied
    finally:
        svc.close()
        session.close()
