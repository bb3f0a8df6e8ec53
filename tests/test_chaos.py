"""Chaos engine: seeded full-stack fault injection (tentpole PR).

Proves the robustness claims end to end:

  * kill-point sweep — the owner process dies at every registry protocol
    step (claim / begin_partial / publish_partial / finish_partial) and
    at the storage commit point, under background fault noise, across
    seeds: every run recovers to TPC-H parity with the fault-free
    reference;
  * exactly-once fleet work — an owner killed right after writing its
    claim leaves an orphan that is TTL-stolen and re-driven with the
    platform seeing exactly one fleet's invocations (count-proven);
  * probabilistic seed sweep — transient GET/PUT errors, 503 throttles,
    latency spikes, torn PUTs, cold-start storms, and worker kills all
    at once, 20 seeds, parity on every one;
  * torn-write protection — a sandbox dying mid-PUT leaves only an
    orphaned ``_tmp/`` object; a readable partial object never appears
    at a final key;
  * typed failure taxonomy — budget exhaustion surfaces
    ``RetryBudgetExhausted`` through the handle with the causal chain
    from the failing fragment intact;
  * claim-steal CAS (satellite) — two waiters racing a TTL-expired
    claim resolve to exactly one winner via the versioned put;
  * ledger kills + lease fencing (satellites) — instance death at each
    ledger CAS leaves a consistent record a peer recovers, and a
    slow-but-alive owner cannot renew an expired lease;
  * hedged reads — the cost model's break-even timeout replaces the
    constant straggler timeout and duplicate GETs are priced/counted.

Every chaos schedule is seeded: a failing case reproduces locally from
its ``(seed, kill_point)`` test id alone.
"""

import threading
import time

import msgpack
import numpy as np
import pytest

from repro.api import (ChaosConfig, ChaosEngine, CoordinatorConfig,
                       FaasPlatform, QueryFailedError, QueryState,
                       RetryBudgetExhausted, RetryPolicy,
                       TransientInfraError, connect)
from repro.core.chaos import ChaosKill
from repro.core.cost import CostModel
from repro.core.registry import ResultRegistry
from repro.data import generate_tpch
from repro.service import (QueryService, RequestLedger, RequestStatus,
                           ServiceHandle)
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.storage import (ColumnSpec, FooterCache, InputHandler,
                           ObjectStore, write_pax)

PLANNER = PlannerConfig(bytes_per_worker=250_000,
                        broadcast_threshold_bytes=150_000,
                        exchange_partitions=3)


def _config(**kw):
    # calibration off: no cross-run state, so invocation counts and
    # plans are bit-deterministic between a reference and a chaos run
    return CoordinatorConfig(planner=PLANNER, calibrate_selectivity=False,
                             **kw)


def _fresh_db(seed=0):
    store = ObjectStore(tier="local", seed=seed)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    return store, catalog


def _run(qname, chaos=None, *, config=None, claim_ttl_s=0.25, quota=16):
    """One full query execution on a fresh store; returns (columns,
    platform invocation count). The parity fetch runs with injection
    paused — the verification read path is not the system under test."""
    store, catalog = _fresh_db()
    platform = FaasPlatform(quota=quota, seed=0)
    registry = ResultRegistry(store, claim_ttl_s=claim_ttl_s)
    session = connect(store, catalog, platform=platform,
                      config=config or _config(), registry=registry,
                      chaos=chaos, max_concurrent_queries=4)
    try:
        res = session.submit(QUERIES[qname]).result(timeout=300)
        if chaos is not None:
            with chaos.pause():
                cols = res.fetch(store)
        else:
            cols = res.fetch(store)
    finally:
        session.close()
        platform.close()
    return cols, platform.invocations


_REFERENCE: dict = {}


def _reference(qname, *, pipelined=True):
    """Fault-free reference columns + invocation count (cached)."""
    key = (qname, pipelined)
    if key not in _REFERENCE:
        _REFERENCE[key] = _run(qname, config=_config(pipelined=pipelined))
    return _REFERENCE[key]


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


# -- chaos engine mechanics ---------------------------------------------------

def test_chaos_schedule_is_deterministic():
    cfg = ChaosConfig(seed=5, get_error_prob=0.3, put_error_prob=0.3,
                      throttle_prob=0.1, torn_put_prob=0.2)
    a, b = ChaosEngine(cfg), ChaosEngine(cfg)
    seq_a = [a.storage_fault(op, f"k{i}")
             for i in range(200) for op in ("get", "put")]
    seq_b = [b.storage_fault(op, f"k{i}")
             for i in range(200) for op in ("get", "put")]
    assert seq_a == seq_b
    assert any(f is not None for f in seq_a)
    c = ChaosEngine(ChaosConfig(seed=6, get_error_prob=0.3,
                                put_error_prob=0.3, throttle_prob=0.1,
                                torn_put_prob=0.2))
    seq_c = [c.storage_fault(op, f"k{i}")
             for i in range(200) for op in ("get", "put")]
    assert seq_a != seq_c


def test_chaos_pause_suspends_injection():
    store = ObjectStore(tier="local", seed=0)
    store.put("k", b"abc")
    store.chaos = ChaosEngine(ChaosConfig(get_error_prob=1.0))
    with store.chaos.pause():
        assert store.get("k").data == b"abc"
    with pytest.raises(TransientInfraError):
        store.get("k")


def test_kv_tier_is_exempt_from_storage_faults():
    store = ObjectStore(tier="local", seed=0)
    store.chaos = ChaosEngine(ChaosConfig(get_error_prob=1.0,
                                          put_error_prob=1.0))
    kv = store.with_tier("dynamodb")
    kv.put("ledger/x", b"entry")          # would raise on a data tier
    assert kv.get("ledger/x").data == b"entry"
    with pytest.raises(TransientInfraError):
        store.put("data/x", b"payload")


# -- torn-write protection ----------------------------------------------------

def test_put_committed_kill_before_commit_leaves_no_final_object():
    store = ObjectStore(tier="local", seed=0)
    store.chaos = ChaosEngine(ChaosConfig(kill_points=("storage.commit",)))
    with pytest.raises(TransientInfraError):
        store.put_committed("data/x", b"hello world")
    # the upload finished but the commit never ran: final key absent,
    # one whole orphan under _tmp/ that nobody will ever read
    assert not store.exists("data/x")
    orphans = store.list("_tmp/")
    assert len(orphans) == 1
    assert store.get(orphans[0]).data == b"hello world"
    # the kill point is one-shot: the retry commits
    store.put_committed("data/x", b"hello world")
    assert store.get("data/x").data == b"hello world"


def test_torn_put_leaves_prefix_only_under_tmp():
    store = ObjectStore(tier="local", seed=0)
    store.chaos = ChaosEngine(ChaosConfig(seed=3, torn_put_prob=1.0))
    payload = bytes(range(200)) * 10
    with pytest.raises(TransientInfraError):
        store.put_committed("data/x", payload)
    assert not store.exists("data/x")
    orphans = store.list("_tmp/")
    assert len(orphans) == 1
    torn = store.get(orphans[0]).data       # list/get are chaos-free here
    assert 0 < len(torn) < len(payload)
    assert payload.startswith(torn)         # a strict prefix, as modeled


def test_memory_backend_put_if_version_cas():
    store = ObjectStore(tier="local", seed=0)
    assert store.put_if_version("k", b"v1", None)        # create-if-absent
    assert not store.put_if_version("k", b"x", None)     # exists now
    tok = store.version("k")
    assert store.put_if_version("k", b"v2", tok)         # matching token
    assert not store.put_if_version("k", b"v3", tok)     # stale token
    assert store.get("k").data == b"v2"


# -- registry claim-steal CAS (satellite) -------------------------------------

def test_claim_steal_is_versioned_cas():
    """Two waiters observe the same TTL-expired claim and both decide to
    steal: the conditional put lets exactly one land; the loser's put —
    conditioned on the version it observed before the winner moved it —
    must fail instead of silently overwriting the winner's claim."""
    store = ObjectStore(tier="local", seed=0)
    reg1 = ResultRegistry(store, claim_ttl_s=0.05)
    assert reg1.claim("h")
    time.sleep(0.08)                     # owner dies silently: claim stale

    key = reg1._key("h")
    kv = reg1.store
    stale_token = kv.version(key)        # both stealers observed this
    reg2 = ResultRegistry(store, claim_ttl_s=0.05)
    assert reg2.claim("h")               # stealer 1 wins the CAS
    # stealer 2 still holds the pre-steal version: its conditional put
    # loses (this is the seam the old check-then-put raced on)
    blob = msgpack.packb({"complete": False, "claimed_at": time.time(),
                          "owner": "stealer-2"})
    assert not kv.put_if_version(key, blob, stale_token)
    entry = msgpack.unpackb(kv.get(key).data)
    assert entry["owner"] == reg2._owned["h"]   # winner's claim intact
    # and a live claim is not claimable
    assert not ResultRegistry(store, claim_ttl_s=0.05).claim("h")


def test_claim_storm_exactly_one_winner():
    store = ObjectStore(tier="local", seed=0)
    stale = ResultRegistry(store, claim_ttl_s=0.05)
    assert stale.claim("h")
    time.sleep(0.08)
    barrier = threading.Barrier(8)
    wins = []

    def steal():
        reg = ResultRegistry(store, claim_ttl_s=0.05)
        barrier.wait()
        wins.append(reg.claim("h"))

    threads = [threading.Thread(target=steal) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(wins) == 1


# -- ledger kill points + lease fencing (satellites) --------------------------

@pytest.mark.parametrize("die_at", [RequestStatus.ADMITTED,
                                    RequestStatus.RUNNING])
def test_ledger_kill_after_cas_is_recoverable(die_at):
    """The service instance dies right after the CAS that landed the
    ``die_at`` transition: the entry is consistent (the write happened),
    the owner is gone, and lease expiry hands it back to QUEUED."""
    store = ObjectStore(tier="local", seed=0)
    store.chaos = ChaosEngine(
        ChaosConfig(kill_points=(f"ledger.{die_at.value}",)))
    led = RequestLedger(store, lease_ttl_s=0.05)
    led.submit("q", request_id="r")
    if die_at is RequestStatus.ADMITTED:
        with pytest.raises(ChaosKill):
            led.claim("r", "svc-dead")
    else:
        led.claim("r", "svc-dead")
        with pytest.raises(ChaosKill):
            led.transition("r", RequestStatus.RUNNING, if_owner="svc-dead")
    entry = led.get("r")
    assert entry.status is die_at        # the CAS landed before the death
    assert entry.owner == "svc-dead"
    time.sleep(0.08)
    recovered = led.recover_expired()
    assert [e.request_id for e in recovered] == ["r"]
    e = led.get("r")
    assert e.status is RequestStatus.QUEUED
    assert e.owner is None and e.attempt == 1
    assert led.claim("r", "svc-peer") is not None    # a peer takes over


def test_ledger_kill_after_terminal_cas_keeps_result():
    """Death right after the SUCCEEDED CAS: the terminal record (and its
    result pointer) survives; recovery has nothing to do."""
    store = ObjectStore(tier="local", seed=0)
    store.chaos = ChaosEngine(ChaosConfig(kill_points=("ledger.SUCCEEDED",)))
    led = RequestLedger(store, lease_ttl_s=0.05)
    led.submit("q", request_id="r")
    led.claim("r", "svc")
    led.transition("r", RequestStatus.RUNNING, if_owner="svc")
    with pytest.raises(ChaosKill):
        led.transition("r", RequestStatus.SUCCEEDED, if_owner="svc",
                       result={"prefix": "results/h"})
    time.sleep(0.08)
    assert led.recover_expired() == []   # terminal states are final
    e = led.get("r")
    assert e.status is RequestStatus.SUCCEEDED
    assert e.result == {"prefix": "results/h"}


def test_late_lease_renewal_is_fenced():
    """``recover_expired`` racing a slow-but-alive owner: once the lease
    deadline passed, the owner's renewal must fail (fencing) whether it
    arrives before or after recovery actually re-queues the entry —
    renewing after expiry would resurrect ownership a peer may already
    hold and run the query twice."""
    store = ObjectStore(tier="local", seed=0)
    led = RequestLedger(store, lease_ttl_s=0.05)
    led.submit("q", request_id="r")
    led.claim("r", "svc-slow")
    time.sleep(0.08)
    # the slow owner wakes up *before* any recovery ran: already fenced
    assert not led.renew_lease("r", "svc-slow")
    assert led.get("r").lease_expires < time.time()   # not extended
    # recovery then re-queues exactly once
    assert [e.request_id for e in led.recover_expired()] == ["r"]
    assert led.get("r").owner is None
    # and the fenced owner stays dead after recovery too
    assert not led.renew_lease("r", "svc-slow")


# -- kill-point sweep (tentpole acceptance) -----------------------------------

KILL_SITES = ("registry.claim", "registry.begin_partial",
              "registry.publish_partial", "registry.finish_partial",
              "storage.commit")


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("site", KILL_SITES)
def test_kill_point_sweep_recovers_to_parity(site, seed):
    """Owner death at every protocol step, under background fault noise,
    across seeds: recovery (TTL steal, partial-stream reset, fragment
    retry) must reconverge to the fault-free TPC-H answer. A failure
    reproduces from the (site, seed) in the test id."""
    ref_cols, _ = _reference("q3")
    chaos = ChaosEngine(ChaosConfig(
        seed=seed, kill_points=(site,),
        get_error_prob=0.003, put_error_prob=0.003,
        worker_kill_prob=0.01))
    # noise means fragments legitimately fail sometimes; give the
    # retry machinery headroom so the test asserts *recovery*, not the
    # max-attempts abort policy (covered by the taxonomy tests)
    cols, _ = _run("q3", chaos, config=_config(max_attempts=6))
    assert chaos.injected.get(f"kill:{site}") == 1, \
        f"kill point {site} never fired (seed={seed})"
    _assert_same_rows(ref_cols, cols, f"site={site} seed={seed}")


def test_zero_probability_engine_injects_nothing():
    """The off path: an engine attached with every probability zero and
    no kill point injects nothing, and the rows are the fault-free
    run's."""
    ref_cols, _ = _reference("q6")
    idle = ChaosEngine(ChaosConfig(seed=0))
    cols, _ = _run("q6", idle)
    assert idle.injected == {}
    _assert_same_rows(ref_cols, cols, "zero-probability engine")


def test_claim_owner_death_runs_fleet_exactly_once():
    """An owner killed right after writing its claim (before invoking
    anything) leaves an orphan. The re-drive TTL-steals it and runs the
    fleet — the platform must see exactly the fault-free invocation
    count: zero duplicate fleet work, count-proven. Barrier mode makes
    the schedule sequential, so the count comparison is exact."""
    ref_cols, ref_inv = _reference("q6", pipelined=False)
    chaos = ChaosEngine(ChaosConfig(kill_points=("registry.claim",)))
    cols, inv = _run("q6", chaos, config=_config(pipelined=False))
    assert chaos.injected.get("kill:registry.claim") == 1
    _assert_same_rows(ref_cols, cols, "claim-kill")
    assert inv == ref_inv, \
        f"duplicate fleet work: {inv} invocations vs reference {ref_inv}"


# -- probabilistic seed sweep (tentpole acceptance) ---------------------------

@pytest.mark.parametrize("seed", range(20))
def test_probabilistic_chaos_sweep_parity(seed):
    """All fault classes at once — transient GET/PUT errors, throttles,
    latency spikes, torn PUTs, cold-start storms, worker kills — across
    20 seeds. Every schedule must recover to the fault-free answer
    within the default retry budget."""
    ref_cols, _ = _reference("q6")
    chaos = ChaosEngine(ChaosConfig(
        seed=seed, get_error_prob=0.005, put_error_prob=0.005,
        throttle_prob=0.003, latency_spike_prob=0.05, torn_put_prob=0.005,
        cold_storm_prob=0.10, worker_kill_prob=0.02))
    cols, _ = _run("q6", chaos, config=_config(max_attempts=6))
    _assert_same_rows(ref_cols, cols, f"seed={seed}")


def test_torn_puts_under_load_never_reach_final_keys():
    ref_cols, _ = _reference("q6")
    chaos = ChaosEngine(ChaosConfig(seed=11, torn_put_prob=0.25))
    store, catalog = _fresh_db()
    platform = FaasPlatform(quota=16, seed=0)
    session = connect(store, catalog, platform=platform,
                      config=_config(max_attempts=6),
                      registry=ResultRegistry(store, claim_ttl_s=0.25),
                      chaos=chaos, max_concurrent_queries=4)
    try:
        res = session.submit(QUERIES["q6"]).result(timeout=300)
        with chaos.pause():
            cols = res.fetch(store)
            # the run tore real writes, and every torn object is an
            # orphan under _tmp/ — never promoted to a final key
            assert chaos.injected.get("storage.put.torn", 0) > 0
            assert len(store.list("_tmp/")) > 0
    finally:
        session.close()
        platform.close()
    _assert_same_rows(ref_cols, cols, "torn-put")


def test_cold_start_storm_forces_cold_invocations():
    ref_cols, _ = _reference("q6")
    chaos = ChaosEngine(ChaosConfig(seed=2, cold_storm_prob=1.0))
    store, catalog = _fresh_db()
    platform = FaasPlatform(quota=16, seed=0)
    session = connect(store, catalog, platform=platform, config=_config(),
                      registry=ResultRegistry(store, claim_ttl_s=0.25),
                      chaos=chaos, max_concurrent_queries=4)
    try:
        res = session.submit(QUERIES["q6"]).result(timeout=300)
        with chaos.pause():
            cols = res.fetch(store)
    finally:
        session.close()
        platform.close()
    assert platform.cold_starts == platform.invocations
    _assert_same_rows(ref_cols, cols, "cold-storm")


# -- typed failure taxonomy ---------------------------------------------------

def test_retry_budget_exhaustion_surfaces_typed_error():
    """With a zero retry budget and every worker killed, the first
    fragment retry is refused: the handle must surface
    ``RetryBudgetExhausted`` (a ``QueryFailedError``) with the causal
    chain from the failing fragment preserved."""
    store, catalog = _fresh_db()
    chaos = ChaosEngine(ChaosConfig(seed=0, worker_kill_prob=1.0))
    platform = FaasPlatform(quota=16, seed=0)
    config = _config(retry=RetryPolicy(budget=0, base_delay_s=1e-4,
                                       max_delay_s=1e-3),
                     pilot_scan_min_units=10_000)
    session = connect(store, catalog, platform=platform, config=config,
                      registry=ResultRegistry(store, claim_ttl_s=0.25),
                      chaos=chaos, max_concurrent_queries=4)
    try:
        handle = session.submit(QUERIES["q6"])
        with pytest.raises(RetryBudgetExhausted) as ei:
            handle.result(timeout=120)
        err = ei.value
        assert isinstance(err, QueryFailedError)     # permanent, typed
        assert err.last_error is not None            # the final transient
        assert isinstance(err.last_error, TransientInfraError)
        assert err.__cause__ is not None             # causal chain intact
        assert handle.state is QueryState.FAILED
        assert handle.error() is err                 # re-raised as-is
    finally:
        session.close()
        platform.close()


def test_retry_policy_backoff_is_bounded_full_jitter():
    policy = RetryPolicy(base_delay_s=0.010, max_delay_s=0.050,
                         multiplier=2.0)
    rng = np.random.default_rng(0)
    for attempt in range(1, 10):
        cap = min(0.050, 0.010 * 2.0 ** (attempt - 1))
        for _ in range(20):
            d = policy.backoff_s(attempt, rng=rng)
            assert 0.0 <= d <= cap


# -- hedged reads -------------------------------------------------------------

def test_hedged_reads_use_cost_model_break_even_timeout():
    cm = CostModel()
    # s3-standard: median first byte + (request cents) / (GiB-s rate)
    t = cm.hedge_timeout_s("s3-standard")
    assert 0.027 < t < 0.2      # above the median, far below the 0.2s
    store = ObjectStore(tier="local", seed=0)
    schema = [ColumnSpec("x", "num", "<i8")]
    store.put("db/t.spax",
              write_pax({"x": np.arange(256, dtype=np.int64)}, schema))
    hedged = InputHandler(store, footer_cache=FooterCache(), cost_model=cm)
    assert hedged.hedged
    assert hedged.straggler_timeout_s == pytest.approx(
        cm.hedge_timeout_s(store.tier))
    plain = InputHandler(store, footer_cache=FooterCache())
    assert not plain.hedged and plain.straggler_timeout_s == 0.2
    # a latency spike pushes the simulated first byte past the hedge
    # timeout: the duplicate GET is issued and counted
    store.chaos = ChaosEngine(ChaosConfig(latency_spike_prob=1.0,
                                          latency_spike_factor=1e9))
    cols, _footer, st = hedged.read_table("db/t.spax")
    np.testing.assert_array_equal(cols["x"], np.arange(256))
    assert st.hedges > 0
    assert st.retriggers >= st.hedges


def test_hedged_reads_keep_query_parity():
    ref_cols, _ = _reference("q6")
    chaos = ChaosEngine(ChaosConfig(seed=4, latency_spike_prob=0.2))
    cols, _ = _run("q6", chaos, config=_config(hedged_reads=True))
    _assert_same_rows(ref_cols, cols, "hedged")


# -- service instance death (end to end) --------------------------------------

def test_service_dispatcher_death_recovered_by_second_instance():
    """The dispatcher dies by chaos kill right after the ledger CAS that
    admitted a request (the instance-crash analog): the first service
    stops cold, the lease expires, and a second instance over the same
    ledger re-queues and finishes the query — with exactly one fleet's
    invocations on the shared platform."""
    # fault-free invocation count for the same query/config
    store0, catalog0 = _fresh_db()
    p0 = FaasPlatform(quota=16, seed=0)
    with connect(store0, catalog0, platform=p0, config=_config(),
                 max_concurrent_queries=4) as s0:
        s0.sql(QUERIES["q6"])
    solo = p0.invocations
    p0.close()

    store, catalog = _fresh_db()
    chaos = ChaosEngine(ChaosConfig(kill_points=("ledger.ADMITTED",)))
    store.chaos = chaos              # before the ledger snapshots its view
    ledger = RequestLedger(store, lease_ttl_s=0.2)
    platform = FaasPlatform(quota=16, seed=0)
    s1 = connect(store, catalog, platform=platform, config=_config(),
                 max_concurrent_queries=4)
    svc1 = QueryService(s1, ledger=ledger, lease_ttl_s=0.2)
    h = svc1.submit(QUERIES["q6"])
    deadline = time.monotonic() + 30
    while not svc1._closing.is_set() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert svc1._closing.is_set()                  # the instance died
    assert chaos.injected.get("kill:ledger.ADMITTED") == 1
    entry = ledger.get(h.request_id)
    assert entry.status is RequestStatus.ADMITTED  # the CAS landed
    assert platform.invocations == 0               # ...before any worker
    svc1.kill()
    time.sleep(0.25)                               # lease expires

    s2 = connect(store, catalog, platform=platform, config=_config(),
                 max_concurrent_queries=4)
    svc2 = QueryService(s2, ledger=ledger, lease_ttl_s=0.2)
    try:
        entry = ServiceHandle(h.request_id, svc2).wait(timeout=120)
        assert entry.status is RequestStatus.SUCCEEDED
        assert entry.attempt == 1                  # recovery was recorded
        assert platform.invocations == solo        # exactly one fleet
        cols = ServiceHandle(h.request_id, svc2).fetch(timeout=30)
        assert len(cols["revenue"]) == 1
    finally:
        svc2.close()
        s2.close()
        s1.close()
        platform.close()
