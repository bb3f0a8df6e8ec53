"""SkyriseSession public API: concurrent multi-query execution over one
shared FaaS quota, cross-session result-cache sharing, query lifecycle
(queued-cancel never invokes a worker), explain-only planning, and the
QueryCoordinator deprecation shim."""

import numpy as np
import pytest

from repro.api import (ConsoleObserver, CoordinatorConfig, FaasPlatform,
                       QueryCancelled, QueryObserver, QueryState, connect)
from repro.core import QueryCoordinator
from repro.data import generate_tpch
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.storage import ObjectStore

CFG = CoordinatorConfig(planner=PlannerConfig(
    bytes_per_worker=250_000, broadcast_threshold_bytes=150_000,
    exchange_partitions=3))


def _fresh_db(seed=0, tier="local"):
    store = ObjectStore(tier=tier, seed=seed)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    return store, catalog


def test_connect_builds_session_and_runs_sql():
    store, catalog = _fresh_db()
    with connect(store, catalog, config=CFG) as session:
        res = session.sql(QUERIES["q6"])
        cols = res.fetch(store)
        assert len(cols["revenue"]) == 1
        assert res.stats.sim_latency_s > 0
        assert res.stats.cost.total_cents > 0


def test_handle_lifecycle_and_stats():
    store, catalog = _fresh_db()
    with connect(store, catalog, config=CFG) as session:
        h = session.submit(QUERIES["q6"])
        res = h.result(timeout=120)
        assert h.state is QueryState.SUCCEEDED
        assert h.done()
        assert h.stats() is res.stats
        assert h.stats().query_id == h.query_id
        # terminal handles can no longer be cancelled
        assert not h.cancel()
        assert h.state is QueryState.SUCCEEDED


def test_concurrent_queries_share_quota_and_never_exceed_it():
    """≥4 concurrently submitted queries, one shared platform: combined
    in-flight workers stay within the quota (wave admission spans
    queries, not just fragments of one pipeline)."""
    store, catalog = _fresh_db()
    quota = 3
    platform = FaasPlatform(quota=quota, seed=0)
    cfg = CoordinatorConfig(planner=CFG.planner, use_result_cache=False)
    with connect(store, catalog, platform=platform, config=cfg,
                 max_concurrent_queries=4) as session:
        handles = [session.submit(QUERIES[q])
                   for q in ("q1", "q6", "q12", "q14")]
        results = [h.result(timeout=300) for h in handles]
    assert all(h.state is QueryState.SUCCEEDED for h in handles)
    adm = platform.admission
    assert 1 <= adm.max_in_flight <= quota
    assert adm.in_flight == 0                   # everything released
    # all four queries really ran workers on the one platform
    total_frags = sum(p.n_fragments for r in results
                      for p in r.stats.pipelines)
    assert platform.invocations >= total_frags > quota


def test_concurrent_submissions_match_sequential_results():
    store, catalog = _fresh_db(tier="s3-standard")
    seq = {}
    with connect(store, catalog, config=CFG) as session:
        for q in ("q1", "q12"):
            seq[q] = session.sql(QUERIES[q]).fetch(store)

    store2, catalog2 = _fresh_db(tier="s3-standard")
    with connect(store2, catalog2, config=CFG, quota=4,
                 max_concurrent_queries=2) as session:
        handles = {q: session.submit(QUERIES[q]) for q in ("q1", "q12")}
        for q, h in handles.items():
            got = h.result(timeout=300).fetch(store2)
            for k in seq[q]:
                np.testing.assert_allclose(
                    np.asarray(got[k], np.float64),
                    np.asarray(seq[q][k], np.float64),
                    err_msg=f"{q}.{k}")


def test_two_sessions_share_result_cache_through_store():
    """Section 3.4 across sessions: the semantic cache lives in the
    store, so a second session skips every pipeline the first ran."""
    store, catalog = _fresh_db()
    platform = FaasPlatform(seed=0)

    with connect(store, catalog, platform=platform, config=CFG) as s1:
        r1 = s1.sql(QUERIES["q12"])
    assert r1.stats.cache_hits == 0

    inv_before = platform.invocations
    with connect(store, catalog, platform=platform, config=CFG) as s2:
        h = s2.submit(QUERIES["q12"])
        st = h.stats(timeout=120)
    assert st.cache_hits == len(st.pipelines)   # visible in handle.stats()
    assert platform.invocations == inv_before   # zero new workers
    # both directions: s2 primes a query, s1's store serves it to a
    # brand-new third session
    with connect(store, catalog, platform=platform, config=CFG) as s3:
        st3 = s3.submit(QUERIES["q12"]).stats(timeout=120)
    assert st3.cache_hits == len(st3.pipelines)


def test_cancel_queued_handle_never_invokes_worker():
    store, catalog = _fresh_db()
    platform = FaasPlatform(seed=0)
    with connect(store, catalog, platform=platform, config=CFG) as session:
        session.pause()                   # admission gate: nothing runs
        h = session.submit(QUERIES["q1"])
        assert h.state is QueryState.QUEUED
        assert h.cancel()
        session.resume()
        assert h.wait(timeout=60)
        assert h.state is QueryState.CANCELLED
        with pytest.raises(QueryCancelled):
            h.result(timeout=10)
    assert platform.invocations == 0


def test_multi_fragment_root_result_is_fully_fetched():
    """The result location(s) come from the registry entry, not a
    hardcoded f0000 — a root pipeline split across fragments must
    return every row."""
    store, catalog = _fresh_db()
    # tiny bytes_per_worker → the lineitem scan splits into >1 fragment;
    # a projection-only query keeps the scan pipeline as root
    cfg = CoordinatorConfig(planner=PlannerConfig(bytes_per_worker=50_000))
    with connect(store, catalog, config=cfg) as session:
        res = session.sql(
            "select l_quantity, l_extendedprice from lineitem")
        root_report = res.stats.pipelines[-1]
        assert len(res.locations) > 1, \
            "expected a multi-fragment root pipeline"
        cols = res.fetch(store)
    n_lineitem = catalog.table("lineitem").rows
    assert len(cols["l_quantity"]) == n_lineitem
    assert root_report.n_fragments == len(res.locations)


def test_explain_plans_without_invoking_workers():
    store, catalog = _fresh_db()
    platform = FaasPlatform(seed=0)
    with connect(store, catalog, platform=platform, config=CFG) as session:
        text = session.explain(QUERIES["q3"])
    assert "pipeline" in text and "root" in text
    assert platform.invocations == 0


def test_observer_receives_lifecycle_and_pipeline_events():
    events = []

    class Recorder(QueryObserver):
        def on_query_state(self, query_id, state):
            events.append(("state", state))

        def on_pipeline_start(self, query_id, pid, sem_hash, n_fragments):
            events.append(("start", pid))

        def on_pipeline_complete(self, query_id, report):
            events.append(("complete", report.pid, report.cache_hit))

    store, catalog = _fresh_db()
    with connect(store, catalog, config=CFG,
                 observers=(Recorder(),)) as session:
        session.sql(QUERIES["q6"])
        session.sql(QUERIES["q6"])          # cached second run
    states = [e[1] for e in events if e[0] == "state"]
    assert states.count("PLANNING") == 2
    assert states.count("SUCCEEDED") == 2
    assert any(e[0] == "start" for e in events)
    assert any(e[0] == "complete" and e[2] for e in events)  # cache hit


def test_console_observer_smoke(capsys):
    import io
    buf = io.StringIO()
    store, catalog = _fresh_db()
    with connect(store, catalog, config=CFG,
                 observers=(ConsoleObserver(out=buf),)) as session:
        session.sql(QUERIES["q6"])
    out = buf.getvalue()
    assert "RUNNING" in out and "pipeline" in out


def test_query_coordinator_shim_still_works_and_warns():
    store, catalog = _fresh_db()
    with pytest.warns(DeprecationWarning, match="SkyriseSession"):
        coord = QueryCoordinator(store, catalog,
                                 platform=FaasPlatform(seed=0), config=CFG)
    res = coord.execute_sql(QUERIES["q6"])
    cols = res.fetch(store)
    assert len(cols["revenue"]) == 1
    # old single-location attribute still present
    assert res.location == res.locations[0]


def test_connect_rejects_conflicting_arguments():
    store, catalog = _fresh_db()
    with pytest.raises(ValueError, match="platform or quota"):
        connect(store, catalog, platform=FaasPlatform(seed=0), quota=8)
    with pytest.raises(ValueError, match="store or store_dir"):
        connect(store, catalog, tier="local")


def test_operations_without_catalog_raise_actionable_error():
    session = connect(tier="local")
    with pytest.raises(RuntimeError, match="no catalog attached"):
        session.submit(QUERIES["q6"])
    with pytest.raises(RuntimeError, match="no catalog attached"):
        session.explain(QUERIES["q6"])


def test_failed_query_surfaces_error_and_state():
    store, catalog = _fresh_db()
    with connect(store, catalog, config=CFG) as session:
        h = session.submit("select nope from lineitem")
        assert h.wait(timeout=120)
        assert h.state is QueryState.FAILED
        assert h.error() is not None
        with pytest.raises(Exception):
            h.result(timeout=10)


def test_compile_cache_follows_env_else_checkout_on_tpu(monkeypatch,
                                                         tmp_path):
    import jax
    from repro import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []                  # JAX's own setting stands
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable_compile_cache() is None   # CPU: off
    assert calls == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert path.endswith(".jax_cache")
    assert dict(calls) == {
        "jax_compilation_cache_dir": path,
        "jax_persistent_cache_min_compile_time_secs": 0.0}
