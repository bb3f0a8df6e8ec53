"""Program spans: the fragment's layer spans and counters, the
coordinator's waits, and their path through the worker response, the
pipeline report and the observer."""

import threading
import time

import numpy as np
import pytest

from repro.api import CoordinatorConfig, connect
from repro.core import worker
from repro.core.engine import QueryEngine
from repro.core.events import ConsoleObserver, ObserverMux, QueryObserver
from repro.core.platform import FaasPlatform, FaultPlan
from repro.data import generate_tpch
from repro.exec import fragment
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES
from repro.storage import ObjectStore

PLANNER = PlannerConfig(bytes_per_worker=250_000,
                        broadcast_threshold_bytes=150_000,
                        exchange_partitions=3)
CFG = CoordinatorConfig(planner=PLANNER, use_result_cache=False)
FRAGMENT_SPANS = ("fragment.read", "fragment.h2d", "fragment.device",
                  "fragment.d2h", "fragment.write")


class SpanLog(QueryObserver):
    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def on_span(self, query_id, pid, fragment, name, start_ns, end_ns):
        with self._lock:
            self.events.append((query_id, pid, fragment, name, start_ns,
                                end_ns))


def _session(store, catalog, *, platform=None, cfg=CFG):
    kwargs = {"platform": platform} if platform is not None \
        else {"quota": 1000}
    return connect(store, catalog, config=cfg, **kwargs)


@pytest.fixture
def fragment_runs(monkeypatch):
    """Every fragment executed, with its own host interval, the
    capacities and arrays ``from_numpy`` was handed inside it, and the
    output block ``to_numpy`` pulled back."""
    runs, local = [], threading.local()
    run_fragment, from_numpy = worker.execute_fragment, fragment.from_numpy
    to_numpy = fragment.to_numpy

    def timed(store, spec, **kw):
        local.blocks, local.out = [], None
        t0 = time.perf_counter_ns()
        res = run_fragment(store, spec, **kw)
        runs.append((spec, t0, time.perf_counter_ns(), res,
                     local.blocks, local.out))
        return res

    def seen(columns, capacity=None):
        local.blocks.append((columns, capacity))
        return from_numpy(columns, capacity)

    def pulled(block):
        local.out = block
        return to_numpy(block)

    monkeypatch.setattr(worker, "execute_fragment", timed)
    monkeypatch.setattr(fragment, "from_numpy", seen)
    monkeypatch.setattr(fragment, "to_numpy", pulled)
    return runs


@pytest.mark.parametrize("query", ["q6", "q3"])
def test_fragment_spans_lie_inside_the_fragment_and_apart(
        tpch_store, fragment_runs, query):
    store, catalog = tpch_store
    with _session(store, catalog) as session:
        session.submit(QUERIES[query]).result(timeout=300)
    runs = [r for r in fragment_runs
            if r[0]["op"]["t"] != "merge_exchange"]
    assert runs
    for spec, t0, t1, res, blocks, out in runs:
        st = res.stats
        names = [n for n, _, _ in st.spans]
        assert set(names) == set(FRAGMENT_SPANS)
        n_leaves = len(blocks)
        assert names.count("fragment.read") == n_leaves
        assert names.count("fragment.h2d") == n_leaves
        for once in ("fragment.device", "fragment.d2h", "fragment.write"):
            assert names.count(once) == 1
        ordered = sorted(st.spans, key=lambda s: s[1])
        for (_, s, e), (_, s2, _) in zip(ordered, ordered[1:]):
            assert s <= e <= s2
        assert all(t0 <= s and e <= t1 for _, s, e in st.spans)
        read_ns = sum(e - s for n, s, e in st.spans if n == "fragment.read")
        assert 0 <= st.decode_ns <= read_ns
        # padded columns and the mask, at the capacity of each block
        want = sum(cap * (sum(v.dtype.itemsize * int(np.prod(v.shape[1:]))
                              for v in cols.values()) + 1)
                   for cols, cap in blocks)
        assert st.h2d_bytes == want
        # the two int32 scalars and the output as it was pulled: never
        # more than its columns at capacity, nor fewer than its rows
        row_bytes = sum(c.dtype.itemsize for c in out.columns.values())
        assert st.d2h_bytes == 8 + out.pulled_bytes
        assert out.count * row_bytes <= out.pulled_bytes \
            <= out.capacity * row_bytes
        # one clock reading serves the device span and compute_s
        (s, e), = [(s, e) for n, s, e in st.spans
                   if n == "fragment.device"]
        assert st.compute_s == pytest.approx((e - s) / 1e9)
    assert any(res.stats.decode_ns > 0 for _, _, _, res, _, _ in runs)


def test_observer_receives_every_span_and_the_report_sums_them(tpch_store):
    store, catalog = tpch_store
    log = SpanLog()
    with _session(store, catalog) as session:
        session.add_observer(log)
        handle = session.submit(QUERIES["q3"])
        handle.result(timeout=300)
        other = session.submit(QUERIES["q6"])
        other.result(timeout=300)
    mine = [e for e in log.events if e[0] == handle.query_id]
    assert mine and any(e[0] == other.query_id for e in log.events)
    reports = {p.pid: p for p in handle.stats().pipelines}
    for pid, report in reports.items():
        sums: dict[str, float] = {}
        for _, epid, frag, name, s, e in mine:
            assert e >= s
            assert (frag == -1) == name.startswith("coord.")
            if epid == pid:
                sums[name] = sums.get(name, 0.0) + (e - s) / 1e6
        assert set(sums) == set(report.span_ms)
        for name, ms in report.span_ms.items():
            assert ms == pytest.approx(sums[name])
        assert set(FRAGMENT_SPANS) <= set(report.span_ms)
        assert report.counters["h2d_bytes"] > 0
        assert report.counters["d2h_bytes"] > 0
    # pipelined consumers waited on their sources; the scans admitted
    names = {e[3] for e in mine}
    assert {"coord.admit", "coord.await_source"} <= names


def test_straggler_reruns_are_spanned_once_each():
    # first-byte latencies of a remote tier: a 50x straggler passes the
    # detection floor
    store = ObjectStore(tier="s3-standard", seed=0)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    plat = FaasPlatform(seed=3, faults=FaultPlan(
        straggle_fragments=((0, 1, 0),), straggler_factor=50.0, seed=5))
    log = SpanLog()
    with _session(store, catalog, platform=plat) as session:
        session.add_observer(log)
        handle = session.submit(QUERIES["q1"])
        handle.result(timeout=300)
    reports = handle.stats().pipelines
    retriggered = sum(p.stragglers_retriggered for p in reports)
    assert retriggered >= 1
    reruns = [e for e in log.events if e[3] == "coord.straggler_rerun"]
    assert len(reruns) == retriggered
    for p in reports:
        assert ("coord.straggler_rerun" in p.span_ms) == \
            (p.stragglers_retriggered > 0)
    # the duplicate ran its fragment's layers inside the re-run span
    _, pid, _, _, s, e = reruns[0]
    inside = {n for _, epid, f, n, s2, e2 in log.events
              if epid == pid and f >= 0 and s <= s2 and e2 <= e}
    assert set(FRAGMENT_SPANS) <= inside


def test_semijoin_probe_spans_its_filter_wait(tpch_store):
    store, catalog = tpch_store
    cfg = CoordinatorConfig(planner=PlannerConfig(
        bytes_per_worker=250_000, broadcast_threshold_bytes=1,
        exchange_partitions=3), use_result_cache=False)
    log = SpanLog()
    engine = QueryEngine(store, catalog, platform=FaasPlatform(seed=1),
                         config=cfg, observer=log)
    plan = engine.plan_sql(
        "select l_orderkey, sum(l_extendedprice) as rev "
        "from lineitem, orders "
        "where l_orderkey = o_orderkey and o_totalprice > 500000 "
        "group by l_orderkey")
    probes = [pid for pid, p in plan.pipelines.items() if p.params.semijoin]
    for pid in probes:
        # past the cost gate, which SF 0.01 never passes
        plan.pipelines[pid].params.semijoin["enabled"] = True
    res = engine.execute_plan(plan)
    engine.platform.close()
    waits = [e for e in log.events if e[3] == "coord.await_filter"]
    assert probes and sorted(e[1] for e in waits) == sorted(probes)
    assert all(e[2] == -1 for e in waits)
    for r in res.stats.pipelines:
        assert ("coord.await_filter" in r.span_ms) == (r.pid in probes)


def test_explain_analyze_prints_each_pipelines_spans(tpch_store):
    store, catalog = tpch_store
    with _session(store, catalog) as session:
        text = session.submit(QUERIES["q6"]).explain_analyze(timeout=300)
    lines = [ln for ln in text.splitlines() if ln.startswith("    spans: ")]
    assert len(lines) == 2
    assert all("fragment.read" in ln and "ms" in ln for ln in lines)


def test_mux_fans_spans_out_and_isolates_failures():
    class Broken(QueryObserver):
        def on_span(self, *a):
            raise RuntimeError("observer fault")

    log = SpanLog()
    mux = ObserverMux([Broken(), log, ConsoleObserver()])
    mux.on_span("q", 0, 2, "fragment.read", 10, 20)
    assert log.events == [("q", 0, 2, "fragment.read", 10, 20)]
    # the base hook is a no-op
    assert QueryObserver().on_span("q", 0, -1, "coord.admit", 1, 2) is None


def test_platform_reports_each_admission_wait():
    plat = FaasPlatform(quota=2)
    waits = []
    try:
        out = plat.invoke_many(lambda spec: ({"ok": 1}, 0.0),
                               [{"fragment": f} for f in range(5)],
                               pipeline=0,
                               on_admit=lambda s, e: waits.append((s, e)))
    finally:
        plat.close()
    assert len(out) == 5 and len(waits) == 5
    assert all(0 <= e - s for s, e in waits)
