"""Semi-join filter pushdown: Bloom filter kernels, the cost gate, the
pilot-K adopt/revoke loop, and end-to-end filtered-vs-unfiltered parity.

The correctness invariant under test everywhere: a Bloom filter has no
false negatives, so a filtered probe produces exactly the rows an
unfiltered probe produces — filters only change *where* rows die (on the
scanning worker instead of after the shuffle), never *which* rows
survive the join.
"""

import numpy as np
import pytest

from repro.api import ChaosConfig, ChaosEngine, connect
from repro.core import CoordinatorConfig, FaasPlatform, QueryCoordinator
from repro.core.cost import CostModel
from repro.core.engine import explain_analyze
from repro.core.registry import ResultRegistry
from repro.data import generate_tpch
from repro.kernels import bloom
from repro.sql.physical import PlannerConfig
from repro.storage import ObjectStore

# a selective build side: few orders pass the price predicate, so most
# lineitem probe rows have no join partner and die at the filter
SELECTIVE_JOIN = (
    "select l_orderkey, sum(l_extendedprice) as rev "
    "from lineitem, orders "
    "where l_orderkey = o_orderkey and o_totalprice > 500000 "
    "group by l_orderkey")

PLANNER = dict(bytes_per_worker=250_000, broadcast_threshold_bytes=1,
               exchange_partitions=3)


def _coordinator(store, catalog, *, semijoin=True, pipelined=False,
                 adaptive=False, seed=1, quota=1000):
    cfg = CoordinatorConfig(
        planner=PlannerConfig(semijoin=semijoin, **PLANNER),
        use_result_cache=False, calibrate_selectivity=False,
        pipelined=pipelined, adaptive=adaptive)
    return QueryCoordinator(store, catalog,
                            platform=FaasPlatform(seed=seed, quota=quota),
                            config=cfg)


def _force_enable(plan, flag=True):
    """Override the plan-time cost verdict (sf=0.01 is far below the
    gate's break-even scale; the plumbing is the system under test)."""
    for p in plan.pipelines.values():
        if p.params.semijoin:
            p.params.semijoin["enabled"] = flag


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


# -- filter primitives ---------------------------------------------------------

@pytest.mark.parametrize("n_keys", [100, 5_000, 200_000])
def test_bloom_no_false_negatives_and_fpr_bound(n_keys):
    rng = np.random.default_rng(n_keys)
    keys = rng.choice(np.arange(4 * n_keys, dtype=np.uint32),
                      size=n_keys, replace=False)
    bits = bloom.bloom_bits_for(n_keys)
    words = bloom.bloom_build(keys, bits)
    # every inserted key hits — the no-false-negative guarantee
    assert bloom.bloom_probe_np(keys, words, bits).all()
    # non-members pass at roughly the theoretical rate
    others = np.setdiff1d(
        rng.integers(4 * n_keys, 2**31, 4 * n_keys).astype(np.uint32),
        keys)
    fpr = bloom.bloom_probe_np(others, words, bits).mean()
    want = bloom.bloom_fpr(n_keys, bits)
    assert fpr <= max(3.0 * want, 0.01), (fpr, want)


def test_bloom_bits_pow2_and_clamped():
    for n in (0, 1, 7, 1000, 10**9):
        bits = bloom.bloom_bits_for(n)
        assert bits & (bits - 1) == 0
        assert bloom.BLOOM_MIN_BITS <= bits <= bloom.BLOOM_MAX_BITS
    assert bloom.bloom_bits_for(10**9) == bloom.BLOOM_MAX_BITS


def test_bloom_merge_equals_single_build():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 2**31, 9_000).astype(np.uint32)
    bits = bloom.bloom_bits_for(keys.size)
    merged = bloom.bloom_merge(
        [bloom.bloom_build(part, bits)
         for part in np.array_split(keys, 7)])
    np.testing.assert_array_equal(merged, bloom.bloom_build(keys, bits))


def test_bloom_wire_roundtrip():
    words = bloom.bloom_build(np.arange(500, dtype=np.uint32),
                              bloom.bloom_bits_for(500))
    wire = bloom.bloom_to_wire(words, mode="hash64")
    assert isinstance(wire["words"], bytes)      # msgpack-safe
    back = bloom.bloom_from_wire(wire)
    assert back["bits"] == words.size * 32
    assert back["mode"] == "hash64"
    np.testing.assert_array_equal(back["words"], words)


@pytest.mark.parametrize("n_rows", [900, 3000, 12000])
def test_probe_np_jnp_pallas_bit_parity(n_rows):
    """All three probe paths share one hash family — the masks must be
    bit-identical, not just statistically alike."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n_rows)
    members = rng.integers(0, 50_000, 2_000).astype(np.uint32)
    bits = bloom.bloom_bits_for(members.size)
    words = bloom.bloom_build(members, bits)
    probe = rng.integers(0, 100_000, n_rows).astype(np.int64)

    m_np = bloom.bloom_probe_np(probe.astype(np.uint32), words, bits)
    m_jnp = np.asarray(bloom.bloom_probe_jnp(
        jnp.asarray(probe), jnp.asarray(words), bits=bits))
    m_pls = np.asarray(bloom.fused_bloom_filter(
        {"key": jnp.asarray(probe)}, jnp.ones(n_rows, dtype=bool),
        pred=None, key="key", words=words, bits=bits, interpret=True))
    np.testing.assert_array_equal(m_np, m_jnp)
    np.testing.assert_array_equal(m_np, m_pls)


# -- the cost gate -------------------------------------------------------------

def test_semijoin_benefit_monotone_in_match_fraction():
    cm = CostModel()
    args = dict(producers=64, n_dest=32, probe_bytes=2e9,
                build_distinct=50_000)
    benefits = [cm.semijoin_benefit(match_fraction=m, **args)
                ["benefit_cents"] for m in (0.01, 0.1, 0.5, 0.9, 1.0)]
    assert all(a >= b for a, b in zip(benefits, benefits[1:]))
    # a selective filter over a big probe pays for itself…
    assert benefits[0] > 0
    # …a PK-FK join (every probe row matches) never does
    assert benefits[-1] < 0


def test_l0_tier_choice_prefers_express_for_small_hot_intermediates():
    cm = CostModel()
    assert cm.l0_tier_choice(16, 1_000_000) == "s3-express"
    # large long-lived spill: express storage premium dominates
    assert cm.l0_tier_choice(4, 50e9, ttl_s=3600.0) == "s3-standard"


# -- pilot-K adopt / revoke ----------------------------------------------------

def test_reoptimizer_adopts_and_revokes_from_observed_build(tpch_store):
    from repro.core.adaptive import Reoptimizer
    store, catalog = tpch_store
    coord = _coordinator(store, catalog)
    plan = coord.plan_sql(SELECTIVE_JOIN)
    probe = next(p for p in plan.pipelines.values() if p.params.semijoin)
    sj = probe.params.semijoin
    # scale the probe to where the gate's economics actually bite
    probe.params.est_out_bytes = int(2e9)
    reopt = Reoptimizer(CostModel())

    sj["enabled"] = False
    a = reopt.semijoin_decision(probe, build_rows=0.01 * sj["base_rows"])
    assert a is not None and a["kind"] == "semijoin_adopt"
    assert sj["enabled"] and a["match_fraction"] <= 0.02

    a = reopt.semijoin_decision(probe, build_rows=float(sj["base_rows"]))
    assert a is not None and a["kind"] == "semijoin_revoke"
    assert not sj["enabled"] and a["match_fraction"] == 1.0

    # verdict unchanged → no adaptation record (hysteresis, no churn)
    assert reopt.semijoin_decision(
        probe, build_rows=float(sj["base_rows"])) is None


# -- end-to-end ----------------------------------------------------------------

def _run_plan(coord, plan):
    res = coord.execute_plan(plan)
    return res, res.fetch(coord.store)


def _same_draws(store):
    """A view of ``store``'s objects with latency draws of its own, the
    same for every run given one: request counts include the reads that
    a slow simulated first byte re-triggers, so two runs compared on
    requests must not draw from one generator shared with earlier runs."""
    return ObjectStore(store.backend, tier=store.tier, seed=0)


def test_filtered_probe_matches_unfiltered_and_shrinks_shuffle(tpch_store):
    store, catalog = tpch_store
    # quota 1 runs the fragments one at a time, so each run takes its
    # draws in one order
    coord = _coordinator(_same_draws(store), catalog, quota=1)
    plan = coord.plan_sql(SELECTIVE_JOIN)
    _force_enable(plan)
    probe_pid = next(pid for pid, p in plan.pipelines.items()
                     if p.params.semijoin)
    filt, got = _run_plan(coord, plan)

    off = _coordinator(_same_draws(store), catalog, semijoin=False, seed=2,
                       quota=1)
    unf, want = _run_plan(off, off.plan_sql(SELECTIVE_JOIN))

    _assert_same_rows(got, want, "filtered vs unfiltered")

    pf = next(r for r in filt.stats.pipelines if r.pid == probe_pid)
    pu = next(r for r in unf.stats.pipelines if r.pid == probe_pid)
    assert pf.semijoin and pf.semijoin["applied"]
    assert pf.semijoin_killed > 0
    # the acceptance bar: ≥3× fewer probe-side shuffled bytes and
    # strictly fewer storage requests at identical result rows
    assert pu.bytes_written >= 3 * pf.bytes_written, \
        (pu.bytes_written, pf.bytes_written)
    assert sum(r.requests for r in filt.stats.pipelines) < \
        sum(r.requests for r in unf.stats.pipelines)

    text = explain_analyze(plan, filt.stats)
    assert "semijoin: pushed" in text
    assert f"actual={pf.semijoin_killed}" in text


def test_sem_hash_unchanged_by_filter_toggle(tpch_store):
    """Gate-on and gate-off runs must share one result-cache entry: the
    sem hash folds the *build side*, not the verdict."""
    store, catalog = tpch_store
    coord = _coordinator(store, catalog)
    p1 = coord.plan_sql(SELECTIVE_JOIN)
    p2 = coord.plan_sql(SELECTIVE_JOIN)
    _force_enable(p2)
    assert {p.sem_hash for p in p1.pipelines.values()} == \
        {p.sem_hash for p in p2.pipelines.values()}
    # but a semijoin-off *plan* must not collide with the annotated one
    off = _coordinator(store, catalog, semijoin=False)
    p3 = off.plan_sql(SELECTIVE_JOIN)
    probe = next(p for p in p1.pipelines.values() if p.params.semijoin)
    assert probe.sem_hash not in {p.sem_hash
                                  for p in p3.pipelines.values()}


def test_pipelined_pilot_revokes_uneconomic_filter():
    """At sf=0.01 the true benefit is negative: the pilot-K peek at the
    build's partial manifest must revoke a (forced) filter before the
    probe pays the sealed-filter wait — and parity must hold.

    Fresh store: an earlier unfiltered run of the same build pipeline
    would leave a complete bloomless registry entry for the build's sem
    hash, short-circuiting the probe to the (also correct, but
    different) "filter unavailable" fallback."""
    store = ObjectStore(tier="local", seed=0)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    coord = _coordinator(store, catalog, pipelined=True, adaptive=True,
                         seed=3)
    plan = coord.plan_sql(SELECTIVE_JOIN)
    _force_enable(plan)
    res, got = _run_plan(coord, plan)
    pr = next(r for r in res.stats.pipelines if r.semijoin is not None)
    assert not pr.semijoin["applied"]
    assert any(a.get("kind") == "semijoin_revoke" for a in pr.adaptations)

    off = _coordinator(store, catalog, semijoin=False, seed=4)
    _, want = _run_plan(off, off.plan_sql(SELECTIVE_JOIN))
    _assert_same_rows(got, want, "pilot-revoked vs unfiltered")


def test_bloomless_cached_build_falls_back_unfiltered():
    """A build exchange first materialized by an unfiltered query leaves
    a complete registry entry with no published filter. A later probe
    that wants the filter must not wait for one that will never arrive —
    it launches unfiltered against the shared build output."""
    store = ObjectStore(tier="local", seed=0)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    off = _coordinator(store, catalog, semijoin=False, seed=6)
    _, want = _run_plan(off, off.plan_sql(SELECTIVE_JOIN))

    # result cache ON: the build pipeline is adopted from the registry
    # (bloomless) instead of re-executing and re-publishing its filter
    cfg = CoordinatorConfig(
        planner=PlannerConfig(semijoin=True, **PLANNER),
        use_result_cache=True, calibrate_selectivity=False,
        pipelined=False, adaptive=False, semijoin_wait_timeout_s=2.0)
    coord = QueryCoordinator(store, catalog,
                             platform=FaasPlatform(seed=7), config=cfg)
    plan = coord.plan_sql(SELECTIVE_JOIN)
    _force_enable(plan)
    res, got = _run_plan(coord, plan)
    pr = next(r for r in res.stats.pipelines if r.semijoin is not None)
    assert not pr.semijoin["applied"]
    assert pr.semijoin.get("reason") == "filter unavailable"
    _assert_same_rows(got, want, "bloomless cached build")


def test_chaos_kill_at_filter_publish_falls_back_to_parity():
    """A coordinator crash at the moment the merged filter is published
    re-drives the query; the rerun (filtered or not) must return the
    exact unfiltered rows — a lost filter can only cost performance."""
    store = ObjectStore(tier="local", seed=0)
    catalog = generate_tpch(store, sf=0.01, n_parts=4, seed=0)
    cfg = CoordinatorConfig(
        planner=PlannerConfig(**PLANNER), calibrate_selectivity=False,
        pipelined=True, max_attempts=6)
    chaos = ChaosEngine(ChaosConfig(
        kill_points=("registry.publish_filter",)))
    platform = FaasPlatform(quota=16, seed=0)
    session = connect(store, catalog, platform=platform, config=cfg,
                      registry=ResultRegistry(store, claim_ttl_s=0.25),
                      chaos=chaos)
    try:
        res = session.submit(SELECTIVE_JOIN).result(timeout=300)
        with chaos.pause():
            got = res.fetch(store)
    finally:
        session.close()
        platform.close()
    assert chaos.injected.get("kill:registry.publish_filter") == 1

    off = _coordinator(store, catalog, semijoin=False, seed=5)
    _, want = _run_plan(off, off.plan_sql(SELECTIVE_JOIN))
    _assert_same_rows(got, want, "chaos-killed filter publish")
