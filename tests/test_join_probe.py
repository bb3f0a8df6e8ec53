"""The generic PK join (``operators.make_pk_join_probe``): both of its
plans against the plain reference's probe and against each other, the
merge plan's compiled program, and the rule that picks the plan."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.exec  # noqa: F401  (x64)
from repro.api import connect
from repro.exec import fragment
from repro.exec import operators as ops
from repro.exec.batch import Block, compact, expand, from_numpy, to_numpy
from repro.sql import oracle
from repro.sql.logical import LJoin, LScan
from repro.sql.queries import QUERIES

SENTINEL = ops.INT64_SENTINEL
NEAR_BUILD = 1 << 14


def _threshold_probe(build: int) -> int:
    """The smallest power-of-two probe capacity that merges against
    ``build``."""
    p = 1024
    while not ops.join_merges(p, build):
        p *= 2
        assert p <= 1 << 24, "no probe capacity merges"
    return p


def _inputs(case: str, P: int, B: int, seed: int):
    """Probe and build tables with their valid rows, as numpy: duplicate
    and missing probe keys, probe keys at the sentinel, masked rows on
    both sides, a float and a byte-string payload, and the build key
    itself as payload."""
    rng = np.random.default_rng(seed)
    nb = 0 if case == "empty_build" else B * 3 // 4
    bkey = rng.permutation(3 * max(nb, 1))[:nb].astype(np.int64)
    build = {"bk": bkey, "b_price": rng.random(nb),
             "b_name": np.array([b"n%09d" % k for k in bkey], "S10"),
             "b_code": rng.integers(-2**31, 2**31, nb).astype(np.int32)}
    bvalid = np.zeros(B, bool)
    bvalid[:nb] = case != "all_masked"
    bvalid[:nb] &= rng.random(nb) < 0.9
    pkey = rng.integers(0, 3 * max(nb, 1), P).astype(np.int64)
    pkey[rng.random(P) < 0.05] = SENTINEL
    probe = {"fk": pkey, "x": np.arange(P, dtype=np.float64)}
    pvalid = rng.random(P) < 0.9
    return probe, pvalid, build, bvalid


def _run_join(probe, pvalid, build, bvalid, P, B, merges, monkeypatch):
    monkeypatch.setattr(ops, "join_merges", lambda p, b: merges)
    pb, bb = from_numpy(probe, P), from_numpy(build, B)
    op = ops.make_pk_join_probe("fk", "bk", ["b_price", "b_name", "b_code",
                                             "bk"])
    cols, hit = jax.jit(op)(pb.columns, pb.mask & jnp.asarray(pvalid),
                            bb.columns, bb.mask & jnp.asarray(bvalid))
    out, count = compact(cols, hit)
    return to_numpy(Block(out, count=int(count)))


def _reference(probe, pvalid, build, bvalid):
    valid_b = bvalid[:len(build["bk"])]
    tables = {"probe": {c: v[pvalid] for c, v in probe.items()},
              "build": {c: v[valid_b] for c, v in build.items()}}
    plan = LJoin(LScan("probe", tuple(probe)), LScan("build", tuple(build)),
                 "fk", "bk")
    return oracle.run(plan, tables)


@pytest.mark.parametrize("case", ["mixed", "empty_build", "all_masked"])
@pytest.mark.parametrize("side", ["merge", "search"])
def test_both_plans_match_the_reference_either_side_of_the_threshold(
        case, side, monkeypatch):
    P = _threshold_probe(NEAR_BUILD)
    if side == "search":
        P //= 2
    B = NEAR_BUILD
    assert ops.join_merges(P, B) == (side == "merge")
    args = _inputs(case, P, B, seed=P + len(case))
    want = _reference(*args)
    got = {m: _run_join(*args, P, B, m, monkeypatch) for m in (True, False)}
    for plan in got.values():
        assert set(plan) == set(want)
        for c, v in want.items():
            assert plan[c].dtype == v.dtype, c
            assert np.array_equal(plan[c], v), c
    for c in want:
        assert np.array_equal(got[True][c], got[False][c])
    if case == "mixed":
        n = len(want["fk"])
        assert 0 < n < int(args[1].sum())    # hits and misses both
        assert len(np.unique(want["fk"])) < n  # duplicate probe keys


@pytest.mark.parametrize("n", [700, 4096])
def test_expand_puts_the_front_rows_where_the_mask_is_set(n):
    """``expand``, which moves the build side's payload to its rows, is
    ``compact``'s inverse: the i-th set row gets the i-th row, bit for
    bit."""
    rng = np.random.default_rng(n)
    cap = 4096
    cols = {"i8": rng.integers(-2**62, 2**62, cap),
            "f8": rng.standard_normal(cap),
            "i4": rng.integers(-2**31, 2**31, cap).astype(np.int32)}
    cols["f8"][::97] = np.nan
    mask = np.zeros(cap, bool)
    mask[rng.choice(cap, n, replace=False)] = True
    got = jax.jit(expand)({c: jnp.asarray(v) for c, v in cols.items()},
                          jnp.asarray(mask))
    for c, v in cols.items():
        assert np.array_equal(np.asarray(got[c])[mask].view(np.uint8),
                              v[:n].view(np.uint8)), c
    back, count = compact(got, jnp.asarray(mask))
    assert int(count) == n
    for c, v in cols.items():
        assert np.array_equal(np.asarray(back[c])[:n].view(np.uint8),
                              v[:n].view(np.uint8)), c


def test_the_merge_plan_has_no_loop_and_no_probe_sized_gather():
    P, B = 1 << 16, 1 << 12
    assert ops.join_merges(P, B)
    S = jax.ShapeDtypeStruct
    args = ({"fk": S((P,), jnp.int64), "x": S((P,), jnp.float64)},
            S((P,), jnp.bool_),
            {"bk": S((B,), jnp.int64), "y": S((B,), jnp.float64),
             "s#8.0": S((B,), jnp.int32), "s#8.1": S((B,), jnp.int32)},
            S((B,), jnp.bool_))

    def ops_of(fn):
        hlo = jax.jit(fn).lower(*args).compile().as_text()
        found: dict[str, list[int]] = {}
        for line in hlo.splitlines():
            m = re.search(r"\s(while|gather|scatter|sort)\(", line)
            if m:
                dims = [int(d) for d in re.findall(r"\[(\d+)[\],]", line)]
                found.setdefault(m.group(1), []).append(max(dims))
        return found

    merged = ops_of(ops.make_pk_join_probe("fk", "bk", ["y", "s", "bk"]))
    assert "while" not in merged
    # gathers only over the build side, none over the probe side
    assert "scatter" not in merged
    assert all(rows <= B for rows in merged.get("gather", []))
    assert 1 <= len(merged["sort"]) <= 2
    # the search plan at a small probe side keeps its loop and gathers
    searched = ops_of(lambda pc, pm, bc, bm: ops.make_pk_join_probe(
        "fk", "bk", ["y", "s"])({c: v[:1024] for c, v in pc.items()},
                                pm[:1024], bc, bm))
    assert "while" in searched and "gather" in searched


@pytest.mark.parametrize("probe,build,merges", [
    (1 << 23, 1 << 21, True),     # a lineitem fragment ⋈ broadcast orders
    (1 << 23, 1 << 10, True),     # lineitem ⋈ a small derived side
    (1 << 21, 1 << 10, True),     # orders ⋈ a small derived side
    (1 << 10, 1 << 18, False),    # a thousand rows ⋈ customer
    (1 << 15, 1 << 21, False),    # a shuffled partition ⋈ all orders
])
def test_the_plan_follows_the_capacities(probe, build, merges):
    assert ops.join_merges(probe, build) == merges


def test_generic_joins_are_found_with_their_probe_leaves():
    scan = {"t": "scan_table", "table": "t", "columns": [], "zone_preds": []}
    exch = {"t": "scan_exchange", "source": "s", "mode": "all"}
    inner = {"t": "join", "probe": {"t": "filter", "child": scan,
                                    "pred": None},
             "build": exch, "probe_key": "a", "build_key": "b",
             "payload": []}
    outer = {"t": "join", "probe": inner, "build": dict(exch),
             "probe_key": "c", "build_key": "d", "payload": []}
    op = {"t": "partial_agg", "child": outer, "strategy": "sort",
          "group_cols": ["g"], "aggs": []}
    caps = {"in0": 1 << 20, "in1": 1 << 10, "in2": 1 << 22}
    assert fragment._generic_joins(op, caps) == [
        ("in0", ops.join_merges(1 << 20, 1 << 10)),
        ("in0", ops.join_merges(1 << 20, 1 << 22))]
    # a direct aggregation's output has one row per group
    agg = {"t": "merge_agg", "child": exch, "strategy": "direct",
           "group_cols": ["g", "h"], "sizes": [3, 4], "aggs": []}
    small = {"t": "join", "probe": agg, "build": dict(exch),
             "probe_key": "g", "build_key": "k", "payload": []}
    assert fragment._generic_joins(small, {"in0": 1 << 20,
                                           "in1": 1 << 20}) == [
        ("in0", ops.join_merges(12, 1 << 20))]


def test_each_generic_join_counts_its_probe_rows():
    """q3 at ``connect()``'s defaults probes the broadcast orders and
    customer sides in one lineitem pipeline: two generic joins, each
    counting the lineitem rows under the plan its capacities chose."""
    s = connect(tier="local", quota=8)
    try:
        s.ensure_tpch(sf=0.01, n_parts=4)
        h = s.submit(QUERIES["q3"])
        h.result()
        pipes = h.stats().pipelines
        merged = sum(p.counters["join_merge_rows"] for p in pipes)
        searched = sum(p.counters["join_search_rows"] for p in pipes)
        lineitem = s.catalog.table("lineitem").rows
        assert merged + searched == 2 * lineitem
        joining = [p for p in pipes if p.counters["join_merge_rows"]
                   + p.counters["join_search_rows"]]
        assert [p.ops.count("join") for p in joining] == [2]
        assert merged == 2 * lineitem     # a lineitem fragment merges
    finally:
        s.close()
