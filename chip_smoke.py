#!/usr/bin/env python3
"""Run the TPC-H query path once on one TPU chip and check its answers.

    python3 chip_smoke.py [--seed 0] [--sf 1]

Generates TPC-H at ``--sf`` from ``--seed`` in this process, then submits
nine queries through the client entry point (``repro.api.connect`` →
``session.submit``) with the planner's default 32 MiB of input per
worker and the result cache off, so every query runs its whole fleet:
TPC-H q1, q3, q6, q12, q14 and q19, plus three that reach the remaining
dispatch kernels (a sort-strategy aggregate, a grouped min/max over
dictionary groups, and the selective repartition join whose probe
scan takes the build side's Bloom filter). Each result is compared
with the NumPy oracle (``repro.sql.oracle``) on the same data.

One line per query: status, rows, the largest relative error of its
float columns, the kernels that ran with their fragment counts, and the
seconds spent tracing and compiling. The last line of standard output
is the device record ``{"ok": true, "device": {...}}``; it is printed
only when every query succeeded, matched the oracle, and every fused
kernel that ran was compiled by Mosaic. Without a TPU the script exits
non-zero before any query runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# Float aggregates accumulate in f32 on the chip (Mosaic has no f64):
# a lane partial that sums n values carries a relative error of at most
# about n * 2^-24, and at SF 1 no lane sums more than ~12k rows
# (1.5M-row groups over 128 lanes), which bounds it near 7e-4.
# Integers, keys and counts (exact in f32 below 2^24) must match exactly.
FLOAT_RTOL = 1e-3

# every kernel the dispatcher (repro.exec.lower) can pick
DISPATCH_KERNELS = ("filter_agg", "groupby_onehot", "segmented_minmax",
                    "join_probe_agg", "sort_agg", "topk", "bloom_filter")

# Integer group keys take the sort strategy. The resident sort network
# holds at most 64k rows, so a partition of lineitem or orders at 32 MiB
# per worker (millions of rows) takes the XLA sort; supplier fits.
SORT_AGG_SQL = ("select s_nationkey, sum(s_acctbal) as balance, "
                "count(*) as n_suppliers, min(s_acctbal) as min_balance "
                "from supplier group by s_nationkey")
MINMAX_SQL = ("select l_returnflag, min(l_quantity) as min_qty, "
              "max(l_extendedprice) as max_price from lineitem "
              "group by l_returnflag")
# the selective join of tests/test_semijoin.py (SELECTIVE_JOIN)
SEMIJOIN_SQL = ("select l_orderkey, sum(l_extendedprice) as rev "
                "from lineitem, orders "
                "where l_orderkey = o_orderkey and o_totalprice > 500000 "
                "group by l_orderkey")


# (name, SQL — None for the TPC-H query of that name, joins repartitioned)
SMOKE_QUERIES = ([(q, None, False)
                  for q in ("q1", "q3", "q6", "q12", "q14", "q19")]
                 + [("sort_agg", SORT_AGG_SQL, False),
                    ("minmax", MINMAX_SQL, False),
                    # repartitioned, as in the semijoin bench suite, so
                    # the cost gate can push the build side's filter down
                    ("semijoin", SEMIJOIN_SQL, True)])


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def _tables(store, catalog) -> dict:
    """Every table as NumPy columns, read back from the store."""
    import numpy as np
    from repro.storage import InputHandler
    ih = InputHandler(store)
    out = {}
    for name, meta in catalog.tables.items():
        parts = [ih.read_table(f)[0] for f in meta.files]
        out[name] = {c.name: np.concatenate([p[c.name] for p in parts])
                     for c in meta.schema}
    return out


def _oracle(catalog, tables, sql: str) -> dict:
    from repro.sql import oracle
    from repro.sql.logical import Binder
    from repro.sql.parser import parse
    from repro.sql.rules import optimize
    lqp, _ = Binder(catalog).bind(parse(sql))
    return oracle.run(optimize(lqp), tables)


def compare(got: dict, want: dict) -> tuple[str | None, float]:
    """(mismatch or None, largest relative error over float columns).
    Rows align by the integer columns (group keys, codes, counts)."""
    import numpy as np
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}", 0.0
    n = len(next(iter(want.values())))
    if len(next(iter(got.values()))) != n:
        return (f"{len(next(iter(got.values())))} rows, oracle has {n}",
                0.0)
    exact = [c for c in sorted(want) if want[c].dtype.kind in "iub"]
    og = np.lexsort([np.asarray(got[c]) for c in exact[::-1]]) \
        if exact else np.arange(n)
    ow = np.lexsort([want[c] for c in exact[::-1]]) \
        if exact else np.arange(n)
    worst = 0.0
    for c in sorted(want):
        g = np.asarray(got[c], np.float64)[og]
        w = np.asarray(want[c], np.float64)[ow]
        if c in exact:
            if not np.array_equal(g, w):
                return f"column {c} differs from the oracle", worst
            continue
        rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-12)
        worst = max(worst, float(rel.max(initial=0.0)))
        if worst > FLOAT_RTOL:
            return (f"column {c}: relative error {worst:.3g} > "
                    f"{FLOAT_RTOL}"), worst
    return None, worst


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling programs."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def run(sf: float, seed: int, queries=SMOKE_QUERIES
        ) -> tuple[bool, list[dict]]:
    """Run ``queries``; (all ok, one record per query)."""
    from repro.api import connect
    from repro.compile_cache import enable_compile_cache
    from repro.core import CoordinatorConfig
    from repro.sql.physical import PlannerConfig
    from repro.sql.queries import QUERIES

    enable_compile_cache()
    clock = _CompileClock()

    t0 = time.perf_counter()
    plain = connect(config=CoordinatorConfig(use_result_cache=False),
                    seed=seed)
    catalog = plain.ensure_tpch(sf=sf, seed=seed)
    gen_s = time.perf_counter() - t0
    shuffled = connect(plain.store, catalog, config=CoordinatorConfig(
        planner=PlannerConfig(broadcast_threshold_bytes=1),
        use_result_cache=False), seed=seed)
    tables = _tables(plain.store, catalog)
    print(f"tpch sf={sf:g} seed={seed}: generated in {gen_s:.1f}s",
          flush=True)

    records, ok = [], True
    try:
        for name, sql, repartition in queries:
            sql = sql or QUERIES[name]
            session = shuffled if repartition else plain
            c0, w0 = clock.total, time.perf_counter()
            handle = session.submit(sql)
            rec = {"query": name}
            try:
                res = handle.result()
            except Exception as e:     # QueryFailedError and kin
                rec.update(status=handle.state.value,
                           error=f"{type(e).__name__}: {e}")
                ok = False
                records.append(rec)
                print(json.dumps(rec), flush=True)
                continue
            got = res.fetch(session.store)
            want = _oracle(catalog, tables, sql)
            mismatch, worst = compare(got, want)
            kernels: dict[str, int] = {}
            interpreted = 0
            for p in res.stats.pipelines:
                for k, n in p.kernel_runs.items():
                    kernels[k] = kernels.get(k, 0) + n
                interpreted += p.kernel_interpreted
            rec.update(status=handle.state.value,
                       rows=len(next(iter(got.values()))),
                       max_rel_err=worst, kernels=kernels,
                       compile_s=round(clock.total - c0, 3),
                       wall_s=round(time.perf_counter() - w0, 3))
            if mismatch:
                rec["mismatch"] = mismatch
            if interpreted:
                rec["interpreted_fragments"] = interpreted
            ok = ok and handle.state.value == "SUCCEEDED" \
                and not mismatch and not interpreted
            records.append(rec)
            print(json.dumps(rec), flush=True)
    finally:
        shuffled.close()
        plain.close()
    return ok, records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sf", type=float, default=1.0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform!r}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

    ok, records = run(args.sf, args.seed)
    if not ok:
        bad = [r["query"] for r in records
               if r.get("status") != "SUCCEEDED" or "mismatch" in r
               or "error" in r or "interpreted_fragments" in r]
        return _fail(f"queries failed or disagree with the oracle: {bad}")
    ran = {k for r in records for k in r["kernels"]}
    if set(DISPATCH_KERNELS) - ran:
        return _fail(f"kernels never ran: "
                     f"{sorted(set(DISPATCH_KERNELS) - ran)}")
    print(f"kernels compiled and run: {sorted(ran)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
