#!/usr/bin/env python3
"""Device time of the generic join's two plans at the chip cells' shapes.

    python3 benchmarks/join_threshold.py [--reps 5] [--out FILE]

For each (probe, build) capacity pair the benchmark cells run, the
sort-merge and the binary search of ``repro.exec.operators`` join random
keys made on the device (three quarters of the build rows valid, an int64
key and an int32 payload lane), once to compile and then
``--reps`` times. One JSON line per shape: each plan's compile seconds and
median milliseconds, whether their answers agree, and ``merge_row_cost``,
the cost of a merged row in searched gathers at which the two plans break
even (``operators.MERGE_ROW_COST`` is set from these). TPU only: exits 2
elsewhere.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

# (probe, build) capacities: q3's and q12's broadcast joins, Q18's lineitem
# and orders probes of its derived side, Q18's customer join
SHAPES = [(1 << 23, 1 << 21), (1 << 23, 1 << 10), (1 << 21, 1 << 10),
          (1 << 10, 1 << 18)]


def _inputs(P: int, B: int, seed: int = 0):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        bk = jax.random.permutation(k1, 4 * B)[:B].astype(jnp.int64)
        bk = jnp.where(jax.random.uniform(k2, (B,)) < 0.75, bk,
                       jnp.iinfo(jnp.int64).max)
        pk = jax.random.randint(k3, (P,), 0, 4 * B).astype(jnp.int64)
        pay = [jax.random.randint(k4, (B,), 0, 1 << 30, jnp.int32)]
        return pk, bk, pay
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def _time(fn, args, reps: int):
    import jax
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    out = jax.block_until_ready(compiled(*args))
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ms.append((time.perf_counter() - t) * 1e3)
    return out, compile_s, statistics.median(ms), ms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("join_threshold: no TPU", file=sys.stderr)
        return 2
    import numpy as np
    from repro.exec import operators as ops

    lines = []
    for P, B in SHAPES:
        pk, bk, pay = _inputs(P, B)
        (mh, mg), mc, mms, mall = _time(ops._merge_probe, (pk, bk, pay),
                                        args.reps)
        (sh, sg), sc, sms, sall = _time(ops._search_probe, (pk, bk, pay),
                                        args.reps)
        mh, sh = np.asarray(mh), np.asarray(sh)
        agree = bool(np.array_equal(mh, sh)) and all(
            np.array_equal(np.asarray(a)[sh], np.asarray(b)[sh])
            for a, b in zip(mg, sg))
        steps = max(1, (B - 1).bit_length())
        line = {"probe": P, "build": B, "hits": int(sh.sum()),
                "agree": agree,
                "merge": {"compile_s": mc, "ms": mms, "runs_ms": mall},
                "search": {"compile_s": sc, "ms": sms, "runs_ms": sall},
                "merge_row_cost": (mms / (P + B)) / (sms / (P * steps)),
                "merges_now": ops.join_merges(P, B),
                "device": jax.devices()[0].device_kind}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(
            "".join(json.dumps(x) + "\n" for x in lines))
    return 0 if all(x["agree"] for x in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
