"""Vectorized physical operator implementations (jnp, static shapes).

Each factory takes plan-time parameters and returns a pure function over
(columns: dict[str, array], mask: bool array) pairs, so a fragment's whole
operator chain composes into one jit-compiled XLA program. Data-dependent
cardinalities are carried in the mask; outputs are capacity-bounded.

Push-based vectorized execution per the paper (section 3.3), adapted to the
TPU's static-shape world: a "batch" is the fragment's full block and
operators push columns through fused element-wise/segment computations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.exec.batch import (compact, expand, lanes_of, renamed_lanes,
                              shift_ahead, shift_behind)
from repro.exec.expr import compile_expr
from repro.kernels.common import NEUTRAL
from repro.sql import ast

INT64_SENTINEL = np.iinfo(np.int64).max

Cols = dict[str, jnp.ndarray]


# -- hashing (numpy/jnp twins; must agree bit-for-bit) -------------------------

_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB


def hash64_jnp(x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(_M1)
    x = (x ^ (x >> 27)) * jnp.uint64(_M2)
    return x ^ (x >> 31)


def hash64_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(_M1)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(_M2)
    return x ^ (x >> np.uint64(31))


def combine_hash_jnp(cols: list[jnp.ndarray]) -> jnp.ndarray:
    h = hash64_jnp(cols[0])
    for c in cols[1:]:
        h = hash64_jnp(h ^ hash64_jnp(c))
    return h


def combine_hash_np(cols: list[np.ndarray]) -> np.ndarray:
    h = hash64_np(cols[0])
    for c in cols[1:]:
        h = hash64_np(h ^ hash64_np(c))
    return h


# -- row-wise operators --------------------------------------------------------

def make_filter(pred: ast.Expr):
    fn = compile_expr(pred)

    def op(cols: Cols, mask):
        return cols, mask & fn(cols)
    return op


def make_project(exprs: list[tuple[str, ast.Expr]]):
    fns = [(name, e, compile_expr(e)) for name, e in exprs]

    def op(cols: Cols, mask):
        out = {}
        for name, e, f in fns:
            if isinstance(e, ast.Col) and e.name not in cols:
                # a byte string: its word lanes, under the new name
                lanes = lanes_of(cols, e.name)
                out.update(zip(renamed_lanes(lanes, name),
                               (cols[c] for c in lanes)))
                continue
            v = f(cols)
            if not hasattr(v, "shape") or v.shape != mask.shape:
                v = jnp.broadcast_to(jnp.asarray(v), mask.shape)
            out[name] = v
        return out, mask
    return op


# -- aggregation ----------------------------------------------------------------

def _neutral(fn: str):
    return NEUTRAL[fn]


def mixed_radix_strides(sizes: list[int]) -> list[int]:
    """Strides assigning each group-key combination a unique id in
    [0, prod(sizes)) — shared by the jnp direct aggregation and the
    fused one-hot kernel path so group codes agree bit-for-bit."""
    strides = []
    acc = 1
    for s in reversed(sizes):
        strides.append(acc)
        acc *= s
    return list(reversed(strides))


def decode_group_ids(group_cols: list[str], sizes: list[int],
                     K: int) -> Cols:
    """Reconstruct the group-key columns from the mixed-radix ids."""
    ids = jnp.arange(K)
    return {c: ((ids // s) % size).astype(jnp.int64)
            for c, s, size in zip(group_cols,
                                  mixed_radix_strides(sizes), sizes)}


def make_direct_agg(group_cols: list[str], sizes: list[int],
                    aggs: list[tuple[str, str, ast.Expr | None]]):
    """Group keys with a small known domain: group id = mixed-radix code.

    Output has exactly K = prod(sizes) rows (one per potential group),
    masked to groups with at least one input row. MXU-friendly: the
    segment sums lower to one-hot matmuls / scatter-adds.
    """
    K = int(np.prod(sizes)) if group_cols else 1
    strides = mixed_radix_strides(sizes)
    agg_fns = [(name, fn, compile_expr(arg) if arg is not None else None)
               for name, fn, arg in aggs]

    def op(cols: Cols, mask):
        if group_cols:
            gid = jnp.zeros(mask.shape, jnp.int32)
            for c, s in zip(group_cols, strides):
                gid = gid + cols[c].astype(jnp.int32) * s
            gid = jnp.where(mask, gid, 0)
        else:
            gid = jnp.zeros(mask.shape, jnp.int32)
        maskf = mask.astype(jnp.float64)
        out: Cols = dict(decode_group_ids(group_cols, sizes, K))
        present = jax.ops.segment_sum(maskf, gid, num_segments=K)
        for name, fn, argf in agg_fns:
            if fn == "count":
                out[name] = jax.ops.segment_sum(maskf, gid, num_segments=K)
            else:
                v = argf(cols).astype(jnp.float64)
                if v.shape != mask.shape:
                    v = jnp.broadcast_to(v, mask.shape)
                if fn == "sum":
                    out[name] = jax.ops.segment_sum(
                        v * maskf, gid, num_segments=K)
                elif fn == "min":
                    out[name] = jax.ops.segment_min(
                        jnp.where(mask, v, jnp.inf), gid, num_segments=K)
                elif fn == "max":
                    out[name] = jax.ops.segment_max(
                        jnp.where(mask, v, -jnp.inf), gid, num_segments=K)
        if not group_cols:
            out_mask = jnp.ones((1,), bool) if K == 1 else None
        else:
            out_mask = present > 0
        return out, out_mask
    return op, K


def make_sort_agg(group_cols: list[str],
                  aggs: list[tuple[str, str, ast.Expr | None]]):
    """General grouped aggregation: lexicographic sort + segment reduce.

    Output capacity equals input capacity (#groups ≤ #rows); invalid rows
    sort last via a leading invalid flag and produce masked-out segments.
    Integer keys group as int64, float keys as themselves, and a byte
    string by its word lanes.
    """
    agg_fns = [(name, fn, compile_expr(arg) if arg is not None else None)
               for name, fn, arg in aggs]

    def op(cols: Cols, mask):
        n = mask.shape[0]
        inv = (~mask).astype(jnp.int32)
        key_cols = [c for g in group_cols for c in lanes_of(cols, g)]
        keys = [cols[c] if jnp.issubdtype(cols[c].dtype, jnp.floating)
                else cols[c].astype(jnp.int64) for c in key_cols]
        vals = []
        for name, fn, argf in agg_fns:
            if fn == "count":
                vals.append(mask.astype(jnp.float64))
            else:
                v = argf(cols).astype(jnp.float64)
                if v.shape != mask.shape:
                    v = jnp.broadcast_to(v, mask.shape)
                vals.append(v)
        operands = [inv] + keys + vals + [mask]
        res = jax.lax.sort(operands, num_keys=1 + len(keys),
                           is_stable=False)
        s_inv = res[0]
        s_keys = res[1:1 + len(keys)]
        s_vals = res[1 + len(keys):-1]
        s_mask = res[-1]
        diff = s_inv[1:] != s_inv[:-1]
        for k in s_keys:
            diff = diff | (k[1:] != k[:-1])
        flags = jnp.concatenate([jnp.ones((1,), bool), diff])
        seg = jnp.cumsum(flags) - 1
        out: Cols = {}
        for c, k in zip(key_cols, s_keys):
            top = jnp.inf if jnp.issubdtype(k.dtype, jnp.floating) \
                else INT64_SENTINEL
            out[c] = jax.ops.segment_min(jnp.where(s_mask, k, top), seg,
                                         num_segments=n)
        maskf = s_mask.astype(jnp.float64)
        for (name, fn, _), v in zip(agg_fns, s_vals):
            if fn in ("sum", "count"):
                out[name] = jax.ops.segment_sum(v * maskf, seg,
                                                num_segments=n)
            elif fn == "min":
                out[name] = jax.ops.segment_min(
                    jnp.where(s_mask, v, jnp.inf), seg, num_segments=n)
            elif fn == "max":
                out[name] = jax.ops.segment_max(
                    jnp.where(s_mask, v, -jnp.inf), seg, num_segments=n)
        out_mask = jax.ops.segment_max(s_mask, seg, num_segments=n)
        return out, out_mask
    return op


MERGE_FN = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def merge_aggs_spec(aggs: list[tuple[str, str, ast.Expr | None]]):
    """Aggregation spec for merging partial states: re-aggregate the partial
    accumulator columns with the merge function (avg was already decomposed
    into sum/count by the binder)."""
    return [(name, MERGE_FN[fn], ast.Col(name)) for name, fn, _ in aggs]


# -- join -----------------------------------------------------------------------

# A probe row's binary search costs one dependent gather of the sorted build
# keys per halving, ceil(log2 B) in all, and one gather per payload lane; the
# sort-merge moves every row of both sides through two sorts and two doubling
# scans. On the TPU a gathered element costs about as much as this many rows
# of the merge (PERF.md, the join threshold measurement).
MERGE_ROW_COST = 4


def join_merges(probe_rows: int, build_rows: int) -> bool:
    """Whether a join of these static capacities takes the sort-merge
    (else the binary search): a large probe side merges, a small probe
    side against a large build side searches."""
    steps = max(1, (build_rows - 1).bit_length())
    return probe_rows * steps > MERGE_ROW_COST * (probe_rows + build_rows)


def make_pk_join_probe(probe_key: str, build_key: str,
                       payload_cols: list[str]):
    """FK→PK equi-join: each probe row meets the build row of its key.

    Build keys are unique (PK) so each probe row matches ≤ 1 build row.
    Output occupies the probe block; misses/invalid rows are masked out.
    The plan follows the static capacities (``join_merges``): a sort-merge
    of both sides, or a binary search of the sorted build side. A payload
    lane that is the build key itself is the probe key on every hit.
    """

    def op(probe_cols: Cols, probe_mask, build_cols: Cols, build_mask):
        bk = jnp.where(build_mask, build_cols[build_key].astype(jnp.int64),
                       INT64_SENTINEL)
        pk = probe_cols[probe_key].astype(jnp.int64)
        lanes = [lane for c in payload_cols for lane in lanes_of(build_cols, c)
                 if lane not in probe_cols and lane != build_key]
        probe = _merge_probe if join_merges(pk.shape[0], bk.shape[0]) \
            else _search_probe
        hit, got = probe(pk, bk, [build_cols[lane] for lane in lanes])
        out = dict(probe_cols)
        out.update(zip(lanes, got))
        if build_key in payload_cols and build_key not in probe_cols:
            out[build_key] = pk.astype(build_cols[build_key].dtype)
        return out, hit & probe_mask & (pk != INT64_SENTINEL)
    return op


def _search_probe(pk, bk, payload):
    """(hit, payload lanes at the probe rows): a binary search of the
    sorted build keys per probe row, then a gather per lane."""
    order = jnp.argsort(bk)
    sk = bk[order]
    pos = jnp.clip(jnp.searchsorted(sk, pk), 0, sk.shape[0] - 1)
    sel = order[pos]
    return sk[pos] == pk, [v[sel] for v in payload]


def _merge_probe(pk, bk, payload):
    """(hit, payload lanes at the probe rows) with no gather or scatter
    over the probe side, in two one-key sorts. The first sorts both
    sides' keys; the build rows' payload, gathered over the build side in
    key order, is spread out to them (``expand``); each probe row takes
    the key and payload of the build row in its key's run; the second
    sort brings the probe rows back to their order. An unstable sort
    leaves the build row anywhere in its run, so the run's probe rows
    look for it both behind and ahead of them. Only 32-bit sort keys
    carry payload: a 64-bit key with payload compiles for minutes."""
    P, B = pk.shape[0], bk.shape[0]
    key = jnp.concatenate([bk, pk])
    row = jnp.concatenate([-1 - jax.lax.iota(jnp.int32, B),
                           jax.lax.iota(jnp.int32, P)])
    key, row = jax.lax.sort((key, row), num_keys=1, is_stable=False)
    build = row < 0
    lanes = []
    if payload:
        order = compact({"b": -1 - row}, build)[0]["b"][:B]
        spread = expand({i: jnp.concatenate([v[order],
                                             jnp.zeros((P,), v.dtype)])
                         for i, v in enumerate(payload)}, build)
        lanes = [spread[i] for i in range(len(payload))]
    behind = _carry(build, [key, *lanes], shift_behind)
    ahead = _carry(build, [key, *lanes], shift_ahead)
    from_behind = behind[0] & (behind[1] == key)
    hit = from_behind | (ahead[0] & (ahead[1] == key))
    got = [jnp.where(from_behind, b, a)
           for b, a in zip(behind[2:], ahead[2:])]
    # probe order: the probe row is the key, its hit the lowest bit; the
    # build rows sort last and are cut off
    dest = jnp.where(build, 2 * P, 2 * row + hit.astype(jnp.int32))
    dest, *got = jax.lax.sort((dest, *got), num_keys=1, is_stable=False)
    return (dest[:P] & 1) == 1, [g[:P] for g in got]


def _carry(src, values, shift):
    """(whether a ``src`` row was found, *values at it): for every row, the
    nearest ``src`` row at or behind it (``shift`` = ``shift_behind``) or at
    or ahead of it (``shift_ahead``). A doubling scan of slices and selects."""
    found, n, s = src, src.shape[0], 1
    while s < n:
        values = [jnp.where(found, v, shift(v, s)) for v in values]
        found = found | shift(found, s)
        s *= 2
    return [found, *values]


# -- exchange partitioning -------------------------------------------------------

def make_hash_partitioner(key_cols: list[str], n_dest: int):
    """Appends a __dest column (hash of the key columns mod n_dest)."""

    def op(cols: Cols, mask):
        h = combine_hash_jnp([cols[c] for c in key_cols])
        dest = (h % jnp.uint64(n_dest)).astype(jnp.int32)
        out = dict(cols)
        out["__dest"] = jnp.where(mask, dest, -1)
        return out, mask
    return op


def np_key_hash(columns: dict[str, np.ndarray],
                key_cols: list[str]) -> np.ndarray:
    """Combined uint64 key hash — shared by destination routing and the
    per-partition distinct-key sketches workers emit for the adaptive
    re-optimizer."""
    return combine_hash_np([columns[c] for c in key_cols])


def np_hash_dest(columns: dict[str, np.ndarray], key_cols: list[str],
                 n_dest: int) -> np.ndarray:
    h = np_key_hash(columns, key_cols)
    return (h % np.uint64(n_dest)).astype(np.int32)


_NP_MERGE = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def np_combine_partials(cols: dict[str, np.ndarray], group_cols: list[str],
                        aggs: list[tuple[str, str]]) -> dict[str, np.ndarray]:
    """Re-combine mergeable partial-aggregate states on the host.

    The multi-level exchange's merge wave collapses the partial states of
    its producer group before re-partitioning: rows sharing a group key
    are folded with the merge function of each aggregate column (sums
    add, counts were already decomposed to sums, min/max reduce). Order-
    independent up to float rounding, like the downstream merge_agg.
    """
    n = len(next(iter(cols.values()))) if cols else 0
    if n == 0:
        return cols
    if not group_cols:
        return {name: _NP_MERGE[fn].reduce(cols[name], keepdims=True)
                for name, fn in aggs}
    keys = [cols[c] for c in group_cols]
    order = np.lexsort(keys[::-1])
    skeys = [k[order] for k in keys]
    diff = np.zeros(n, bool)
    for k in skeys:
        diff[1:] |= k[1:] != k[:-1]
    diff[0] = True
    starts = np.flatnonzero(diff)
    out = {c: k[starts] for c, k in zip(group_cols, skeys)}
    for name, fn in aggs:
        out[name] = _NP_MERGE[fn].reduceat(cols[name][order], starts)
    return out


# -- distinct-key sketches (KMV) -------------------------------------------------

KMV_K = 32


def kmv_sketch(hashes: np.ndarray, k: int = KMV_K) -> list[int]:
    """K-minimum-values sketch of a uint64 hash column: the ``k``
    smallest *distinct* hash values. Tiny, mergeable, and order-free —
    workers attach one per output partition so the coordinator can
    estimate distinct join/group keys without a second pass."""
    if hashes.size == 0:
        return []
    u = np.unique(hashes)
    return [int(x) for x in u[:k]]


def kmv_merge(sketches: list[list[int]], k: int = KMV_K) -> list[int]:
    """Union of per-worker sketches (min-k of the combined value set)."""
    all_vals = [v for s in sketches for v in s]
    if not all_vals:
        return []
    u = np.unique(np.array(all_vals, dtype=np.uint64))
    return [int(x) for x in u[:k]]


def kmv_estimate(sketch: list[int], k: int = KMV_K) -> int:
    """Distinct-count estimate: exact below ``k`` values, else the
    classic (k-1) / kth-minimum fraction of the uint64 hash space."""
    if len(sketch) < k:
        return len(sketch)
    kth = max(sketch[k - 1], 1)
    return int((k - 1) * (2.0 ** 64) / kth)
