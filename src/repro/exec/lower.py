"""Kernel dispatch: lower matched fragment op trees to fused Pallas kernels.

The physical→fragment compilation path runs every fragment as a generic
jit-compiled jnp operator chain (``repro.exec.fragment``). This module is
the dispatch layer on top: a pattern matcher over the serialized fragment
op tree recognizes supported hot-loop chains and emits a kernel-backed
program with the exact same ``blocks → (columns, mask)`` signature, so the
caller swaps it in transparently and falls back to the generic chain — bit
compatibly — for every unmatched shape.

Matched patterns (paper section 3.3's one-pass vectorized worker loop):

  ``scan → [filter…] → partial_agg/merge_agg`` (direct, no groups)
      → :func:`repro.kernels.ops.fused_filter_agg` — predicate and
        aggregate inputs evaluate inside the kernel over VMEM column
        tiles; one (8, 128) partial tile per aggregate crosses the
        row-block grid. TPC-H Q6 is the canonical instance.

  ``scan → [filter…] → partial_agg/merge_agg`` (direct, K groups)
      → :func:`repro.kernels.ops.fused_groupby` — one-hot masks over
        (K, 128) partial tiles. Dispatched as ``groupby_onehot`` when
        every aggregate is a sum/count (TPC-H Q1), else as
        ``segmented_minmax``, the same kernel with its min/max lanes.

  ``scan → [filter…] → partial_agg`` (sort strategy)
      → :func:`repro.kernels.ops.fused_sort_agg` — fully VMEM-resident
        bitonic sort by group keys plus a segmented scan; large or
        unsized group domains that the one-hot kernels reject.

  ``join(probe=[filter…]→scan, build=scan) → [filter…] → partial_agg``
      → :func:`repro.kernels.ops.fused_join_probe_agg` — the sorted
        build side stays VMEM-resident; each probe block binary-searches
        it in-kernel and folds straight into the aggregation tile
        (TPC-H Q12/Q14/Q19).

  ``final`` with ORDER BY + LIMIT over ``[filter…]→scan``
      → :func:`repro.kernels.ops.fused_topk` — bitonic sort with per-key
        descending directions; only the top ``limit`` rows stay valid,
        and the coordinator's host sort is idempotent on them (Q3's
        final pipeline).

Block sizes and resident capacities are not hand constants: every arm
asks ``repro.analysis.roofline`` for a :class:`KernelTiling` derived from
the kernel's working set, the VMEM budget, and its arithmetic intensity
relative to the machine balance. The tiling key joins the compiled-
program cache key (``dispatch_signature``) and its estimates surface in
EXPLAIN via :func:`kernel_info`.

Lowering is value-semantics-preserving: predicates/arguments are the same
compiled expressions the generic path uses, and in interpret mode (CPU CI)
the kernels accumulate in float64 like the jnp path. ``set_enabled`` /
``disabled()`` switch the layer off globally — used by the parity tests
and the fused-vs-generic benchmark rows.

Adding a new fused kernel: add a match arm in :func:`match_fragment_ex`
(return a :class:`Match` with a roofline tiling, or a precise miss
reason), add the kernel factory under ``repro.kernels`` plus its tiling
model in ``repro.analysis.roofline``, and emit the lowered program in
:func:`lower_fragment`; everything downstream (jit caching, stats,
explain output, the fusion benchmark) picks it up from the returned
:class:`Lowered`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.analysis import roofline
from repro.exec import operators as xops
from repro.exec.expr import compile_expr, expr_from_dict
from repro.exec.operators import decode_group_ids, mixed_radix_strides
from repro.kernels import ops as kops
from repro.kernels import common as kcommon

AGG_FNS = frozenset({"sum", "count", "min", "max"})
ONEHOT_AGG_FNS = frozenset({"sum", "count"})  # pure-matmul groupby subset

# Back-compat aliases (pre-PR9 names).
UNGROUPED_AGG_FNS = AGG_FNS
GROUPED_AGG_FNS = ONEHOT_AGG_FNS

_LEAF_OPS = ("scan_table", "scan_exchange")

_enabled = os.environ.get("SKYRISE_DISABLE_FUSED", "") not in ("1", "true")


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool) -> bool:
    """Toggle kernel dispatch globally; returns the previous setting."""
    global _enabled
    prev, _enabled = _enabled, bool(flag)
    return prev


@contextlib.contextmanager
def disabled():
    """Run a scope on the generic jnp path (parity tests, benchmarks)."""
    prev = set_enabled(False)
    try:
        yield
    finally:
        set_enabled(prev)


_interpret_gate = os.environ.get(
    "SKYRISE_INTERPRET_COST_GATE", "1") not in ("0", "false")


def set_interpret_gate(flag: bool) -> bool:
    """Toggle the interpret-mode cost gate; returns the previous setting."""
    global _interpret_gate
    prev, _interpret_gate = _interpret_gate, bool(flag)
    return prev


@contextlib.contextmanager
def interpret_gate_disabled():
    """Match compute-bound resident kernels even on interpreted backends
    (kernel parity tests exercise them regardless of dispatch policy)."""
    prev = set_interpret_gate(False)
    try:
        yield
    finally:
        set_interpret_gate(prev)


@dataclasses.dataclass
class Match:
    kernel: str                  # kernel name (see module docstring)
    leaf: dict                   # probe-side scan op feeding the chain
    preds: list[dict]            # post-join/agg-level predicate dicts
    group_cols: list[str]
    sizes: list[int]
    aggs: list                   # [name, fn, arg expr dict | None]
    tiling: roofline.KernelTiling
    # join_probe_agg only:
    build_leaf: dict | None = None
    probe_preds: list = dataclasses.field(default_factory=list)
    build_preds: list = dataclasses.field(default_factory=list)
    probe_key: str | None = None
    build_key: str | None = None
    payload: list = dataclasses.field(default_factory=list)
    # topk only:
    sort_keys: list = dataclasses.field(default_factory=list)
    limit: int | None = None
    # bloom_filter only (probe_key doubles as the bloom key column):
    bloom_bits: int | None = None
    bloom_k: int | None = None


def _always(caps: dict) -> bool:
    return True


@dataclasses.dataclass
class Lowered:
    fn: Callable                 # blocks → (columns, mask)
    leaves: list[tuple[str, dict]]
    kernel: str
    tiling: roofline.KernelTiling | None = None
    # leaf capacities ({leaf id: rows}) → whether ``fn`` runs the kernel
    # or its identical-semantics XLA fallback at those capacities
    runs_kernel: Callable[[dict], bool] = _always


def _expr_cols(d: dict, out: set) -> None:
    if d.get("t") == "col":
        out.add(d["name"])
    for v in d.values():
        if isinstance(v, dict):
            _expr_cols(v, out)
        elif isinstance(v, list):
            for x in v:
                if isinstance(x, dict):
                    _expr_cols(x, out)


def _peel_filters(op: dict) -> tuple[list[dict], dict]:
    preds: list[dict] = []
    while op.get("t") == "filter":
        preds.append(op["pred"])
        op = op["child"]
    return preds, op


def _leaf_width(leaf: dict, fallback: int) -> int:
    """Column count feeding a kernel: exact for table scans, an estimate
    for exchange scans whose schema is only known at runtime."""
    if leaf.get("t") == "scan_table":
        return len(leaf["columns"])
    return max(fallback, 1)


def match_fragment_ex(op: dict) -> tuple[Match | None, str | None]:
    """Recognize a fragment op tree one of the fused kernels covers.

    Returns ``(match, None)`` on success or ``(None, miss_reason)`` — a
    short human-readable account of the first structural property that
    disqualified the tree, surfaced in EXPLAIN ANALYZE so erosion of
    match coverage is observable.
    """
    t = op.get("t")
    if t == "final":
        return _match_final(op)
    if t in ("partial_agg", "merge_agg"):
        return _match_agg(op)
    if t == "semijoin_probe":
        return _match_semijoin(op)
    return None, f"no fusible root (op={t})"


def _match_final(op: dict):
    sort_keys = [(k, bool(d)) for k, d in (op.get("sort_keys") or [])]
    limit = op.get("limit")
    if not sort_keys or limit is None:
        return None, "final lacks ORDER BY + LIMIT (no top-k)"
    if op.get("project"):
        return None, "final has a post-project"
    preds, child = _peel_filters(op["child"])
    if child.get("t") not in _LEAF_OPS:
        return None, f"unsupported op under final (op={child.get('t')})"
    needed: set[str] = {k for k, _ in sort_keys}
    for p in preds:
        _expr_cols(p, needed)
    if child["t"] == "scan_table" and not needed <= set(child["columns"]):
        missing = sorted(needed - set(child["columns"]))
        return None, f"columns {missing} absent from scan"
    tiling = roofline.resident_sort_tiling(
        "topk", n_arrays=_leaf_width(child, len(sort_keys) + 4) + 2)
    if _interpret_gate and kcommon.interpret_mode() \
            and roofline.interpret_prefers_jnp(tiling):
        return None, "interpret_cost"
    return Match("topk", child, preds, [], [], [], tiling,
                 sort_keys=sort_keys, limit=int(limit)), None


def _match_semijoin(op: dict):
    """``semijoin_probe`` wrapper (attached by the fragment driver when a
    probe-side spec carries a kernel-eligible Bloom filter): fuse the
    scan chain's predicate with the in-kernel Bloom membership test. The
    filter words arrive as the runtime ``__bloom`` pseudo-leaf — never
    baked into the trace, so the compiled program is shared across
    queries and across filter contents of the same capacity bucket."""
    key = op["key"]
    preds, child = _peel_filters(op["child"])
    if child.get("t") not in _LEAF_OPS:
        return None, (f"semijoin probe over non-scan chain "
                      f"(op={child.get('t')})")
    needed: set[str] = {key}
    for p in preds:
        _expr_cols(p, needed)
    if child["t"] == "scan_table" and not needed <= set(child["columns"]):
        missing = sorted(needed - set(child["columns"]))
        return None, f"columns {missing} absent from scan"
    tiling = roofline.bloom_probe_tiling(
        n_cols=_leaf_width(child, len(needed)), n_bits=int(op["bits"]))
    return Match("bloom_filter", child, preds, [], [], [], tiling,
                 probe_key=key, bloom_bits=int(op["bits"]),
                 bloom_k=int(op["k"])), None


def _match_agg(op: dict):
    strategy = op.get("strategy")
    group_cols = list(op["group_cols"])
    sizes = list(op["sizes"] or [])
    aggs = list(op["aggs"])
    fns = {fn for _, fn, _ in aggs}
    if not fns <= AGG_FNS:
        return None, f"aggregate fns {sorted(fns - AGG_FNS)} unsupported"
    needed: set[str] = set(group_cols)
    for _, _, arg in aggs:
        if arg is not None:
            _expr_cols(arg, needed)
    preds, child = _peel_filters(op["child"])
    for p in preds:
        _expr_cols(p, needed)

    if strategy == "sort":
        if op.get("wide_keys"):
            return None, (f"float or byte-string group keys "
                          f"{op['wide_keys']} (32-bit key lanes)")
        if child.get("t") not in _LEAF_OPS:
            return None, (f"sort-strategy aggregate over non-scan child "
                          f"(op={child.get('t')})")
        if child["t"] == "scan_table" and \
                not needed <= set(child["columns"]):
            missing = sorted(needed - set(child["columns"]))
            return None, f"columns {missing} absent from scan"
        tiling = roofline.resident_sort_tiling(
            "sort_agg", n_arrays=2 + len(group_cols) + len(aggs))
        if _interpret_gate and kcommon.interpret_mode() \
                and roofline.interpret_prefers_jnp(tiling):
            return None, "interpret_cost"
        return Match("sort_agg", child, preds, group_cols, sizes, aggs,
                     tiling), None
    if strategy != "direct":
        return None, f"aggregation strategy {strategy!r} unsupported"

    if group_cols:
        if len(sizes) != len(group_cols):
            return None, "group columns not dict-coded (no sizes)"
        K = int(np.prod(sizes))
        # per-group lane partials live in VMEM: the roofline caps K
        cap = roofline.onehot_group_capacity(len(aggs))
        if K > cap:
            return None, (f"group domain {K} exceeds the one-hot VMEM cap "
                          f"{cap}")
    else:
        K = 0

    if child.get("t") == "join":
        return _match_join(op, child, preds, needed, group_cols, sizes,
                           aggs, K)
    if child.get("t") not in _LEAF_OPS:
        return None, f"unsupported child op {child.get('t')}"
    if child["t"] == "scan_table" and not needed <= set(child["columns"]):
        missing = sorted(needed - set(child["columns"]))
        return None, f"columns {missing} absent from scan"
    if not group_cols:
        kernel = "filter_agg"
        tiling = roofline.filter_agg_tiling(
            n_cols=_leaf_width(child, len(needed)), n_aggs=len(aggs))
    else:
        kernel = ("groupby_onehot" if fns <= ONEHOT_AGG_FNS
                  else "segmented_minmax")
        tiling = roofline.groupby_tiling(
            kernel, n_cols=_leaf_width(child, len(needed)),
            n_aggs=len(aggs), n_groups=K)
    return Match(kernel, child, preds, group_cols, sizes, aggs,
                 tiling), None


def _match_join(op, join, preds, needed, group_cols, sizes, aggs, K):
    probe_preds, probe_leaf = _peel_filters(join["probe"])
    if probe_leaf.get("t") not in _LEAF_OPS:
        return None, (f"join probe side is not a scan chain "
                      f"(op={probe_leaf.get('t')})")
    build_preds, build_leaf = _peel_filters(join["build"])
    if build_leaf.get("t") not in _LEAF_OPS:
        return None, (f"join build side is not a scan chain "
                      f"(op={build_leaf.get('t')})")
    payload = list(join["payload"])
    probe_key, build_key = join["probe_key"], join["build_key"]
    for p in probe_preds:
        _expr_cols(p, needed)
    needed.add(probe_key)
    if probe_leaf["t"] == "scan_table":
        avail = set(probe_leaf["columns"]) | set(payload)
        if not needed <= avail:
            missing = sorted(needed - avail)
            return None, f"columns {missing} absent from join inputs"
    if build_leaf["t"] == "scan_table":
        bneeded = {build_key} | set(payload)
        for p in build_preds:
            _expr_cols(p, bneeded)
        if not bneeded <= set(build_leaf["columns"]):
            missing = sorted(bneeded - set(build_leaf["columns"]))
            return None, f"columns {missing} absent from build scan"
    # a payload column that is the build key is read from the resident
    # sorted keys, not held twice
    tiling = roofline.join_probe_tiling(
        n_cols=_leaf_width(probe_leaf, len(needed)),
        n_payload=len(set(payload) - {build_key}), n_aggs=len(aggs),
        n_groups=K)
    return Match("join_probe_agg", probe_leaf, preds, group_cols, sizes,
                 aggs, tiling, build_leaf=build_leaf,
                 probe_preds=probe_preds, build_preds=build_preds,
                 probe_key=probe_key, build_key=build_key,
                 payload=payload), None


def match_fragment(op: dict) -> Match | None:
    """Recognize a fragment op tree one of the fused kernels covers."""
    m, _ = match_fragment_ex(op)
    return m


def kernel_miss_reason(op: dict) -> str | None:
    """Why ``op`` stays on the generic jnp path (None if it matched)."""
    _, miss = match_fragment_ex(op)
    return miss


def dispatch_signature(op: dict) -> tuple[str, tuple | None, dict]:
    """(kernel name or "", tiling cache key, effective op) for an op tree
    — matching only, no program construction, so the compiled-program
    cache can form its key cheaply. ``final`` ops whose top-k arm misses
    dispatch on their child (the coordinator's host sort still runs)."""
    m, _ = match_fragment_ex(op)
    if m is not None:
        return m.kernel, m.tiling.key, op
    if op.get("t") == "final":
        return dispatch_signature(op["child"])
    return "", None, op


def match_kernel(op: dict) -> str | None:
    """Name of the fused kernel ``op`` lowers to, or None (plan/explain)."""
    kernel, _, _ = dispatch_signature(op)
    return kernel or None


def kernel_info(op: dict) -> dict:
    """Dispatch summary for EXPLAIN: ``{"kernel", "miss", "tiling"}``.

    ``kernel`` is the fused kernel the pipeline will actually run with
    (for ``final`` ops, possibly on the child under the host sort) or
    None; ``miss`` is the miss reason for the op that executes; ``tiling``
    the roofline tiling estimates of the matched kernel.
    """
    m, miss = match_fragment_ex(op)
    if m is None and op.get("t") == "final":
        info = kernel_info(op["child"])
        if info["kernel"] is None:
            # neither arm matched: the final's own reason names the
            # blocker (the child's is just "no fusible root")
            info["miss"] = miss
        return info
    if m is None:
        return {"kernel": None, "miss": miss, "tiling": None}
    return {"kernel": m.kernel, "miss": None,
            "tiling": m.tiling.as_dict()}


def _compile_pred(preds: list[dict]):
    if not preds:
        return None
    fns = [compile_expr(expr_from_dict(p)) for p in preds]

    def pred(cols):
        out = fns[0](cols)
        for f in fns[1:]:
            out = out & f(cols)
        return out
    return pred


def leaf_capacities(blocks: dict) -> dict:
    """{leaf id: capacity} of a fragment's leaf blocks — static at trace
    time, so a program's kernel-or-fallback choice is fixed per shape."""
    return {lid: int(mask.shape[0]) for lid, (_, mask) in blocks.items()}



def _resident_fits(n: int, cap: int) -> bool:
    """Capacities the resident bitonic kernels take: a power of two of
    whole vreg tiles, within the roofline cap."""
    return kcommon.ROW_TILE <= n <= cap and n & (n - 1) == 0


def lower_fragment(op: dict) -> Lowered | None:
    """Build the kernel-backed program for a matched fragment op tree.

    The returned function consumes the same leaf blocks as the generic
    chain and produces outputs identical in names, shapes, dtypes, and
    mask semantics to the generic operators — callers need no
    special-casing beyond swapping the function.
    """
    m = match_fragment(op)
    if m is None:
        return None
    if m.kernel == "topk":
        return _lower_topk(m)
    if m.kernel == "join_probe_agg":
        return _lower_join_probe(op, m)
    if m.kernel == "sort_agg":
        return _lower_sort_agg(op, m)
    if m.kernel == "bloom_filter":
        return _lower_bloom_filter(m)
    return _lower_direct_agg(m)


def _agg_closures(aggs):
    names = [name for name, _, _ in aggs]
    fns = [(fn, compile_expr(expr_from_dict(arg)) if arg is not None
            else None) for _, fn, arg in aggs]
    return names, fns


def _gid_fn(group_cols, sizes):
    strides = mixed_radix_strides(sizes)

    def gid(cols):
        g = jnp.zeros(cols[group_cols[0]].shape, jnp.int32)
        for c, s in zip(group_cols, strides):
            g = g + cols[c].astype(jnp.int32) * s
        return g
    return gid


def _lower_direct_agg(m: Match) -> Lowered:
    pred = _compile_pred(m.preds)
    agg_names, aggs = _agg_closures(m.aggs)
    leaf_id = "in0"
    leaves = [(leaf_id, m.leaf)]
    block = m.tiling.block_rows

    if m.kernel == "filter_agg":
        def fn(blocks):
            cols, mask = blocks[leaf_id]
            acc = kops.fused_filter_agg(cols, mask, pred=pred, aggs=aggs,
                                        block=block)
            out = {name: acc[j].reshape(1).astype(jnp.float64)
                   for j, name in enumerate(agg_names)}
            return out, jnp.ones((1,), bool)
        return Lowered(fn, leaves, m.kernel, m.tiling)

    # grouped: mixed-radix group id over dict-coded key columns, same
    # code assignment as operators.make_direct_agg
    K = int(np.prod(m.sizes))
    gid_fn = _gid_fn(list(m.group_cols), list(m.sizes))
    group_cols, sizes = list(m.group_cols), list(m.sizes)

    def fn(blocks):
        cols, mask = blocks[leaf_id]
        tile = kops.fused_groupby(cols, mask, pred=pred, gid_fn=gid_fn,
                                  aggs=aggs, n_groups=K, block=block)
        out = dict(decode_group_ids(group_cols, sizes, K))
        for j, name in enumerate(agg_names):
            out[name] = tile[:, j].astype(jnp.float64)
        return out, tile[:, -1] > 0
    return Lowered(fn, leaves, m.kernel, m.tiling)


def _lower_sort_agg(op: dict, m: Match) -> Lowered:
    pred = _compile_pred(m.preds)
    aggs3 = [(name, fn, compile_expr(expr_from_dict(arg))
              if arg is not None else None) for name, fn, arg in m.aggs]
    group_cols = list(m.group_cols)
    # identical-semantics XLA path for capacities the resident bitonic
    # network can't take (see _resident_fits)
    generic = xops.make_sort_agg(
        group_cols, [(n, fn, expr_from_dict(a) if a else None)
                     for n, fn, a in m.aggs])
    cap = m.tiling.resident_rows
    leaf_id = "in0"

    def runs(caps):
        return _resident_fits(caps[leaf_id], cap)

    def fn(blocks):
        cols, mask = blocks[leaf_id]
        if not runs(leaf_capacities(blocks)):
            m2 = mask if pred is None else mask & pred(cols)
            return generic(cols, m2)
        out, om = kops.fused_sort_agg(cols, mask, group_cols=group_cols,
                                      pred=pred, aggs=aggs3)
        out = {c: (v.astype(jnp.int64) if c in group_cols
                   else v.astype(jnp.float64)) for c, v in out.items()}
        return out, om
    return Lowered(fn, [(leaf_id, m.leaf)], m.kernel, m.tiling, runs)


def _lower_join_probe(op: dict, m: Match) -> Lowered:
    agg_pred = _compile_pred(m.preds)
    probe_pred = _compile_pred(m.probe_preds)
    build_pred = _compile_pred(m.build_preds)
    # inside the kernel the probe filters evaluate after the payload
    # gather; the conjunction with the hit mask is order-independent
    kernel_pred = _compile_pred(m.probe_preds + m.preds)
    agg_names, aggs = _agg_closures(m.aggs)
    K = int(np.prod(m.sizes)) if m.group_cols else 0
    gid_fn = (_gid_fn(list(m.group_cols), list(m.sizes))
              if m.group_cols else None)
    group_cols, sizes = list(m.group_cols), list(m.sizes)
    probe_key, build_key = m.probe_key, m.build_key
    payload = list(m.payload)
    block, cap = m.tiling.block_rows, m.tiling.resident_rows
    leaves = [("in0", m.leaf), ("in1", m.build_leaf)]
    # identical-semantics XLA path for build sides past the VMEM cap
    join_generic = xops.make_pk_join_probe(probe_key, build_key, payload)
    agg_generic, _ = xops.make_direct_agg(
        group_cols, sizes,
        [(n, fn, expr_from_dict(a) if a else None)
         for n, fn, a in m.aggs])

    def runs(caps):
        return caps["in1"] <= cap

    def fn(blocks):
        pcols, pmask = blocks["in0"]
        bcols, bmask = blocks["in1"]
        if build_pred is not None:
            bmask = bmask & build_pred(bcols)
        if not runs(leaf_capacities(blocks)):
            pm = pmask if probe_pred is None else pmask & probe_pred(pcols)
            jcols, jmask = join_generic(pcols, pm, bcols, bmask)
            if agg_pred is not None:
                jmask = jmask & agg_pred(jcols)
            return agg_generic(jcols, jmask)
        # XLA prepass: sort the build side by key once (masked rows to
        # the end under the sentinel), mirroring make_pk_join_probe
        kdt = kops.join_key_dtype()
        sentinel = jnp.asarray(jnp.iinfo(kdt).max, kdt)
        bk = jnp.where(bmask, bcols[build_key].astype(kdt), sentinel)
        order = jnp.argsort(bk)
        spay = {c: bcols[c][order] for c in payload
                if c not in pcols and c != build_key}
        res = kops.fused_join_probe_agg(
            pcols, pmask, bk[order], spay, probe_key=probe_key,
            pred=kernel_pred, gid_fn=gid_fn, aggs=aggs, n_groups=K,
            block=block, build_key=(build_key if build_key in payload
                                    and build_key not in pcols else None))
        if not K:
            out = {name: res[j].reshape(1).astype(jnp.float64)
                   for j, name in enumerate(agg_names)}
            return out, jnp.ones((1,), bool)
        out = dict(decode_group_ids(group_cols, sizes, K))
        for j, name in enumerate(agg_names):
            out[name] = res[:, j].astype(jnp.float64)
        return out, res[:, -1] > 0
    return Lowered(fn, leaves, m.kernel, m.tiling, runs)


def _lower_bloom_filter(m: Match) -> Lowered:
    """Probe-side scan chain with an in-kernel Bloom membership test.

    The program keeps the generic mask semantics (predicate-surviving
    rows stay valid) and emits the Bloom verdict as the reserved
    ``__bloom_pass`` column, which the fragment program's output step
    (``exec.fragment._compacting``) folds into the mask on the device,
    counting killed rows exactly before partitioning. The jnp fallback
    (``exec.fragment._build``'s ``semijoin_probe`` arm) produces the
    same column bit-for-bit — both paths share one hash family."""
    from repro.kernels.bloom import bloom_probe_jnp
    pred = _compile_pred(m.preds)
    key, bits, k = m.probe_key, m.bloom_bits, m.bloom_k
    block = m.tiling.block_rows
    leaf_id = "in0"

    def runs(caps):
        return caps[leaf_id] > 0

    def fn(blocks):
        cols, mask = blocks[leaf_id]
        words = blocks["__bloom"][0]["words"]
        m2 = mask if pred is None else mask & pred(cols)
        if not runs(leaf_capacities(blocks)):
            hit = bloom_probe_jnp(cols[key], words, bits=bits, k=k) & m2
        else:
            hit = kops.fused_bloom_filter(
                {key: cols[key]}, m2, pred=None, key=key, words=words,
                bits=bits, k=k, block=block)
        out = dict(cols)
        out["__bloom_pass"] = hit.astype(jnp.int32)
        return out, m2
    return Lowered(fn, [(leaf_id, m.leaf),
                        ("__bloom", {"t": "bloom_words"})],
                   "bloom_filter", m.tiling, runs)


def _lower_topk(m: Match) -> Lowered:
    pred = _compile_pred(m.preds)
    sort_keys, limit, cap = list(m.sort_keys), m.limit, m.tiling.resident_rows
    leaf_id = "in0"

    def runs(caps):
        return _resident_fits(caps[leaf_id], cap)

    def fn(blocks):
        cols, mask = blocks[leaf_id]
        if not runs(leaf_capacities(blocks)):
            # host sort handles it — pass the filtered batch through
            return cols, (mask if pred is None else mask & pred(cols))
        return kops.fused_topk(cols, mask, pred=pred,
                               sort_keys=sort_keys, limit=limit)
    return Lowered(fn, [(leaf_id, m.leaf)], m.kernel, m.tiling, runs)
