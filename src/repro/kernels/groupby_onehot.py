"""One-hot grouped aggregation (TPC-H Q1) — Pallas TPU kernel.

For group keys with a small known domain K (Q1: returnflag × linestatus,
K = 6), grouped aggregation needs no hash table and no scatter: each
128-row stripe of a block compares its group ids against a (K, 128)
iota — the one-hot mask — and folds every aggregate input under that
mask into a persistent (K, 128) partial tile, one per aggregate, on the
VPU. Sums and counts add, min/max (the ``segmented_minmax`` dispatch
name) take ``minimum``/``maximum`` against ±inf identities, so absent
groups keep exactly the identities ``jax.ops.segment_min/max`` give on
the generic path. After the grid the 128 lane partials reduce to the
(K, A+1) result with a trailing per-group presence count.

:func:`fused_groupby` is the generic kernel behind the engine's dispatch
layer (``repro.exec.lower``): predicate, group-id, and aggregate-input
expressions are compiled jnp closures evaluated inside the kernel body,
so a matched scan→filter→partial_agg(grouped) fragment filters and
aggregates in one streaming pass. :func:`groupby_onehot` is the
fixed-layout benchmark entry point written against it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (COMBINE, NEUTRAL, REDUCE, LANES,
                                  SUBLANES, acc_dtype, lane_types,
                                  resident_spec, row_block, row_spec,
                                  to_lanes, to_rows)

BLOCK_ROWS = 1024


def group_tile_shape(aggs, n_groups: int) -> tuple:
    """(A+1, K padded to whole sublanes, 128) lane-partial tile."""
    return (len(aggs) + 1, -(-n_groups // SUBLANES) * SUBLANES, LANES)


def init_group_tile(o_ref, aggs, acc) -> None:
    """Seed each aggregate's partials with its identity (presence: 0)."""
    for j, (fn, _) in enumerate(aggs):
        o_ref[j] = jnp.full(o_ref.shape[1:], NEUTRAL[fn], acc)
    o_ref[len(aggs)] = jnp.zeros(o_ref.shape[1:], acc)


def update_group_tile(o_ref, m, gid, cols, aggs, acc) -> None:
    """Fold one block's surviving rows into the partial tile.

    ``m`` is the surviving-row mask, ``gid`` the raw group ids, both
    ``(rows, 128)``; masked rows get gid -1 — an all-false one-hot
    column — so they reach no group. Shared with the fused join-probe
    kernel."""
    gid = jnp.where(m, gid.astype(jnp.int32), -1)
    kp = o_ref.shape[1]
    groups = jax.lax.broadcasted_iota(jnp.int32, (kp, LANES), 0)
    vals = []
    for fn, argf in aggs:
        if fn == "count":
            vals.append(None)
        else:
            v = jnp.broadcast_to(jnp.asarray(argf(cols), acc), gid.shape)
            vals.append(v.astype(acc))
    tiles = [o_ref[j] for j in range(len(aggs) + 1)]
    for r in range(gid.shape[0]):          # one 128-row stripe at a time
        onehot = gid[r:r + 1, :] == groups                  # (kp, 128)
        hit = onehot.astype(acc)
        for j, ((fn, _), v) in enumerate(zip(aggs, vals)):
            if v is None:
                tiles[j] = tiles[j] + hit
            else:
                tiles[j] = COMBINE[fn](tiles[j], jnp.where(
                    onehot, v[r:r + 1, :], acc(NEUTRAL[fn])))
        tiles[-1] = tiles[-1] + hit
    for j, t in enumerate(tiles):
        o_ref[j] = t


def reduce_group_tile(tile, aggs, n_groups: int) -> jnp.ndarray:
    """(A+1, Kp, 128) partials → (K, A+1) per-group results."""
    fns = [fn for fn, _ in aggs] + ["count"]
    return jnp.stack([REDUCE[fn](tile[j, :n_groups], axis=1)
                      for j, fn in enumerate(fns)], axis=1)


def _fused_groupby_kernel(*refs, names, pred, gid_fn, aggs, acc):
    *col_refs, mask_ref, o_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        init_group_tile(o_ref, aggs, acc)

    cols = {n: r[...] for n, r in zip(names, col_refs)}   # (rows, 128)
    m = mask_ref[...] != 0
    if pred is not None:
        m = m & pred(cols)
    update_group_tile(o_ref, m, gid_fn(cols), cols, aggs, acc)


def fused_groupby(columns: dict, mask, *, pred, gid_fn, aggs,
                  n_groups: int, block: int = BLOCK_ROWS,
                  interpret: bool = False) -> jnp.ndarray:
    """One-pass filtered grouped aggregation over named column blocks.

    ``gid_fn`` maps the column dict to mixed-radix group ids in
    [0, n_groups); ``aggs`` is a list of ``(fn, argf)`` with fn in
    {sum, count, min, max}. Returns (n_groups, A+1): the A aggregate
    columns (absent groups hold the identity: 0 for sum/count, ±inf for
    min/max) plus a trailing per-group presence count (rows surviving
    the filter).
    """
    acc = acc_dtype(interpret)
    names = tuple(columns)
    block = row_block(mask.shape[0], block)
    arrs, m, nb = to_rows(to_lanes([columns[c] for c in names], interpret),
                          mask, block)
    shape = group_tile_shape(aggs, n_groups)
    with lane_types(interpret):
        tile = pl.pallas_call(
            functools.partial(_fused_groupby_kernel, names=names,
                              pred=pred, gid_fn=gid_fn, aggs=aggs, acc=acc),
            grid=(nb,),
            in_specs=[row_spec(block)] * (len(names) + 1),
            out_specs=resident_spec(shape),
            out_shape=jax.ShapeDtypeStruct(shape, acc),
            interpret=interpret,
        )(*arrs, m)
    return reduce_group_tile(tile, aggs, n_groups)


def groupby_onehot(group_ids, values, *, n_groups: int,
                   block: int = BLOCK_ROWS,
                   interpret: bool = False) -> jnp.ndarray:
    """group_ids: (n,) int in [0, n_groups); values: (n, A) float.
    Returns (n_groups, A) grouped sums (append a ones column for counts).
    """
    n, A = values.shape
    cols = {"gid": group_ids}
    cols.update({f"v{j}": values[:, j] for j in range(A)})
    aggs = [("sum", lambda c, j=j: c[f"v{j}"]) for j in range(A)]
    tile = fused_groupby(cols, jnp.ones((n,), bool), pred=None,
                         gid_fn=lambda c: c["gid"], aggs=aggs,
                         n_groups=n_groups, block=block,
                         interpret=interpret)
    return tile[:, :A]
