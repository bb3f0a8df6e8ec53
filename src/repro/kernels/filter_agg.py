"""Fused filter + aggregate scan — Pallas TPU kernel.

The hot loop of a scan-heavy serverless query worker: evaluate a
conjunctive predicate over columnar blocks and accumulate the aggregates
in one pass — columns stream HBM→VMEM once, no intermediate mask or
filtered column is ever materialized. Grid = row blocks; one (8, 128)
partial tile per aggregate accumulates across sequential grid steps and
is reduced to a scalar after the kernel.

Two entry points:

  * :func:`fused_filter_agg` — the generic kernel behind the engine's
    dispatch layer (``repro.exec.lower``): predicate and aggregate-input
    expressions are compiled jnp closures evaluated *inside* the kernel
    body over the VMEM-resident column tiles, so any matched
    scan→filter→partial_agg fragment runs as one streaming pass;
  * :func:`filter_agg` — TPC-H Q6 (sum(price·discount) + count) written
    against it, kept as the benchmark's fixed-shape entry point.

In interpret mode (CPU CI) the kernel accumulates in float64,
bit-comparable with the generic jnp operator path; on TPU it runs the
same program in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (COMBINE, NEUTRAL, REDUCE, SUBLANES,
                                  LANES, acc_dtype, fold_rows, lane_types,
                                  resident_spec, row_block, row_spec,
                                  to_lanes, to_rows)

BLOCK_ROWS = 2048


def filter_agg(shipdate, discount, quantity, extendedprice, *,
               date_lo: int, date_hi: int, disc_lo: float, disc_hi: float,
               qty_hi: float, block: int = BLOCK_ROWS,
               interpret: bool = False) -> jnp.ndarray:
    """Q6 over 1-D columns of equal length n. Returns (2,):
    [revenue sum, match count]."""
    cols = {"ship": shipdate, "disc": discount, "qty": quantity,
            "price": extendedprice}

    def pred(c):
        return ((c["ship"] >= date_lo) & (c["ship"] < date_hi)
                & (c["disc"] >= disc_lo) & (c["disc"] <= disc_hi)
                & (c["qty"] < qty_hi))

    aggs = [("sum", lambda c: c["price"] * c["disc"]), ("count", None)]
    return fused_filter_agg(cols, jnp.ones(shipdate.shape, bool),
                            pred=pred, aggs=aggs, block=block,
                            interpret=interpret)


def _fused_filter_agg_kernel(*refs, names, pred, aggs, acc):
    *col_refs, mask_ref, o_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for j, (fn, _) in enumerate(aggs):
            o_ref[j] = jnp.full((SUBLANES, LANES), NEUTRAL[fn], acc)

    cols = {n: r[...] for n, r in zip(names, col_refs)}   # (rows, 128)
    m = mask_ref[...] != 0
    if pred is not None:
        m = m & pred(cols)
    for j, (fn, argf) in enumerate(aggs):
        if fn == "count":
            v = m.astype(acc)
        else:
            v = jnp.broadcast_to(jnp.asarray(argf(cols), acc), m.shape)
            v = jnp.where(m, v.astype(acc), acc(NEUTRAL[fn]))
        o_ref[j] = COMBINE[fn](o_ref[j], fold_rows(v, fn))


def fused_filter_agg(columns: dict, mask, *, pred, aggs,
                     block: int = BLOCK_ROWS,
                     interpret: bool = False) -> jnp.ndarray:
    """One-pass ungrouped filter+aggregate over named column blocks.

    ``columns``: dict of equal-length 1-D arrays; ``mask``: bool (n,)
    validity; ``pred``: compiled-expression closure over the column dict
    (or None); ``aggs``: list of ``(fn, argf)`` with fn in
    {sum, count, min, max} and argf a closure (None for count).
    Returns the (A,) accumulator vector.
    """
    acc = acc_dtype(interpret)
    names = tuple(columns)
    block = row_block(mask.shape[0], block)
    arrs, m, nb = to_rows(to_lanes([columns[c] for c in names], interpret),
                          mask, block)
    out_shape = (len(aggs), SUBLANES, LANES)
    with lane_types(interpret):
        out = pl.pallas_call(
            functools.partial(_fused_filter_agg_kernel, names=names,
                              pred=pred, aggs=aggs, acc=acc),
            grid=(nb,),
            in_specs=[row_spec(block)] * (len(names) + 1),
            out_specs=resident_spec(out_shape),
            out_shape=jax.ShapeDtypeStruct(out_shape, acc),
            interpret=interpret,
        )(*arrs, m)
    return jnp.stack([REDUCE[fn](out[j]) for j, (fn, _) in enumerate(aggs)])
