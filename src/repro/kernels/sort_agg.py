"""Sort-path grouped aggregation — Pallas TPU kernel.

For group domains too large (or unknown) for the one-hot kernels the
planner picks the *sort* strategy: lexicographic sort by group keys, then
segment reduces. This kernel runs the whole thing inside one VMEM-resident
grid step: a bitonic sorting network orders ``[invalid] + keys`` (carrying
the aggregate inputs and mask), boundary flags and a log-step segmented
inclusive scan produce per-segment totals on each segment's last row, and
a second bitonic pass — a *placement* sort by destination — compacts those
rows to output positions 0..S-1 with the identity rows parked behind them.
Every output (segment order, empty-segment identities: sentinel keys, 0
sums, ±inf min/max, false mask) matches the generic
``operators.make_sort_agg`` lane for lane.

Everything is selects and rotations (``kernels.sortnet``) — no gathers —
so the network vectorizes on the VPU. The input capacity must be a power
of two of at least ``ROW_TILE`` rows and fit the roofline's
resident-rows cap; the dispatch wrapper falls back to the identical XLA
sort path otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (COMBINE, LANES, NEUTRAL, ROW_TILE,
                                  SUBLANES, acc_dtype, key_dtype,
                                  lane_types, resident_spec)
from repro.kernels.sortnet import (bitonic_sort, copy_tiles, load_tile,
                                   shift, store_tile, tile_index, tile_scan,
                                   to_back, to_front)


def _sort_agg_kernel(*refs, n_keys: int, fns, acc, kdt, n: int):
    n_in = 2 + n_keys + len(fns)             # invalid, keys, values, mask
    ins = refs[:n_in]
    outs = refs[n_in:2 * n_in - 1]           # keys, values, mask
    work = refs[2 * n_in - 1:3 * n_in - 1]
    pos_ref, dest_ref = refs[-2:]
    sentinel = jnp.asarray(jnp.iinfo(kdt).max, kdt)
    n_tiles = n // ROW_TILE

    copy_tiles(work, ins)
    bitonic_sort(work, pos_ref, num_keys=1 + n_keys)
    seg_refs = work[:1 + n_keys]             # invalid flag + group keys
    val_refs, mask_ref = work[1 + n_keys:-1], work[-1]
    idents = [acc(NEUTRAL[fn]) for fn in fns]

    def body(t, carry):
        """One tile of the sorted run: segment boundaries against the
        neighbouring slots (the edge slots read the adjacent tiles),
        then segment ordinals and totals. ``carry`` holds the previous
        tile's last ordinal and totals in slot 0, where they fold into
        a segment that continues across the tile edge."""
        local, glob = tile_index(t)
        first = local == 0
        prev_t = jnp.maximum(t - 1, 0)
        next_t = jnp.minimum(t + 1, n_tiles - 1)
        flags = glob == 0
        is_last = glob == n - 1
        keys = []
        for r in seg_refs:
            k = load_tile(r, t)
            keys.append(k)
            prev = jnp.where(first, to_front(load_tile(r, prev_t)),
                             shift(k, 1, 0, local))
            nxt = jnp.where(local == ROW_TILE - 1,
                            to_back(load_tile(r, next_t)),
                            shift(k, -1, 0, local))
            flags = flags | (k != prev)
            is_last = is_last | (k != nxt)
        heads = flags.astype(jnp.int32)
        seg = tile_scan(jnp.where(first, heads + carry[0], heads),
                        jnp.zeros_like(flags), jnp.add, 0, local)
        s_mask = load_tile(mask_ref, t) != 0
        totals = []
        for fn, r, c, ident, o in zip(fns, val_refs, carry[1:], idents,
                                      outs[n_keys:-1]):
            v = load_tile(r, t)
            if fn in ("sum", "count"):
                v = v * s_mask.astype(acc)
            else:
                v = jnp.where(s_mask, v, ident)
            v = jnp.where(first & ~flags, COMBINE[fn](c, v), v)
            tot = tile_scan(v, flags, COMBINE[fn], ident, local)
            totals.append(to_front(tot))
            # segment-last slots carry the results to their segment's
            # output slot; everything else parks behind as identities
            store_tile(o, t, jnp.where(is_last, tot, ident))
        live = is_last & s_mask
        for o, k in zip(outs[:n_keys], keys[1:]):
            store_tile(o, t, jnp.where(live, k, sentinel))
        store_tile(outs[-1], t, live.astype(jnp.int32))
        store_tile(dest_ref, t, jnp.where(is_last, seg - 1, n))
        return (to_front(seg), *totals)

    shape = (SUBLANES, LANES)
    jax.lax.fori_loop(0, n_tiles, body,
                      (jnp.zeros(shape, jnp.int32),
                       *[jnp.full(shape, i, acc) for i in idents]))
    # placement sort by destination slot
    bitonic_sort([dest_ref, *outs], pos_ref, num_keys=1)


def fused_sort_agg(columns: dict, mask, *, group_cols, pred, aggs,
                   interpret: bool = False):
    """One-pass filtered sort-strategy grouped aggregation.

    Same output contract as ``operators.make_sort_agg`` applied after the
    filters: ``(out_cols, out_mask)`` at input capacity, group keys
    with sentinel-filled empty segments. ``pred`` folds into the validity
    mask (filtered rows sort last as invalid). Capacity must be a power
    of two of at least ``ROW_TILE`` rows (callers go through
    ``bucket_capacity``).
    """
    acc = acc_dtype(interpret)
    kdt = key_dtype(interpret)
    n = int(mask.shape[0])
    assert n & (n - 1) == 0 and n >= ROW_TILE, \
        f"sort_agg needs a power-of-two capacity >= {ROW_TILE}: {n}"
    m = mask
    if pred is not None:
        m = m & pred(columns)
    inv = (~m).astype(jnp.int32)
    keys = [columns[c].astype(kdt) for c in group_cols]
    fns = []
    vals = []
    for _, fn, argf in aggs:
        fns.append(fn)
        if fn == "count":
            vals.append(m.astype(acc))
        else:
            v = jnp.asarray(argf(columns), acc)
            vals.append(jnp.broadcast_to(v, m.shape).astype(acc))
    fns = tuple(fns)

    shape = (n // LANES, LANES)
    spec = resident_spec(shape)
    ins = [inv, *keys, *vals, m.astype(jnp.int32)]
    out_shape = ([jax.ShapeDtypeStruct(shape, kdt) for _ in keys]
                 + [jax.ShapeDtypeStruct(shape, acc) for _ in vals]
                 + [jax.ShapeDtypeStruct(shape, jnp.int32)])
    scratch = ([pltpu.VMEM(shape, a.dtype) for a in ins]
               + [pltpu.VMEM(shape, jnp.int32)] * 2)
    with lane_types(interpret):
        res = pl.pallas_call(
            functools.partial(_sort_agg_kernel, n_keys=len(keys), fns=fns,
                              acc=acc, kdt=kdt, n=n),
            grid=(1,),
            in_specs=[spec] * len(ins),
            out_specs=[spec] * len(out_shape),
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*[a.reshape(shape) for a in ins])
    out_keys = res[:len(keys)]
    out_vals = res[len(keys):-1]
    out = {c: k.reshape(n) for c, k in zip(group_cols, out_keys)}
    for (name, _, _), v in zip(aggs, out_vals):
        out[name] = v.reshape(n)
    return out, res[-1].reshape(n) != 0
