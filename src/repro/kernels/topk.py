"""Fused top-k selection for ORDER BY … LIMIT — Pallas TPU kernel.

Final pipelines with both sort keys and a LIMIT only ever surface
``limit`` rows, yet the generic path ships the full filtered batch to the
host sorter. This kernel sorts the whole VMEM-resident batch with the
bitonic network (descending keys are per-key direction flips, invalid
rows sort last) and masks everything past the first ``limit`` survivors,
so the fragment emits at most ``limit`` valid rows. The coordinator's
final host sort still runs — the network's position tiebreak gives the
same stable tie order as ``np.lexsort``, making the pre-selection exactly
idempotent under it.

Capacity must be a power of two of at least ``ROW_TILE`` rows and fit
the roofline resident cap; the dispatch wrapper falls back to the
generic path otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (LANES, ROW_TILE, SUBLANES, lane_types,
                                  resident_spec, to_lanes)
from repro.kernels.sortnet import (bitonic_sort, copy_tiles, load_tile,
                                   store_tile, tile_index)


def _topk_kernel(*refs, n_sort: int, directions, limit: int):
    n_arr = (len(refs) - 3) // 2                 # columns + mask
    ins, outs = refs[:n_arr + 1], refs[n_arr + 1:2 * n_arr + 1]
    inv_ref, pos_ref = refs[-2:]
    # sort a working copy: the outputs themselves plus the invalid flag
    copy_tiles([inv_ref, *outs], ins)
    bitonic_sort([inv_ref, *outs], pos_ref, num_keys=1 + n_sort,
                 directions=[1] + list(directions))
    mask_ref = outs[-1]

    def keep(t, c):
        glob = tile_index(t)[1]
        store_tile(mask_ref, t, ((load_tile(mask_ref, t) != 0)
                                 & (glob < limit)).astype(jnp.int32))
        return c
    jax.lax.fori_loop(0, mask_ref.shape[0] // SUBLANES, keep, 0)


def fused_topk(columns: dict, mask, *, pred, sort_keys, limit: int,
               interpret: bool = False):
    """Sort by ``sort_keys`` ([(name, desc), …]) and keep the top
    ``limit`` valid rows. Returns ``(out_cols, out_mask)`` at input
    capacity: columns in sorted order, mask true only on the first
    ``limit`` survivors. ``pred`` folds into the validity mask."""
    n = int(mask.shape[0])
    assert n & (n - 1) == 0 and n >= ROW_TILE, \
        f"topk needs a power-of-two capacity >= {ROW_TILE}: {n}"
    m = mask
    if pred is not None:
        m = m & pred(columns)
    key_names = [name for name, _ in sort_keys]
    directions = tuple(-1 if desc else 1 for _, desc in sort_keys)
    carry = [c for c in columns if c not in key_names]
    names = tuple(key_names + carry)
    arrs = to_lanes([columns[c] for c in names], interpret)
    inv = (~m).astype(jnp.int32)

    shape = (n // LANES, LANES)
    spec = resident_spec(shape)
    n_arr = len(arrs) + 1                            # columns + mask
    out_shape = ([jax.ShapeDtypeStruct(shape, a.dtype) for a in arrs]
                 + [jax.ShapeDtypeStruct(shape, jnp.int32)])
    with lane_types(interpret):
        res = pl.pallas_call(
            functools.partial(_topk_kernel, n_sort=len(sort_keys),
                              directions=directions, limit=limit),
            grid=(1,),
            in_specs=[spec] * (1 + n_arr),
            out_specs=[spec] * n_arr,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM(shape, jnp.int32)] * 2,
            interpret=interpret,
        )(*[a.reshape(shape) for a in [inv, *arrs, m.astype(jnp.int32)]])
    out = {c: r.reshape(n).astype(columns[c].dtype)
           for c, r in zip(names, res[:-1])}
    return out, res[-1].reshape(n) != 0
