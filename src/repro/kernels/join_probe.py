"""Fused broadcast-join probe + partial aggregation — Pallas TPU kernel.

``join → [filter…] → partial_agg`` chains (the Q12/Q14/Q19 shape) probe a
PK build side and immediately aggregate; on the generic path the probe
output materializes as full-width columns before the aggregate consumes
it. Here the *sorted* build side (keys + payload, prepared by one XLA
argsort outside the kernel — identical to ``make_pk_join_probe``) stays
VMEM-resident across every grid step, each probe block runs a vectorized
in-kernel binary search against it, gathers payload for the hits, applies
the residual predicates, and folds straight into the aggregation tile —
the joined relation never leaves VMEM.

The build side is laid out ``(B / 128, 128)`` like the probe columns.
Mosaic gathers only along the lanes of one row, so every probe of the
search and the payload fetch goes through
:func:`~repro.kernels.common.resident_take`, which walks the build rows.
Hit semantics mirror the generic operator exactly:
``sorted_key[pos] == probe_key``, probe row valid, and probe key ≠ the
mask sentinel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (COMBINE, LANES, NEUTRAL, REDUCE,
                                  SUBLANES, acc_dtype, fold_rows, key_dtype,
                                  lane_types, resident_spec, resident_take,
                                  row_block, row_spec, to_lanes, to_rows)
from repro.kernels.groupby_onehot import (group_tile_shape, init_group_tile,
                                          reduce_group_tile,
                                          update_group_tile)


def _lower_bound(sk_ref, pk, n_build: int):
    """Vectorized lower-bound binary search of ``pk`` (rows, 128) in the
    sorted resident keys: first index with sk[i] >= pk, like
    ``jnp.searchsorted(side='left')``. Static trip count."""
    lo = jnp.zeros(pk.shape, jnp.int32)
    hi = jnp.full(pk.shape, n_build, jnp.int32)
    for _ in range(max(int(n_build).bit_length(), 1)):
        active = lo < hi
        mid = (lo + hi) >> 1                     # (lo + hi) // 2
        (mv,), = resident_take([sk_ref], [mid])
        go_right = active & (mv < pk)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def _join_probe_kernel(*refs, names, bnames, pred, probe_key, build_key,
                       gid_fn, aggs, acc, kdt, grouped: bool, n_build: int):
    n_probe_refs = len(names) + 1                 # probe columns + mask
    col_refs = refs[:len(names)]
    mask_ref = refs[len(names)]
    sk_ref = refs[n_probe_refs]
    payload_refs = refs[n_probe_refs + 1:-1]
    o_ref = refs[-1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        if grouped:
            init_group_tile(o_ref, aggs, acc)
        else:
            for j, (fn, _) in enumerate(aggs):
                o_ref[j] = jnp.full((SUBLANES, LANES), NEUTRAL[fn], acc)

    cols = {n: r[...] for n, r in zip(names, col_refs)}   # (rows, 128)
    m = mask_ref[...] != 0
    sentinel = jnp.asarray(jnp.iinfo(kdt).max, kdt)
    pk = cols[probe_key].astype(kdt)
    pos = _lower_bound(sk_ref, pk, n_build)
    pos_c = jnp.clip(pos, 0, n_build - 1)
    found = resident_take([sk_ref, *payload_refs], [pos_c])
    hit = (found[0][0] == pk) & m & (pk != sentinel)
    for bn, (v,) in zip(bnames, found[1:]):      # the hits' payload
        cols[bn] = v
    if build_key is not None:                     # equals the key on hits
        cols[build_key] = found[0][0]
    if pred is not None:
        hit = hit & pred(cols)

    if grouped:
        update_group_tile(o_ref, hit, gid_fn(cols), cols, aggs, acc)
        return
    for j, (fn, argf) in enumerate(aggs):
        if fn == "count":
            v = hit.astype(acc)
        else:
            v = jnp.broadcast_to(jnp.asarray(argf(cols), acc), hit.shape)
            v = jnp.where(hit, v.astype(acc), acc(NEUTRAL[fn]))
        o_ref[j] = COMBINE[fn](o_ref[j], fold_rows(v, fn))


def fused_join_probe_agg(probe_cols: dict, probe_mask, sorted_keys,
                         sorted_payload: dict, *, probe_key: str, pred,
                         gid_fn, aggs, n_groups: int, block: int,
                         build_key: str | None = None,
                         interpret: bool = False) -> jnp.ndarray:
    """One-pass join probe + filter + aggregation.

    ``sorted_keys``/``sorted_payload`` are the build side already sorted
    by join key (masked build rows pushed to the end under the key
    dtype's sentinel — the caller reuses the generic operator's
    preparation). ``build_key`` names a payload column that is the join
    key itself: it is read from the resident keys instead of being held
    in VMEM twice. ``aggs`` fns may be any of {sum, count, min, max}.
    Returns the (A,) accumulator for ungrouped aggregation
    (``n_groups == 0``) or the (K, A+1) group tile with trailing
    presence counts.
    """
    acc = acc_dtype(interpret)
    kdt = key_dtype(interpret)
    names = tuple(probe_cols)
    bnames = tuple(sorted_payload)
    block = row_block(probe_mask.shape[0], block)
    arrs, mask, nb = to_rows(
        to_lanes([probe_cols[c] for c in names], interpret), probe_mask,
        block)
    B = int(sorted_keys.shape[0])
    # pad the build side to whole 128-lane rows; the search never reads
    # past index B-1, so the pad value is irrelevant
    bpad = (-B) % LANES
    build = [sorted_keys.astype(kdt)] + to_lanes(
        [sorted_payload[c] for c in bnames], interpret)
    build = [jnp.pad(a, (0, bpad)).reshape(-1, LANES) for a in build]
    A = len(aggs)
    grouped = bool(n_groups)
    shape = (group_tile_shape(aggs, n_groups) if grouped
             else (A, SUBLANES, LANES))

    with lane_types(interpret):
        out = pl.pallas_call(
            functools.partial(
                _join_probe_kernel, names=names, bnames=bnames, pred=pred,
                probe_key=probe_key, build_key=build_key, gid_fn=gid_fn,
                aggs=aggs, acc=acc,
                kdt=kdt, grouped=grouped, n_build=B),
            grid=(nb,),
            in_specs=([row_spec(block)] * (len(names) + 1)
                      + [resident_spec(b.shape) for b in build]),
            out_specs=resident_spec(shape),
            out_shape=jax.ShapeDtypeStruct(shape, acc),
            interpret=interpret,
        )(*arrs, mask, *build)
    if grouped:
        return reduce_group_tile(out, aggs, n_groups)
    return jnp.stack([REDUCE[fn](out[j]) for j, (fn, _) in enumerate(aggs)])
