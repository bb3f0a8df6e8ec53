"""In-kernel sorting-network and segmented-scan primitives.

Shared by the fused sort-aggregation and top-k kernels: a bitonic sort
in place over VMEM-resident power-of-two arrays and a segmented inclusive
scan, both built from rotations (``pltpu.roll``), static masks and
element-wise selects — no gathers — so they lower to Mosaic and
vectorize on the VPU.

Arrays are ``(rows, 128)`` refs in the row layout of ``kernels.common``
(element ``i`` at ``[i // 128, i % 128]``), ``rows`` a power of two and
a multiple of 8, so they split into (8, 128) vreg tiles of
``ROW_TILE`` elements. The compare-exchange partner at distance j (a
power of two) is index ``i ^ j``: below 128 it sits ``j`` lanes away in
the same row, below ``ROW_TILE`` ``j / 128`` rows away in the same tile
— one rotation each way and a select on bit j — and from ``ROW_TILE``
on it is the same slot of the tile ``j / ROW_TILE`` tiles away. Every
network step is a ``fori_loop`` over tiles or tile pairs, so the
program grows with log² of the capacity, not with the capacity.

A trailing original-position key makes the comparison a total order,
which (a) removes the classic duplicate-key corruption of select-based
bitonic networks and (b) makes the sort *stable* — the same tie order
``np.lexsort`` produces, which the top-k kernel relies on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, ROW_TILE, SUBLANES, flat_index

_TILE_BITS = ROW_TILE.bit_length() - 1


def _roll(a: jnp.ndarray, shift: int, axis: int) -> jnp.ndarray:
    """``jnp.roll`` along ``axis`` (shift may be negative)."""
    return pltpu.roll(a, shift % a.shape[axis], axis)


def load_tile(ref, t) -> jnp.ndarray:
    """The (8, 128) tile ``t`` of a ``(rows, 128)`` ref."""
    return ref[pl.ds(pl.multiple_of(t * SUBLANES, SUBLANES), SUBLANES), :]


def store_tile(ref, t, v) -> None:
    ref[pl.ds(pl.multiple_of(t * SUBLANES, SUBLANES), SUBLANES), :] = v


def tile_index(t) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(local, global) element index of every slot of tile ``t``."""
    local = flat_index((SUBLANES, LANES))
    return local, local + t * ROW_TILE


def to_front(tile: jnp.ndarray) -> jnp.ndarray:
    """Rotate a tile so its last slot lands on the first."""
    return _roll(_roll(tile, 1, 1), 1, 0)


def to_back(tile: jnp.ndarray) -> jnp.ndarray:
    """Rotate a tile so its first slot lands on the last."""
    return _roll(_roll(tile, -1, 1), -1, 0)


def copy_tiles(dsts: list, srcs: list) -> None:
    """``dst[...] = src[...]`` for each pair, one tile per loop step."""
    def body(t, c):
        for d, s in zip(dsts, srcs):
            store_tile(d, t, load_tile(s, t))
        return c
    jax.lax.fori_loop(0, dsts[0].shape[0] // SUBLANES, body, 0)


def _partner(a: jnp.ndarray, j: int, idx: jnp.ndarray) -> jnp.ndarray:
    """Value at index ``i ^ j`` within a tile (j < ``ROW_TILE``)."""
    axis, d = (1, j) if j < LANES else (0, j // LANES)
    return jnp.where((idx & j) == 0, _roll(a, -d, axis), _roll(a, d, axis))


def shift(a: jnp.ndarray, d: int, fill, idx: jnp.ndarray) -> jnp.ndarray:
    """Element ``i - d`` at every index i of a tile (``d`` ± a power of
    two below ``ROW_TILE``; a negative ``d`` reads ahead); ``fill``
    where that falls off either end of the tile."""
    e = abs(d)
    if e % LANES == 0:
        out = _roll(a, d // LANES, 0)
    else:
        # within a row the lanes rotate; the ``e`` lanes that wrapped
        # take their value from the neighbouring row instead
        assert e < LANES, d
        lane = idx & (LANES - 1)
        rl = _roll(a, d, 1)
        if d > 0:
            out = jnp.where(lane >= e, rl, _roll(rl, 1, 0))
        else:
            out = jnp.where(lane < LANES - e, rl, _roll(rl, -1, 0))
    inside = (idx >= d) if d > 0 else (idx < a.size + d)
    return jnp.where(inside, out, fill)


def _lex_less(keys, partner_keys, directions) -> jnp.ndarray:
    """Strict lexicographic self < partner, honoring per-key direction
    (+1 ascending, -1 descending). Built backwards so key 0 dominates."""
    less = jnp.zeros(keys[0].shape, bool)
    for k, pk, d in zip(keys[::-1], partner_keys[::-1],
                        directions[::-1]):
        lt = (k < pk) if d >= 0 else (k > pk)
        less = lt | ((k == pk) & less)
    return less


def bitonic_sort(refs: list, pos_ref, num_keys: int,
                 directions: list | None = None) -> None:
    """Sort equal-shape ``(rows, 128)`` refs in place, lexicographically
    by the first ``num_keys``; the rest are carried along. ``pos_ref``
    (int32, same shape) is scratch for the original position, which
    breaks ties — the sort is stable."""
    n_tiles = refs[0].shape[0] // SUBLANES
    n = n_tiles * ROW_TILE
    assert n & (n - 1) == 0 and n_tiles >= 1, \
        f"bitonic sort needs a power-of-two number of tiles, got {n}"
    dirs = list(directions or []) + [1] * (num_keys - len(directions or []))
    refs = list(refs) + [pos_ref]
    kdirs = dirs[:num_keys] + [1]
    keys = lambda arrs: arrs[:num_keys] + [arrs[-1]]

    def init(t, c):
        store_tile(pos_ref, t, tile_index(t)[1])
        return c
    jax.lax.fori_loop(0, n_tiles, init, 0)

    def in_tile(steps):
        """Compare-exchange steps ``(stage, j)``, j < ROW_TILE: tiles
        are independent, so each loads once and runs them all."""
        def body(t, c):
            local, glob = tile_index(t)
            vals = [load_tile(r, t) for r in refs]
            for stage, j in steps:
                asc = (glob & (2 << stage)) == 0
                partners = [_partner(v, j, local) for v in vals]
                less = _lex_less(keys(vals), keys(partners), kdirs)
                keep_small = ((local & j) == 0) == asc
                # the total order (position tiebreak) makes `less` exactly
                # inverted on the partner slot, so min/max selects agree
                take_self = keep_small == less
                vals = [jnp.where(take_self, v, p)
                        for v, p in zip(vals, partners)]
            for r, v in zip(refs, vals):
                store_tile(r, t, v)
            return c
        jax.lax.fori_loop(0, n_tiles, body, 0)

    def across_tiles(stage: int, j: int):
        """Compare-exchange at distance j >= ROW_TILE: whole tiles
        ``d = j / ROW_TILE`` apart swap slot for slot."""
        lg = (j >> _TILE_BITS).bit_length() - 1

        def body(p, c):
            left = ((p >> lg) << (lg + 1)) | (p & ((1 << lg) - 1))
            right = left + (1 << lg)
            a = [load_tile(r, left) for r in refs]
            b = [load_tile(r, right) for r in refs]
            asc = (tile_index(left)[1] & (2 << stage)) == 0
            swap = _lex_less(keys(a), keys(b), kdirs) != asc
            for r, x, y in zip(refs, a, b):
                store_tile(r, left, jnp.where(swap, y, x))
                store_tile(r, right, jnp.where(swap, x, y))
            return c
        jax.lax.fori_loop(0, n_tiles // 2, body, 0)

    n_stages = n.bit_length() - 1                # block size 2^(stage+1)
    in_tile([(s, 1 << sub) for s in range(min(_TILE_BITS, n_stages))
             for sub in range(s, -1, -1)])
    for s in range(_TILE_BITS, n_stages):
        for sub in range(s, _TILE_BITS - 1, -1):
            across_tiles(s, 1 << sub)
        in_tile([(s, 1 << sub) for sub in range(_TILE_BITS - 1, -1, -1)])


def tile_scan(vals: jnp.ndarray, heads: jnp.ndarray, combine, identity,
              idx: jnp.ndarray) -> jnp.ndarray:
    """Segmented *inclusive* scan within one tile (Hillis–Steele, log
    steps of static shifts): ``heads`` marks segment starts; each
    segment's total lands on its last slot."""
    flag = heads.astype(jnp.int32)
    d = 1
    while d < ROW_TILE:
        shifted = shift(vals, d, identity, idx)
        blocked = shift(flag, d, 1, idx)
        vals = jnp.where(flag != 0, vals, combine(vals, shifted))
        flag = flag | blocked
        d *= 2
    return vals
