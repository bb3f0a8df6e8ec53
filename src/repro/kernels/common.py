"""Helpers shared by the generic fused kernels (the dispatch targets).

Single source of truth for the interpret-mode decision, the accumulation
and key dtype policy, the row layout Mosaic tiles, and aggregation
neutral elements — the jnp operator path (``repro.exec.operators``) and
the fused kernels must agree on these or their numerics silently diverge.

Row layout: a 1-D column of ``n`` rows is padded to whole row blocks and
reshaped to ``(rows / 128, 128)``, so a block of ``block`` rows is a
dense ``(block / 128, 128)`` tile — ``block`` a multiple of
:data:`ROW_TILE` keeps every block a whole number of (8, 128) vregs.
Row ``r`` lives at ``[r // 128, r % 128]``.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEUTRAL = {"sum": 0.0, "count": 0.0, "min": float("inf"),
           "max": float("-inf")}
COMBINE = {"sum": jnp.add, "count": jnp.add, "min": jnp.minimum,
           "max": jnp.maximum}
REDUCE = {"sum": jnp.sum, "count": jnp.sum, "min": jnp.min, "max": jnp.max}

LANES = 128
SUBLANES = 8
ROW_TILE = SUBLANES * LANES      # rows in one (8, 128) 32-bit vreg


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: on any backend but a
    TPU. The one place this is decided — the dispatcher, the kernel
    wrappers and the fragment stats all ask here."""
    return jax.default_backend() != "tpu"


def acc_dtype(interpret: bool):
    """Interpret mode runs on host XLA where f64 matches the generic jnp
    path exactly; Mosaic has no f64, so on-TPU accumulation is f32."""
    if interpret and jax.config.jax_enable_x64:
        return jnp.float64
    return jnp.float32


def key_dtype(interpret: bool):
    """Join/sort key lane dtype: int64 on the host-XLA interpret path
    (bit-parity with the generic operators and their int64 sentinel),
    int32 on TPU where Mosaic has no 64-bit lanes."""
    if interpret and jax.config.jax_enable_x64:
        return jnp.int64
    return jnp.int32


def lane_types(interpret: bool):
    """Context to trace a kernel in. On the chip x64 is off inside it,
    so literals and promotions in the compiled expression closures stay
    32-bit (Mosaic has no 64-bit lanes); interpret mode keeps x64 for
    bit-parity with the generic path."""
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def to_lanes(arrs, interpret: bool):
    """Cast columns to the chip's lane types (f32 / int32); interpret
    mode keeps them as they are."""
    if interpret:
        return list(arrs)
    return [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
            else a.astype(jnp.int32) for a in arrs]


def row_block(n: int, block: int) -> int:
    """Rows per grid step: ``block`` rounded down to whole vreg tiles,
    at least one tile, and no more tiles than ``n`` rows fill."""
    fill = -(-max(n, 1) // ROW_TILE) * ROW_TILE
    return max(ROW_TILE, min(block // ROW_TILE * ROW_TILE, fill))


def to_rows(arrs, mask, block: int):
    """Zero-pad 1-D columns + validity mask to whole ``block``-row blocks
    and lay them out as ``(rows / 128, 128)``; returns (arrs, int32 mask,
    n_blocks). Pad rows are masked out."""
    n = mask.shape[0]
    pad = (-n) % block
    if pad:
        arrs = [jnp.pad(a, (0, pad)) for a in arrs]
        mask = jnp.pad(mask, (0, pad))
    return ([a.reshape(-1, LANES) for a in arrs],
            mask.astype(jnp.int32).reshape(-1, LANES), (n + pad) // block)


def row_spec(block: int) -> pl.BlockSpec:
    """Grid step ``i`` sees rows ``[i*block, (i+1)*block)``."""
    return pl.BlockSpec((block // LANES, LANES), lambda i: (i, 0))


def resident_spec(shape) -> pl.BlockSpec:
    """The whole array, resident in VMEM across every grid step."""
    return pl.BlockSpec(tuple(shape), lambda i: (0,) * len(shape))


def flat_index(shape) -> jnp.ndarray:
    """Row number of every element of a ``(rows, 128)`` tile."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def fold_rows(v, fn: str):
    """Reduce a ``(rows, 128)`` tile to one (8, 128) vreg of partials."""
    return REDUCE[fn](v.reshape(-1, SUBLANES, LANES), axis=0)


def resident_take(tables, idxs):
    """``[[t.ravel()[i] for i in idxs] for t in tables]`` for
    ``(rows, 128)`` tables resident in VMEM and ``(r, 128)`` int32 index
    tiles.

    Mosaic gathers only along the lanes of one row, so the loop walks
    the table rows: each lane-gathers its candidates and keeps them where
    the index falls in that row. An index past the table keeps 0."""
    n_rows = tables[0].shape[0]
    rows = [i >> 7 for i in idxs]                 # i // LANES
    lanes = [i & (LANES - 1) for i in idxs]

    def body(t, outs):
        new = []
        for tab, tab_outs in zip(tables, outs):
            row = jnp.broadcast_to(tab[pl.ds(t, 1), :], idxs[0].shape)
            new.append(tuple(
                jnp.where(r == t, jnp.take_along_axis(row, ln, axis=1), o)
                for r, ln, o in zip(rows, lanes, tab_outs)))
        return tuple(new)

    init = tuple(tuple(jnp.zeros(i.shape, tab.dtype) for i in idxs)
                 for tab in tables)
    return [list(o) for o in jax.lax.fori_loop(0, n_rows, body, init)]
