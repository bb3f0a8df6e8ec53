"""Fixed-capacity columnar blocks with validity masks.

XLA programs need static shapes; SQL produces data-dependent cardinalities.
The execution layer therefore works on ``Block``s: equal-length column
arrays padded to a bucketed capacity plus a boolean validity mask. Filters
flip mask bits; joins and aggregations emit capacity-bounded outputs. At a
fragment's output the program itself moves the valid rows to the front
(``compact``), so the host pulls back only a bucketed prefix of each column,
all columns in one batched transfer (``to_numpy``).

Capacity bucketing (next power of two, floor 1024) bounds the number of
distinct shapes XLA compiles per operator, and per output prefix.

A fixed-width byte-string column (``|S<w>``) crosses the device as
ceil(w/4) int32 word lanes named ``<column>#<w>.<i>``: ``from_numpy``
splits it, ``to_numpy`` puts it back together, and in between every
operator moves the lanes like any other column (``lanes_of``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

MIN_CAPACITY = 1024


def bucket_capacity(n: int, floor: int = MIN_CAPACITY) -> int:
    cap = floor
    while cap < n:
        cap *= 2
    return cap


@dataclasses.dataclass
class Block:
    columns: dict[str, jnp.ndarray]
    mask: jnp.ndarray | None = None   # bool[capacity], on an input
    # on a compacted output instead of ``mask``: its valid rows are the
    # first ``count`` rows of every column
    count: int | None = None
    # bytes ``to_numpy`` pulled from the device for this block
    pulled_bytes: int = 0

    @property
    def capacity(self) -> int:
        if self.mask is not None:
            return int(self.mask.shape[0])
        return int(next(iter(self.columns.values())).shape[0]) \
            if self.columns else 0

    def col(self, name: str) -> jnp.ndarray:
        return self.columns[name]


def _lane_name(name: str, width: int, i: int) -> str:
    return f"{name}#{width}.{i}"


def lanes_of(columns, name: str) -> list[str]:
    """The device columns that carry column ``name``: itself, or the word
    lanes of a byte string, in order."""
    if name in columns:
        return [name]
    head = f"{name}#"
    lanes = [c for c in columns if c.startswith(head)]
    if not lanes:
        raise KeyError(name)
    return sorted(lanes, key=lambda c: int(c.rsplit(".", 1)[1]))


def renamed_lanes(lanes: list[str], name: str) -> list[str]:
    """``lanes`` (as ``lanes_of`` gives them) under column name ``name``."""
    return [name if "#" not in c else f"{name}#{c.rsplit('#', 1)[1]}"
            for c in lanes]


def _split_bytes(name: str, arr: np.ndarray) -> dict[str, np.ndarray]:
    width = arr.dtype.itemsize
    words = -(-width // 4)
    buf = np.zeros((len(arr), 4 * words), np.uint8)
    buf[:, :width] = np.frombuffer(arr.tobytes(), np.uint8).reshape(
        len(arr), width)
    lanes = buf.view("<i4")
    return {_lane_name(name, width, i): np.ascontiguousarray(lanes[:, i])
            for i in range(words)}


def _join_bytes(columns: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Every group of word lanes in ``columns`` back as its byte string,
    in the place of its first lane."""
    out: dict[str, np.ndarray] = {}
    for c, v in columns.items():
        if "#" not in c:
            out[c] = v
            continue
        name, rest = c.rsplit("#", 1)
        if name in out:
            continue
        width = int(rest.split(".", 1)[0])
        lanes = np.stack([columns[x].astype("<i4")
                          for x in lanes_of(columns, name)], axis=1)
        raw = np.ascontiguousarray(lanes).view(np.uint8)
        out[name] = np.ascontiguousarray(raw[:, :width]).view(
            f"S{width}").reshape(len(v))
    return out


def from_numpy(columns: dict[str, np.ndarray],
               capacity: int | None = None) -> Block:
    n = len(next(iter(columns.values()))) if columns else 0
    cap = capacity if capacity is not None else bucket_capacity(n)
    split = {}
    for name, arr in columns.items():
        if arr.dtype.kind == "S":
            split.update(_split_bytes(name, arr))
        else:
            split[name] = arr
    cols = {}
    for name, arr in split.items():
        pad = np.zeros((cap - n,) + arr.shape[1:], dtype=arr.dtype)
        cols[name] = jnp.asarray(np.concatenate([arr, pad]))
    mask = np.zeros(cap, dtype=bool)
    mask[:n] = True
    return Block(cols, jnp.asarray(mask))


def _ahead(x, s: int):
    """``x`` moved ``s`` rows towards the front, zeros behind."""
    return jnp.concatenate([x[s:], jnp.zeros((s,), x.dtype)])


def _behind(x, s: int):
    return jnp.concatenate([jnp.zeros((s,), x.dtype), x[:-s]])


def compact(columns: dict[str, jnp.ndarray], mask: jnp.ndarray):
    """(columns, int32 count of valid rows): a program's output as it
    leaves the device, the valid rows first in their order. Traceable,
    for use inside ``jit``.

    Each valid row moves towards the front by the number of invalid rows
    before it, one binary digit of that distance per step, lowest first:
    rows keep their order and never land on each other. Every step is a
    slice and a select, and the prefix count a doubling scan of the same
    kind. A gather or scatter over the column runs slowly on the TPU, and
    a sort or ``cumsum`` over it takes seconds to compile there; a select
    moves values with their bits.
    """
    cap = mask.shape[0]
    upto = mask.astype(jnp.int32)
    s = 1
    while s < cap:
        upto = upto + _behind(upto, s)
        s *= 2
    gap = jnp.where(mask, lax.iota(jnp.int32, cap) + 1 - upto, 0)
    live, cols = mask, dict(columns)
    s = 1
    while s < cap:
        move = live & ((gap & s) != 0)
        arrive = _ahead(move, s)
        cols = {n: jnp.where(arrive, _ahead(c, s), c)
                for n, c in cols.items()}
        gap = jnp.where(arrive, _ahead(gap, s), gap)
        live = arrive | (live & ~move)
        s *= 2
    return cols, jnp.sum(mask, dtype=jnp.int32)


# one program per (columns' shapes, prefix bucket): at most one per power
# of two below the capacity
_prefix = jax.jit(lambda columns, rows: {name: col[:rows]
                                         for name, col in columns.items()},
                  static_argnums=1)


def to_numpy(block: Block) -> dict[str, np.ndarray]:
    """A compacted block's valid rows as numpy columns (at fragment edges).

    One batched transfer of every column's prefix up to the count's
    bucket, or of the whole columns where that bucket reaches the
    capacity; the host keeps the first ``count`` rows and records the
    bytes it pulled in ``block.pulled_bytes``."""
    cols = block.columns
    rows = bucket_capacity(block.count)
    if rows < block.capacity:
        cols = _prefix(cols, rows)
    pulled = jax.device_get(cols)
    block.pulled_bytes = sum(v.nbytes for v in pulled.values())
    return _join_bytes({name: v[:block.count]
                        for name, v in pulled.items()})
