"""Semi-join Bloom filters — build, merge, probe (np / jnp / Pallas).

When a pipeline materializes the build side of a repartition join, each
worker folds the join-key column of its output into a compact Bloom
filter; the coordinator OR-merges the per-fragment words and publishes
the merged filter in the build exchange's manifest. Probe-side scan
fragments then test every row against the filter *before* partitioning,
so rows that cannot find a join partner die on the worker that scanned
them instead of being shuffled (requests + bytes are the dominant
serverless cost — see ``CostModel.semijoin_benefit``).

All three probe paths — host numpy (the l0-write kill in
``exec.fragment``), traced jnp (the fallback fragment program), and the
Pallas kernel (``fused_bloom_filter``, dispatched by ``exec.lower``) —
share one hash family so a bit set by any builder is found by every
prober:

  * double hashing over a 32-bit murmur3 finalizer (``fmix32``):
    ``pos_i = (h1 + i·h2) & (n_bits − 1)`` with ``h2`` forced odd, so
    the k probes cycle the full power-of-two bit space. 32-bit lanes
    keep the same arithmetic exact on the TPU VPU (no 64-bit lanes in
    Mosaic) and in numpy.
  * two key modes, recorded in the filter so build and probe always
    apply the identical transform: ``u32`` truncates a single integer
    join-key column (kernel-eligible); ``hash64`` takes the low 32 bits
    of the engine's combined uint64 key hash (multi-column or
    non-integer keys; host-side only).

Sizing: ``n_bits = pow2(~12 bits per expected distinct key)``, k = 6,
for a theoretical false-positive rate around 0.4% (residue rows are
still shuffled but then dropped by the exact join).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import (LANES, lane_types, resident_spec,
                                  resident_take, row_block, row_spec,
                                  to_lanes, to_rows)

BLOOM_K = 6
BLOOM_BITS_PER_KEY = 12
BLOOM_MIN_BITS = 1 << 10            # 128 B floor: never degenerate
BLOOM_MAX_BITS = 1 << 22            # 512 KiB cap: stays VMEM-resident
_SEED1 = np.uint32(0x9E3779B9)
_SEED2 = np.uint32(0x41C64E6D)
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35


def _fmix32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= np.uint32(_M1)
        x ^= x >> np.uint32(13)
        x *= np.uint32(_M2)
        x ^= x >> np.uint32(16)
    return x


def _fmix32_jnp(x):
    x = x.astype(jnp.uint32)
    x ^= x >> jnp.uint32(16)
    x *= jnp.uint32(_M1)
    x ^= x >> jnp.uint32(13)
    x *= jnp.uint32(_M2)
    x ^= x >> jnp.uint32(16)
    return x


def bloom_bits_for(n_keys: int, *, bits_per_key: int = BLOOM_BITS_PER_KEY,
                   max_bits: int = BLOOM_MAX_BITS) -> int:
    """Power-of-two filter size for an expected distinct-key count
    (typically a KMV estimate), clamped to [BLOOM_MIN_BITS, max_bits]."""
    want = max(int(n_keys), 1) * bits_per_key
    bits = 1 << max(math.ceil(math.log2(max(want, 1))), 0)
    return max(BLOOM_MIN_BITS, min(bits, max_bits))


def bloom_fpr(n_keys: int, n_bits: int, k: int = BLOOM_K) -> float:
    """Theoretical false-positive rate (1 - e^{-kn/m})^k."""
    if n_bits <= 0:
        return 1.0
    return (1.0 - math.exp(-k * max(n_keys, 0) / n_bits)) ** k


def bloom_build(keys_u32: np.ndarray, n_bits: int,
                k: int = BLOOM_K) -> np.ndarray:
    """Set the k bit positions of every key; returns the uint32 words
    (n_bits/32 of them). ``n_bits`` must be a power of two."""
    assert n_bits & (n_bits - 1) == 0, n_bits
    words = np.zeros(n_bits // 32, dtype=np.uint32)
    if keys_u32.size == 0:
        return words
    keys_u32 = keys_u32.astype(np.uint32, copy=False)
    with np.errstate(over="ignore"):
        h1 = _fmix32_np(keys_u32 ^ _SEED1)
        h2 = _fmix32_np(keys_u32 ^ _SEED2) | np.uint32(1)
        m = np.uint32(n_bits - 1)
        for i in range(k):
            pos = (h1 + np.uint32(i) * h2) & m
            np.bitwise_or.at(words, pos >> np.uint32(5),
                             np.uint32(1) << (pos & np.uint32(31)))
    return words


def bloom_merge(words_list) -> np.ndarray:
    """OR-union of same-size filters (build fragments are unioned the
    way KMV sketches are merged)."""
    out = None
    for w in words_list:
        w = np.asarray(w, dtype=np.uint32)
        out = w.copy() if out is None else np.bitwise_or(out, w)
    if out is None:
        raise ValueError("bloom_merge of zero filters")
    return out


def bloom_probe_np(keys_u32: np.ndarray, words: np.ndarray, n_bits: int,
                   k: int = BLOOM_K) -> np.ndarray:
    """Membership mask (bool) — no false negatives by construction."""
    if keys_u32.size == 0:
        return np.zeros(0, dtype=bool)
    keys_u32 = keys_u32.astype(np.uint32, copy=False)
    with np.errstate(over="ignore"):
        h1 = _fmix32_np(keys_u32 ^ _SEED1)
        h2 = _fmix32_np(keys_u32 ^ _SEED2) | np.uint32(1)
        m = np.uint32(n_bits - 1)
        hit = np.ones(keys_u32.shape, dtype=bool)
        for i in range(k):
            pos = (h1 + np.uint32(i) * h2) & m
            bit = (words[pos >> np.uint32(5)]
                   >> (pos & np.uint32(31))) & np.uint32(1)
            hit &= bit != 0
    return hit


def bloom_probe_jnp(keys, words, *, bits: int, k: int = BLOOM_K):
    """jnp twin of :func:`bloom_probe_np` — bit-identical positions.
    ``keys`` is any integer array (truncated to uint32 like the np
    path); ``words`` a uint32 array."""
    ku = keys.astype(jnp.uint32)
    h1 = _fmix32_jnp(ku ^ jnp.uint32(_SEED1))
    h2 = _fmix32_jnp(ku ^ jnp.uint32(_SEED2)) | jnp.uint32(1)
    m = jnp.uint32(bits - 1)
    hit = jnp.ones(ku.shape, dtype=bool)
    for i in range(k):
        pos = (h1 + jnp.uint32(i) * h2) & m
        w = jnp.take(words, (pos >> jnp.uint32(5)).astype(jnp.int32))
        hit &= ((w >> (pos & jnp.uint32(31))) & jnp.uint32(1)) != 0
    return hit


# -- key extraction (build and probe must agree) --------------------------------

def key_mode_for(columns: dict, key_cols: list[str]) -> str:
    """``u32`` for a single integer key column (kernel-eligible),
    ``hash64`` otherwise."""
    if len(key_cols) == 1:
        col = columns.get(key_cols[0])
        if col is not None and col.dtype.kind in "iu":
            return "u32"
    return "hash64"


def keys_u32(columns: dict, key_cols: list[str], mode: str) -> np.ndarray:
    """The 32-bit key stream a filter is built over / probed with.
    Both sides of a join must use the same mode or false negatives
    appear — the mode travels inside the published filter."""
    if mode == "u32":
        with np.errstate(over="ignore"):
            return columns[key_cols[0]].astype(np.uint32)
    from repro.exec.operators import np_key_hash
    h = np_key_hash(columns, key_cols)
    return (h & np.uint64(0xFFFFFFFF)).astype(np.uint32)


# -- serialization (registry manifests are msgpack) ------------------------------

def bloom_to_wire(words: np.ndarray, *, k: int = BLOOM_K,
                  mode: str = "u32") -> dict:
    words = np.asarray(words, dtype=np.uint32)
    return {"bits": int(words.size * 32), "k": int(k), "mode": mode,
            "words": words.tobytes()}


def bloom_from_wire(d: dict) -> dict:
    """Decoded filter: words as a uint32 array, ready to probe."""
    words = np.frombuffer(d["words"], dtype=np.uint32)
    return {"bits": int(d["bits"]), "k": int(d["k"]),
            "mode": d.get("mode", "u32"), "words": words}


# -- fused Pallas probe kernel (exec.lower dispatch target) ----------------------

def _bloom_filter_kernel(*refs, names, key, pred, bits, k):
    *col_refs, mask_ref, words_ref, o_ref = refs
    cols = {n: r[...] for n, r in zip(names, col_refs)}   # (rows, 128)
    m = mask_ref[...] != 0
    if pred is not None:
        m = m & pred(cols)
    ku = cols[key].astype(jnp.uint32)
    h1 = _fmix32_jnp(ku ^ jnp.uint32(_SEED1))
    h2 = _fmix32_jnp(ku ^ jnp.uint32(_SEED2)) | jnp.uint32(1)
    bm = jnp.uint32(bits - 1)
    pos = [(h1 + jnp.uint32(i) * h2) & bm for i in range(k)]
    (words,) = resident_take(
        [words_ref], [(p >> jnp.uint32(5)).astype(jnp.int32) for p in pos])
    hit = m
    for p, w in zip(pos, words):
        # int32 words: the arithmetic shift leaves the tested low bit
        # identical to the unsigned one
        hit = hit & (((w >> (p & jnp.uint32(31)).astype(jnp.int32)) & 1)
                     != 0)
    o_ref[...] = hit.astype(jnp.int32)


def fused_bloom_filter(columns: dict, mask, *, pred, key: str, words,
                       bits: int, k: int = BLOOM_K, block: int = 2048,
                       interpret: bool = False):
    """One-pass predicate + Bloom membership mask over column blocks.

    The filter words stay VMEM-resident across the whole row grid (the
    size cap keeps them ≤ 512 KiB), laid out ``(words / 128, 128)`` as
    int32 bit patterns; each grid step evaluates the compiled predicate
    closure and the k hash probes over one row block (the word fetch
    walks the resident rows — :func:`~repro.kernels.common.resident_take`)
    and emits the surviving-row mask tile. Returns a bool (n,) mask
    aligned with the inputs — the caller compacts the columns.
    """
    names = tuple(columns)
    n = mask.shape[0]
    if n == 0:
        return jnp.zeros((0,), dtype=bool)
    block = row_block(n, block)
    arrs, m, nb = to_rows(to_lanes([columns[c] for c in names], interpret),
                          mask, block)
    words = jax.lax.bitcast_convert_type(
        jnp.asarray(words, dtype=jnp.uint32), jnp.int32)
    words = jnp.pad(words, (0, (-words.shape[0]) % LANES)).reshape(-1, LANES)

    with lane_types(interpret):
        out = pl.pallas_call(
            functools.partial(_bloom_filter_kernel, names=names, key=key,
                              pred=pred, bits=bits, k=k),
            grid=(nb,),
            in_specs=([row_spec(block)] * (len(names) + 1)
                      + [resident_spec(words.shape)]),
            out_specs=row_spec(block),
            out_shape=jax.ShapeDtypeStruct(m.shape, jnp.int32),
            interpret=interpret,
        )(*arrs, m, words)
    return out.reshape(-1)[:n] != 0
