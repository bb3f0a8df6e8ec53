"""Fragment outputs leave the device compacted: the valid rows first, their
count, and only the count's bucketed prefix pulled back, in one batched
transfer. The reference is the mask path: every column at capacity,
indexed with the mask on the host, then the Bloom verdict applied there,
as the host did before the compaction moved onto the device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec import fragment
from repro.exec.batch import MIN_CAPACITY, Block, to_numpy


def _columns(rng, cap: int, bloom: bool) -> dict[str, np.ndarray]:
    f8 = rng.standard_normal(cap) * 1e6
    f8[::97], f8[1::89], f8[2::83] = np.nan, -0.0, np.inf
    cols = {"i4": rng.integers(-2**31, 2**31, cap, dtype=np.int32),
            "i8": rng.integers(-2**62, 2**62, cap, dtype=np.int64),
            "f8": f8,
            "b1": rng.random(cap) < 0.5}
    if bloom:
        # a verdict of any non-zero value passes
        cols["__bloom_pass"] = rng.choice(
            np.array([0, 1, 7, -3], np.int32), cap)
    return cols


def _host_path(cols, mask) -> tuple[dict, int]:
    out = {c: np.asarray(v)[mask] for c, v in cols.items()}
    killed = 0
    if "__bloom_pass" in out:
        hit = out.pop("__bloom_pass") != 0
        killed = int(hit.size - hit.sum())
        out = {c: v[hit] for c, v in out.items()}
    return out, killed


CAP = 4 * MIN_CAPACITY
CASES = [(CAP, 0), (CAP, 1), (CAP, 1500), (CAP, 3000), (CAP, CAP),
         # no prefix is shorter than the bucket floor: whole columns
         (MIN_CAPACITY, 700),
         # an output below the floor (a one-row aggregate): whole too
         (1, 1)]


@pytest.mark.parametrize("bloom", [False, True], ids=["mask", "bloom"])
@pytest.mark.parametrize("cap, n", CASES,
                         ids=["none", "one", "partial_bucket",
                              "bucket_at_capacity", "full", "floor",
                              "below_floor"])
def test_compacted_prefix_equals_mask_indexing(monkeypatch, cap, n, bloom):
    rng = np.random.default_rng(n + 10 * bloom)
    cols = _columns(rng, cap, bloom)
    mask = np.zeros(cap, bool)
    mask[rng.choice(cap, n, replace=False)] = True
    want, want_killed = _host_path(cols, mask)

    program = jax.jit(fragment._compacting(lambda blocks: blocks["in0"]))
    out_cols, count, killed = program(
        {"in0": ({c: jnp.asarray(v) for c, v in cols.items()},
                 jnp.asarray(mask))})
    assert "__bloom_pass" not in out_cols
    assert int(killed) == want_killed
    assert int(count) == len(want["i4"])
    block = Block(out_cols, count=int(count))

    calls = []
    device_get = jax.device_get

    def recording(tree):
        calls.append(jax.tree_util.tree_leaves(tree))
        return device_get(tree)
    monkeypatch.setattr(jax, "device_get", recording)
    got = to_numpy(block)

    # the same rows in the same order, bit for bit, NaNs and -0.0 included
    assert sorted(got) == sorted(want)
    for c in want:
        assert got[c].dtype == want[c].dtype
        assert got[c].tobytes() == want[c].tobytes(), c
    # one batched transfer of what pulled_bytes counts: at most the
    # columns at capacity, all of them when every row is valid or no
    # prefix is shorter than the floor, at most half of them when at
    # most half the rows are valid above it
    row_bytes = sum(v.dtype.itemsize for v in out_cols.values())
    assert len(calls) == 1
    assert block.pulled_bytes == sum(a.nbytes for a in calls[0])
    assert block.pulled_bytes <= cap * row_bytes
    if int(count) == cap or cap <= MIN_CAPACITY:
        assert block.pulled_bytes == cap * row_bytes
    elif int(count) <= cap // 2:
        assert block.pulled_bytes <= cap * row_bytes // 2
