"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch × shape × mesh), all in seconds-per-step on TPU v5e:

    compute    = per-device HLO FLOPs / 197e12
    memory     = per-device HLO bytes accessed / 819e9
    collective = per-device collective operand bytes / 50e9

``cost_analysis()`` supplies FLOPs/bytes of the *partitioned per-device*
program. Collective bytes are not in cost_analysis — we parse the compiled
HLO text and sum the output-tensor sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op (output size ≈ bytes
an operand moves through a device's links; multi-link utilization and
bidirectional rings make this a conservative upper bound).
"""

from __future__ import annotations

import dataclasses
import math
import re

from repro.launch.mesh import target_hardware

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,2048,128]{2,1,0:T(8,128)(2,1)}
_TYPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^=]*?\)|\S+))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for m in _TYPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    bytes_by: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    count_by: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        out_type, kind = m.group(1), m.group(2)
        # avoid double counting async -start/-done pairs
        if f"{kind}-done" in line:
            continue
        bytes_by[kind] += _tensor_bytes(out_type)
        count_by[kind] += 1
    del seen_done
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    peak_memory_bytes: int
    collectives: CollectiveStats

    def as_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device":
                self.collective_bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "peak_memory_bytes": self.peak_memory_bytes,
            "collective_counts": self.collectives.count_by_kind,
            "collective_bytes": self.collectives.bytes_by_kind,
        }


def analyze(compiled, hlo_text: str | None = None) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)

    hw = target_hardware()
    compute_s = flops / hw["peak_bf16_flops"]
    memory_s = nbytes / hw["hbm_bandwidth"]
    collective_s = coll.total_bytes / hw["ici_link_bandwidth"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    ma = compiled.memory_analysis()
    peak = int(getattr(ma, "argument_size_in_bytes", 0)
               + getattr(ma, "output_size_in_bytes", 0)
               + getattr(ma, "temp_size_in_bytes", 0)
               - getattr(ma, "alias_size_in_bytes", 0))
    return Roofline(flops, nbytes, float(coll.total_bytes), compute_s,
                    memory_s, collective_s, dominant, peak, coll)


def model_flops(cfg, shape, *, train: bool) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) global step FLOPs; 2·N·D for
    forward-only kinds."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if train else 2
    return mult * n * tokens


# -- kernel tiling autotune ------------------------------------------------------
#
# The analytic half of the roofline: pick Pallas block sizes and resident
# capacities for the fused query kernels (repro.exec.lower) from the same
# machine constants the post-hoc analyzer divides by, instead of
# hand-picked constants. The model is deliberately static — a pure
# function of the op shape (column counts, group domain, aggregate
# count) — so the chosen tiling can join the compiled-program cache key
# and be unit-tested without tracing anything.

# Per-element VMEM cost of a kernel operand lane. Interpret mode runs
# f64/i64, but the tiling models the TPU execution (f32/i32 lanes) —
# the cast happens at kernel entry either way.
_ELEM_BYTES = 4

# Fraction of VMEM a kernel's working set may claim; the rest is head
# room for Mosaic's double buffering of grid inputs and spills.
_VMEM_FRACTION = 0.25

# Row blocks are whole (8, 128) tiles of 32-bit rows: the kernels lay a
# block of ``b`` rows out as a (b / 128, 128) tile, and Mosaic takes a
# block only when its second-minor dimension is a multiple of 8.
_LANES = 128
_MIN_BLOCK = 8 * _LANES
_MAX_BLOCK = 8192


def machine_balance() -> float:
    """Machine balance point in FLOPs/byte: arithmetic intensities above
    it are compute-bound on the MXU, below it HBM-bandwidth-bound."""
    hw = target_hardware()
    return hw["peak_bf16_flops"] / hw["hbm_bandwidth"]


def vmem_budget_bytes() -> int:
    return int(target_hardware()["vmem_bytes"] * _VMEM_FRACTION)


def _pow2_floor(n: int) -> int:
    return 1 << max(int(n).bit_length() - 1, 0)


@dataclasses.dataclass(frozen=True)
class KernelTiling:
    """Roofline-chosen tiling for one fused kernel instance.

    ``block_rows`` is the per-grid-step row block; ``resident_rows`` the
    largest input capacity a fully-VMEM-resident kernel (sort / top-k /
    join build side) accepts before the dispatch falls back to the XLA
    path. ``arithmetic_intensity`` and ``dominant`` record which side of
    the machine balance the kernel lands on at the chosen block.
    """
    kernel: str
    block_rows: int
    resident_rows: int
    vmem_bytes: int              # estimated working set at block_rows
    flops_per_row: float
    bytes_per_row: float
    arithmetic_intensity: float
    dominant: str                # "compute" | "memory"

    @property
    def key(self) -> tuple:
        return (self.kernel, self.block_rows, self.resident_rows)

    def as_dict(self) -> dict:
        return {
            "kernel": self.kernel,
            "block_rows": self.block_rows,
            "resident_rows": self.resident_rows,
            "vmem_bytes": self.vmem_bytes,
            "flops_per_row": round(self.flops_per_row, 3),
            "bytes_per_row": round(self.bytes_per_row, 3),
            "arithmetic_intensity": round(self.arithmetic_intensity, 4),
            "dominant": self.dominant,
        }


def _finish(kernel: str, block: int, resident: int, ws_bytes: float,
            flops_per_row: float, bytes_per_row: float) -> KernelTiling:
    ai = flops_per_row / max(bytes_per_row, 1e-9)
    dominant = "compute" if ai >= machine_balance() else "memory"
    return KernelTiling(kernel, block, resident, int(ws_bytes),
                        flops_per_row, bytes_per_row, ai, dominant)


def _grid_block(ws_at, flops_per_row: float, bytes_per_row: float) -> int:
    """Largest power-of-two block whose working set fits the budget; a
    compute-bound kernel (AI past the machine balance) halves once —
    the MXU is the bottleneck anyway and the smaller tile deepens the
    grid pipeline instead of hogging VMEM."""
    budget = vmem_budget_bytes()
    block = _MIN_BLOCK
    while block * 2 <= _MAX_BLOCK and ws_at(block * 2) <= budget:
        block *= 2
    ai = flops_per_row / max(bytes_per_row, 1e-9)
    if ai >= machine_balance() and block > _MIN_BLOCK:
        block //= 2
    return block


def filter_agg_tiling(*, n_cols: int, n_aggs: int) -> KernelTiling:
    """scan→filter→agg: streaming VPU kernel, one (1, A) accumulator."""
    def ws(b):
        return (n_cols + 1) * b * _ELEM_BYTES + n_aggs * _ELEM_BYTES
    flops = 4.0 * n_aggs + 2.0 * n_cols          # pred eval + accumulate
    bpr = float((n_cols + 1) * _ELEM_BYTES)
    block = _grid_block(ws, flops, bpr)
    return _finish("filter_agg", block, _MAX_BLOCK * 16, ws(block),
                   flops, bpr)


def _group_tile_bytes(n_groups: int, n_aggs: int) -> int:
    """The one-hot kernels' partials: one (K, 128) tile per aggregate plus
    the presence count, and the (K, 128) one-hot stripe itself."""
    return (n_aggs + 2) * max(n_groups, 1) * _LANES * _ELEM_BYTES


def groupby_tiling(kernel: str, *, n_cols: int, n_aggs: int,
                   n_groups: int) -> KernelTiling:
    """One-hot grouped aggregation (sums/counts, plus masked min/max for
    ``segmented_minmax``): every 128-row stripe of a block folds into
    per-group lane partials, which dominate the working set at large K."""
    K, A = max(n_groups, 1), n_aggs

    def ws(b):
        return ((n_cols + 1) * b * _ELEM_BYTES      # input columns + mask
                + _group_tile_bytes(K, A))
    flops = 2.0 * K * (A + 1)                       # compare + fold per group
    bpr = float((n_cols + 1) * _ELEM_BYTES)
    block = _grid_block(ws, flops, bpr)
    return _finish(kernel, block, _MAX_BLOCK * 16, ws(block), flops, bpr)


def join_probe_tiling(*, n_cols: int, n_payload: int, n_aggs: int,
                      n_groups: int) -> KernelTiling:
    """Fused join probe + aggregation: the sorted build side stays
    resident across every grid step, so the budget splits between the
    build arrays and the per-step probe block."""
    budget = vmem_budget_bytes()
    build_lane = (n_payload + 1) * _ELEM_BYTES      # sorted keys + payload
    resident = _pow2_floor(max(budget // 2 // build_lane, _MIN_BLOCK))
    K, A = max(n_groups, 1), n_aggs

    def ws(b):
        return (resident * build_lane
                + (n_cols + 1) * b * _ELEM_BYTES
                + _group_tile_bytes(K, A))
    # log2(B) binary-search compares + gathers + the agg update
    flops = 2.0 * math.log2(max(resident, 2)) + 2.0 * K * (A + 1)
    bpr = float((n_cols + 1) * _ELEM_BYTES)
    block = _grid_block(ws, flops, bpr)
    return _finish("join_probe_agg", block, resident, ws(block), flops,
                   bpr)


def resident_sort_tiling(kernel: str, *, n_arrays: int) -> KernelTiling:
    """Fully-resident sorting kernels (bitonic sort-aggregation, top-k):
    every operand array plus one scratch copy lives in VMEM for the whole
    sort network, so capacity — not block — is what the budget caps."""
    budget = vmem_budget_bytes()
    lane = 2 * max(n_arrays, 1) * _ELEM_BYTES       # arrays + shifted copy
    resident = _pow2_floor(max(budget // lane, _MIN_BLOCK))
    stages = math.log2(max(resident, 2))
    flops = n_arrays * stages * (stages + 1) / 2    # compare-exchange net
    bpr = float(n_arrays * _ELEM_BYTES)
    return _finish(kernel, resident, resident, resident * lane, flops,
                   bpr)


def bloom_probe_tiling(*, n_cols: int, n_bits: int) -> KernelTiling:
    """In-kernel semi-join Bloom probe: the filter words (n_bits/8 bytes,
    capped well under the VMEM budget by ``kernels.bloom.BLOOM_MAX_BITS``)
    stay resident across the whole row grid; each row pays two fmix32
    mixes plus k position/gather/bit-test steps — firmly memory-bound,
    like the filter_agg scan it fuses with."""
    words_bytes = max(n_bits, 32) // 8

    def ws(b):
        return words_bytes + (n_cols + 1) * b * _ELEM_BYTES
    # 2 finalizer mixes (5 ops each) + k * (mul-add, mask, shift, gather,
    # shift, and) with k = 6
    flops = 2 * 5.0 + 6 * 6.0
    bpr = float((n_cols + 1) * _ELEM_BYTES)
    block = _grid_block(ws, flops, bpr)
    return _finish("bloom_filter", block, _MAX_BLOCK * 16, ws(block),
                   flops, bpr)


def interpret_prefers_jnp(tiling: KernelTiling) -> bool:
    """Whether an interpreted (CPU) backend should skip this kernel for
    the identical-semantics jnp path.

    The fully-resident bitonic kernels (``block_rows == resident_rows``:
    sort_agg, topk) pay a log²-stage compare-exchange network per element
    — worth it on TPU, where VMEM residency removes the HBM round trips
    the network would otherwise issue, but pure overhead when the kernel
    body is interpreted on a host whose XLA sort is O(log n) per element.
    The tiling's flops-per-row already encodes the network depth, so the
    test is that compute per row dwarfs the byte traffic (a host has no
    MXU: its balance point is ~1 flop/byte, not the TPU's)."""
    return (tiling.resident_rows == tiling.block_rows
            and tiling.flops_per_row > tiling.bytes_per_row)


def onehot_group_capacity(n_aggs: int = 4) -> int:
    """Largest group domain K the one-hot kernels accept: at the minimum
    block the input tiles plus the per-group lane partials must fit the
    VMEM budget."""
    lane = (n_aggs + 2) * _LANES * _ELEM_BYTES
    room = vmem_budget_bytes() - 2 * _MIN_BLOCK * _ELEM_BYTES
    return _pow2_floor(max(room // lane, 1))
