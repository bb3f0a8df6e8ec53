"""The chip's peaks, and what each kernel must move and compute.

Peaks are keyed by ``jax.Device.device_kind``; a kind missing from the
table is an error, never a default. The bytes and operations of a kernel
are counted here from the shapes it was called with (its operands and
results as the profiler's trace records them), never taken from the
program's own roofline model.
"""

from __future__ import annotations

import math
import re

# Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}: add it with its source")
    return PEAKS[device_kind]


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))"
                    r"\[([0-9,]*)\]")


def shapes(hlo_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[dims]`` array shape named in an HLO instruction."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(hlo_text)]


def nbytes(arrays) -> int:
    return sum(_BYTES[dt] * math.prod(dims) for dt, dims in arrays)


def elements(arrays) -> int:
    return sum(math.prod(dims) for _, dims in arrays)


def _streaming(arrays) -> tuple[int, int]:
    """A kernel that reads each operand once and writes each result once,
    with at least one operation per element read."""
    return nbytes(arrays), elements(arrays)


def _sort(arrays) -> tuple[int, int]:
    """A bitonic network: n/2 * log2(n) * (log2(n) + 1) / 2 compare-
    exchanges per sorted lane set, over every array sorted along."""
    n = max((math.prod(d) for _, d in arrays), default=1)
    lg = max(1, math.ceil(math.log2(max(n, 2))))
    return nbytes(arrays), (n // 2) * lg * (lg + 1) // 2 * 2


# kernel name → (bytes, operations) from the shapes of one call; a kernel
# not named here is counted as streaming.
KERNEL_COST = {
    "filter_agg": _streaming,
    "groupby_onehot": _streaming,
    "segmented_minmax": _streaming,
    "join_probe_agg": _streaming,
    "bloom_filter": _streaming,
    "sort_agg": _sort,
    "topk": _sort,
}


def kernel_cost(kernel: str, arrays) -> tuple[int, int]:
    return KERNEL_COST.get(kernel, _streaming)(arrays)


def roofline_s(kernel: str, arrays, device_kind: str) -> tuple[float, str]:
    """Least time the chip could take for one call, and which bound
    (``memory`` or ``compute``) sets it."""
    p = peak(device_kind)
    b, ops = kernel_cost(kernel, arrays)
    mem, comp = b / p["hbm_bytes_per_s"], ops / p["flops_per_s"]
    return (mem, "memory") if mem >= comp else (comp, "compute")
