"""Production mesh construction.

Target: TPU v5e pods — 256 chips per pod arranged (16, 16) as
(data, model); the multi-pod configuration adds a leading pure-DP "pod"
axis (2 pods = 512 chips). Defined as functions so importing this module
never touches jax device state (the dry-run pins the host device count
before first jax init).
"""

from __future__ import annotations

import jax


def auto_axis_kwargs(n_axes: int) -> dict:
    """``axis_types`` kwarg for ``jax.make_mesh``: every axis Auto."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **auto_axis_kwargs(len(axes)))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires host-device override)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         **auto_axis_kwargs(2))


# Per-chip hardware constants (roofline denominators), keyed by
# ``jax.Device.device_kind``. Source: Google Cloud TPU documentation,
# "TPU v5e" (system architecture table): 197 TFLOP/s bf16, 16 GiB HBM at
# 819 GB/s, 1,600 Gbit/s ICI over 4 links. ``vmem_bytes`` is the scoped
# VMEM a Pallas kernel gets by default on v5e (Pallas TPU docs), the
# budget the kernel tiling plans against.
HARDWARE = {
    "TPU v5 lite": {
        "peak_bf16_flops": 197e12,      # per chip
        "hbm_bandwidth": 819e9,         # bytes/s per chip
        "ici_link_bandwidth": 50e9,     # bytes/s per link
        "hbm_bytes": 16 * 2**30,
        "vmem_bytes": 16 * 2**20,
    },
}

# The chip a process without one models: the CPU backend runs the
# kernels interpreted, and tiles them for a v5e.
MODELED_KIND = "TPU v5 lite"


def target_hardware() -> dict:
    """Constants of the chip this process runs kernels on: the attached
    TPU's entry, or v5e's where no TPU is attached. A TPU kind missing
    from the table raises — tiling it with another chip's numbers would
    be a silent guess."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return HARDWARE[MODELED_KIND]
    try:
        return HARDWARE[dev.device_kind]
    except KeyError:
        raise KeyError(f"no hardware constants for {dev.device_kind!r}; "
                       f"add its published peaks to launch.mesh.HARDWARE"
                       ) from None
