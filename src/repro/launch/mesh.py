"""Production mesh construction (function, not module-level constant — importing
this module never touches jax device state)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist (tests / examples on CPU): 1D 'data' mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))


# Peaks per chip, keyed by ``jax.Device.device_kind`` — the one table every
# roofline reads; a device kind missing here is an error, never a default.
#   "TPU v5 lite": TPU v5e, Google Cloud documentation "TPU v5e" (197
#       TFLOP/s bf16, 16 GB HBM at 819 GB/s); ICI is the per-link figure.
#   "cpu": no published peak — a nominal single-socket DDR stream figure,
#       so a roofline fraction read off a CPU run is an upper bound.
CHIP_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
    "cpu": {"hbm_bw": 4.1e10},
}


def chip_peak(device_kind: str, metric: str) -> float:
    """``CHIP_PEAKS[device_kind][metric]``; raises for a device kind (or a
    metric) the table does not list."""
    try:
        return CHIP_PEAKS[device_kind][metric]
    except KeyError:
        raise ValueError(f"no {metric!r} peak for device kind "
                         f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                         ) from None


# TPU v5e constants of the dry-run cell roofline (DESIGN.md / EXPERIMENTS.md)
PEAK_FLOPS_BF16 = chip_peak("TPU v5 lite", "flops_bf16")   # per chip
HBM_BW = chip_peak("TPU v5 lite", "hbm_bw")                # bytes/s per chip
ICI_BW = chip_peak("TPU v5 lite", "ici_bw")                # bytes/s per link
