"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
v5e chip has 16 GiB of HBM at 819 GB/s and 197 TFLOP/s in bfloat16.  A kind
that is not listed is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(f"no {what!r} peak for device kind {device_kind!r}; "
                         f"the table lists {sorted(PEAKS)}") from None
