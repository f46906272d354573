"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s
per chip.  Only what a metric reads is kept.  A device that is not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
