"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source for the TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per
chip.  A device that is not in the table is an error, not a
default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS = {
    "TPU v5 lite": {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to chipbench/peaks.py with their source") from None
