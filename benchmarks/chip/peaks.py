"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device missing from the table is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bfloat16 and 394 TOP/s int8 per chip, 16 GB of HBM2 at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # B/s
        "hbm_bytes": 16e9,         # B
        "ici_bytes_per_s": 200e9,  # B/s per chip (1,600 Gbit/s)
        "source": "Google Cloud, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
