"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s per chip.
A device that is not in this table is an error, never a default.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16 * 2**30,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
