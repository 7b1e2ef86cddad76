"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. Source: Google Cloud documentation, "TPU
v5e" (system architecture): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.

A device that is not in the table is an error, not a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
