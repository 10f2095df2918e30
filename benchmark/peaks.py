"""Published peaks of one chip, keyed by a substring of ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.  A v5e reports itself as "TPU v5 lite".
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9},
    "v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
            "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9},
}


def peaks_of(device_kind: str) -> dict:
    kind = device_kind.lower()
    for key, val in PEAKS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peaks on record for device_kind {device_kind!r}; known: "
        f"{sorted(PEAKS)}")
