"""Published peaks of the accelerators the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip. A kind that is not in the table is an
error, never a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud documentation, 'TPU v5e': per-chip peaks"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; ``KeyError`` naming the
    known kinds when it is absent."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)} ({SOURCE})") from None
