"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip,
1,600 Gbit/s inter-chip interconnect. A device kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench: no peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
