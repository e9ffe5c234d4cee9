"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e system architecture"}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(SystemExit):
    pass


def peaks_for(kind: str) -> dict:
    if kind not in PEAKS:
        raise UnknownDevice(f"device kind {kind!r} is not in the peaks table "
                            f"(known: {', '.join(sorted(PEAKS))})")
    return PEAKS[kind]
