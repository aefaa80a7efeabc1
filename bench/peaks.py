"""Published peaks per chip, keyed by JAX's ``device_kind``, and the bytes a
decode has to move.

A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def decode_bytes(compressed_bytes: int, decoded_bytes: int) -> int:
    """The least HBM traffic of a decode: every compressed byte read once and
    every decoded byte written once."""
    if compressed_bytes < 0 or decoded_bytes < 0:
        raise ValueError("byte counts are not negative")
    return int(compressed_bytes) + int(decoded_bytes)
