"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

A device kind missing from the table is an error, never a default: a share
of a peak taken against the wrong chip's peak means nothing.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks"]

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system architecture)",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
