"""Peak rates of each chip the benchmark may run on, keyed by device_kind.

A device that is not in the table is an error, not a default: a roofline
share against a guessed peak would be a guess.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip,
    # 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(device_kind: str, n_bytes: float, flops: float) -> float:
    """The least time the chip could take: the larger of the bytes over
    its memory bandwidth and the operations over its peak rate."""
    p = peaks(device_kind)
    return max(n_bytes / p["hbm_bytes_per_s"], flops / p["flops_bf16"])
