"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of inter-chip interconnect. A kind that is not in this table is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # B/s
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind that the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for the work: the larger of the
    compute bound and the memory bound."""
    pk = peaks_for(device_kind)
    return max(flops / pk["flops_bf16"], nbytes / pk["hbm_bytes_per_s"])
