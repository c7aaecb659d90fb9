"""Peaks of the chips the benchmark knows, and the least bytes a
rate-limit decision has to move on the device.

The roofline reckons the work, not the implementation: it never looks
at which program ran or how that program packs its buffers.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by jax's `device_kind`.  Source:
# Google Cloud documentation, "TPU v5e" system architecture page
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}

# One BucketState row: 12 int32/uint32 columns (ops/bucket_kernel.py).
ROW_BYTES = 48
# What a decision brings to the device: its row index (4), hits, limit,
# duration, burst (8 each), algorithm and behavior flags (4).
REQUEST_BYTES = 40
# What it takes back: status (4), remaining (8), reset_time (8); the
# limit is the request's own.
RESPONSE_BYTES = 20


def peaks(device_kind: str) -> dict:
    """The chip's peaks; an unknown kind is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}: add it to "
            f"benchmarks/lib/roofline.py PEAKS with its source"
        ) from None


def decision_bytes(decisions: int, row_bytes: int = ROW_BYTES) -> int:
    """The least HBM traffic for `decisions` bucket updates: each row
    read once and written once, plus the request and response words."""
    return decisions * (2 * row_bytes + REQUEST_BYTES + RESPONSE_BYTES)


def roofline_pct(decisions: int, kernel_seconds: float,
                 device_kind: str) -> float:
    """Least time for the work over the time the kernels took, in %.
    HBM-bound by construction (a decision is a few integer operations).
    A share over 100 % means the count or the time is wrong: raise."""
    if kernel_seconds <= 0 or decisions <= 0:
        raise ValueError("roofline needs a positive count and time")
    least = decision_bytes(decisions) / peaks(device_kind)["hbm_bytes_per_s"]
    share = 100.0 * least / kernel_seconds
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.1f} % > 100 %: {decisions} decisions "
            f"in {kernel_seconds:.6f} s of kernel time"
        )
    return share
