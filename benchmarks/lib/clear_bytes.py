"""The least bytes the eviction clear has to move on the device, beside
`roofline.py`'s count for a decision.

A clear marks the rows of evicted keys unoccupied (the program's
`_clear_occupied_impl`): per row it brings the row's index and reads
and writes back the one word that holds the occupied bit.  As in
`roofline.py`, the work is reckoned, not the implementation: the
padding lanes of a clear and a pass over the column are not work.
"""

from __future__ import annotations

from .roofline import peaks

# A cleared row: its index (4), the meta word read (4) and written (4).
CLEAR_ROW_BYTES = 12


def clear_roofline_pct(rows: int, kernel_seconds: float,
                       device_kind: str) -> float:
    """Least HBM time for clearing `rows` rows over the time the clear
    programs took, in %.  Over 100 % the count or the time is wrong."""
    if kernel_seconds <= 0 or rows <= 0:
        raise ValueError("roofline needs a positive count and time")
    least = rows * CLEAR_ROW_BYTES / peaks(device_kind)["hbm_bytes_per_s"]
    share = 100.0 * least / kernel_seconds
    if share > 100.0:
        raise ValueError(
            f"clear roofline share {share:.1f} % > 100 %: {rows} rows in "
            f"{kernel_seconds:.6f} s of kernel time"
        )
    return share
