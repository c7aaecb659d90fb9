"""Device-side ops: the vectorized bucket-update kernel and expiry sweep.

These replace the reference's per-request goroutine hot loop
(reference: gubernator_pool.go:193-247 + algorithms.go) with one XLA
computation over the whole batch (SURVEY.md §7.1).
"""

from gubernator_tpu.ops.bucket_kernel import (
    BucketState,
    BatchInput,
    make_state,
)

__all__ = ["BucketState", "BatchInput", "make_state"]
