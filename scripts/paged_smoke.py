#!/usr/bin/env python
"""Paged-state smoke: one fast pass over the GUBER_PAGED plane's
load-bearing contract (ci_fast stage; 30 s wall budget enforced by
the caller, jax on CPU, no TPU).

Asserts, in order:
  1. a paged engine boots with device capacity = frames x page_size
     while interning at the full logical capacity;
  2. fault-then-hit roundtrip: keys past the resident budget fault
     (counted — never silent), spill a victim page, and answer with
     the SAME remaining sequence a dense engine produces;
  3. an evicted key's bucket survives the spill→refill roundtrip
     bit-exactly (the re-hit debits the spilled remaining, not a
     fresh bucket);
  4. resident re-hits after the roundtrip pay zero additional faults.

The deep coverage (spec parity fuzz, TTL boundaries, restore,
host-side sweep) lives in tests/test_paged_state.py and the
three-way harness in tests/test_fused_parity.py; this is the canary
that the page table still translates and the fault path still
counts after any engine/kernel edit.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["GUBER_PAGED"] = "1"
os.environ["GUBER_PAGE_SIZE"] = "16"
os.environ["GUBER_PAGED_RESIDENT"] = "4"
os.environ["GUBER_PUMP"] = "0"

import numpy as np


def main() -> int:
    from gubernator_tpu.clock import Clock
    from gubernator_tpu.core.engine import DecisionEngine
    from gubernator_tpu.types import RateLimitReq

    clock = Clock().freeze()
    eng = DecisionEngine(capacity=1024, clock=clock)
    assert eng.paging is not None, "GUBER_PAGED=1 must build the plane"
    assert eng.capacity == 64, eng.capacity  # 4 frames x 16 rows
    assert eng.logical_capacity == 1024

    def hit(lo, hi, expect_remaining):
        reqs = [
            RateLimitReq(
                name="pg", unique_key=str(i), hits=1, limit=10,
                duration=600_000,
            )
            for i in range(lo, hi)
        ]
        rs = eng.get_rate_limits(reqs, now_ms=clock.now_ms())
        bad = [
            (i, r.status, r.remaining)
            for i, r in zip(range(lo, hi), rs)
            if r.error or r.remaining != expect_remaining
        ]
        assert not bad, bad[:5]

    # 1+2. Key space 3x the resident rows: first contact fills the
    # frames, the tail faults — every fault counted, zero errors.
    hit(0, 192, expect_remaining=9)
    f1 = eng.paging.faults
    assert f1 > 0, "cold tail past the frames must fault"
    assert eng.paging.spills > 0
    assert eng.paging.refills == f1
    assert eng.paging.fault_duration.count == f1

    # 3. Fault-then-hit roundtrip: the first keys' pages went cold;
    # re-hitting them must refill the SPILLED bucket (remaining 9→8),
    # not create a fresh one.
    assert not eng.paging.is_resident(0), "slot 0 should have spilled"
    clock.advance(ms=5)
    hit(0, 32, expect_remaining=8)
    assert eng.paging.faults > f1

    # 4. Resident re-hits are fault-free.
    f2 = eng.paging.faults
    clock.advance(ms=5)
    hit(0, 32, expect_remaining=7)
    assert eng.paging.faults == f2, "resident re-hit must not fault"

    print(
        "paged smoke ok: faults=%d spills=%d refills=%d "
        "fault_p99_ms=%.3f" % (
            eng.paging.faults, eng.paging.spills, eng.paging.refills,
            eng.paging.fault_duration.p99() * 1000.0,
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
