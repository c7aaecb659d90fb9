"""The benchmark's own copy of the plain reference of a bounded cache.

Upstream gubernator's `lrucache.go` over `lib/spec.py`'s bucket
functions, in the most straightforward Python: an `OrderedDict` in LRU
order (first = least recently used), one request at a time.
`store.go:69-78` `Loader.Load()` streams items in before serving, each
through `cache.Add` (`lrucache.go:82-110`): a known key moves to the
front and takes the new value, an unknown one is pushed to the front
and, over capacity, the oldest is removed (`:148-159`) and counted if it
had not expired (`gubernator_unexpired_evictions_count`).  An evicted
key that returns starts from an empty bucket.

Copied from `gubernator_tpu/models/lru_reference.py` as PR 28 left it,
so that a later change to the program cannot move the yardstick; it
imports nothing of the program (its departures from `lrucache.go` are
noted there).  `tests/benchmark/test_filled.py` checks that the two
files still agree on a seeded stream.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Iterable, List, Optional, Tuple

from .spec import SlotState, SpecInput, SpecOutput, apply_spec


class LRUReference:
    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self.buckets: "OrderedDict[str, Optional[SlotState]]" = OrderedDict()
        self.evictions = 0
        self.unexpired_evictions = 0
        self.evicted: List[str] = []

    def _touch(self, key: str, now: int) -> None:
        """`key` to the front; the oldest makes room for an unknown one."""
        if key in self.buckets:
            self.buckets.move_to_end(key)
            return
        if len(self.buckets) == self.capacity:
            old_key, old = self.buckets.popitem(last=False)
            self.evictions += 1
            if old is not None and old.expire_at > now:
                self.unexpired_evictions += 1
            self.evicted.append(old_key)
        self.buckets[key] = None

    def load(self, rows: Iterable[Tuple[str, SlotState]], now: int) -> None:
        """Loader.Load: `Add` per row, in the stream's order."""
        for key, state in rows:
            self._touch(key, now)
            self.buckets[key] = state

    def get_rate_limit(self, key: str, inp: SpecInput, now: int) -> SpecOutput:
        self._touch(key, now)
        self.buckets[key], out = apply_spec(self.buckets[key], inp, now)
        return out
