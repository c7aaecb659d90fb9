"""The plain reference of a node whose cache is bounded: upstream's
`lrucache.go` over `spec.py`'s bucket functions, in the most
straightforward Python — an `OrderedDict` in LRU order (first = least
recently used), one request at a time.  It knows nothing of
`InternTable`, the native table or the engine; tests hold them to it
(tests/test_filled_table.py), and the benchmark keeps its own copy
(benchmarks/lib/lru_reference.py).

Upstream, for the record (github.com/mailgun/gubernator):
`store.go:69-78` `Loader.Load()` streams items in before serving, each
through `cache.Add`; `lrucache.go:82-110` `Add` moves a known key to
the front and replaces its value, else pushes it to the front and, over
capacity, removes the oldest (`:148-159`), counting the removal of an
item that had not expired (`gubernator_unexpired_evictions_count`);
`lrucache.go:112-138` `GetItem` is a miss for an expired item.

Departures from `lrucache.go`, each because the program under test
keeps the bucket on the device and only the key on the host:

* An expired item that is asked for again is not removed and re-added;
  it keeps its entry and `apply_spec` starts it afresh.  The key ends
  at the front either way.
* RESET_REMAINING on a live token bucket (`algorithms.go:83-97`
  `c.Remove`) leaves the key in the cache with no bucket, where
  upstream frees the entry: the program learns of the removal on the
  device and its host table keeps the key until it is evicted or swept.
* `evictions` counts every removal of the oldest (upstream counts only
  the unexpired ones); `evicted` lists their keys in order.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Iterable, List, Optional, Tuple

from gubernator_tpu.models.spec import (
    SlotState,
    SpecInput,
    SpecOutput,
    apply_spec,
)


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
