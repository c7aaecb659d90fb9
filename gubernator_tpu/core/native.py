"""ctypes wrapper over the native interning table.

`NativeInternTable` is API-compatible with `core.interning.InternTable`
plus the batch `schedule()` fast path the engine prefers: one FFI call
interns the whole batch, assigns serialization rounds, and returns
eviction clears — replacing the per-key Python dict walk on the host
hot path (SURVEY.md §7.3 hard part #1).  Equivalence with the Python
table is fuzz-tested (tests/test_native_table.py).
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Tuple

import numpy as np

from gubernator_tpu.core.native_build import ensure_built

_lib = None


def load_library():
    """Load (building if needed) the shared object; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    so = ensure_built()
    if so is None:
        return None
    lib = ctypes.CDLL(str(so))
    lib.git_new.restype = ctypes.c_void_p
    lib.git_new.argtypes = [ctypes.c_int64]
    lib.git_free.argtypes = [ctypes.c_void_p]
    lib.git_len.restype = ctypes.c_int64
    lib.git_len.argtypes = [ctypes.c_void_p]
    lib.git_schedule.restype = ctypes.c_int64
    lib.git_schedule.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,  # buf
        ctypes.c_void_p,  # offsets
        ctypes.c_int64,  # n
        ctypes.c_int64,  # now_ms
        ctypes.c_void_p,  # out_slots
        ctypes.c_void_p,  # out_rounds
        ctypes.c_void_p,  # out_evicted
        ctypes.c_void_p,  # out_evict_rounds
        ctypes.c_void_p,  # stats_out
    ]
    lib.git_schedule_idx.restype = ctypes.c_int64
    lib.git_schedule_idx.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,  # buf
        ctypes.c_void_p,  # offsets
        ctypes.c_void_p,  # idx (nullable)
        ctypes.c_int64,  # n
        ctypes.c_int64,  # now_ms
        ctypes.c_void_p,  # out_slots
        ctypes.c_void_p,  # out_rounds
        ctypes.c_void_p,  # out_evicted
        ctypes.c_void_p,  # out_evict_rounds
        ctypes.c_void_p,  # stats_out
    ]
    lib.git_multi_schedule.restype = ctypes.c_int64
    lib.git_multi_schedule.argtypes = [
        ctypes.c_void_p,  # tables (void*[n_sh])
        ctypes.c_int64,  # n_sh
        ctypes.c_void_p,  # buf
        ctypes.c_void_p,  # offsets
        ctypes.c_void_p,  # hashes (nullable)
        ctypes.c_int64,  # n
        ctypes.c_int64,  # now_ms
        ctypes.c_void_p,  # expires (nullable)
        ctypes.c_void_p,  # out_shard
        ctypes.c_void_p,  # out_slots
        ctypes.c_void_p,  # out_rounds
        ctypes.c_void_p,  # out_order
        ctypes.c_void_p,  # out_shard_counts
        ctypes.c_void_p,  # out_evicted
        ctypes.c_void_p,  # out_evict_shard
        ctypes.c_void_p,  # out_evict_rounds
        ctypes.c_void_p,  # out_n_evicted
        ctypes.c_void_p,  # stats_out
        ctypes.c_int64,  # n_threads
    ]
    lib.git_load.restype = None
    lib.git_load.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,  # buf
        ctypes.c_void_p,  # offsets
        ctypes.c_int64,  # n
        ctypes.c_int64,  # now_ms
        ctypes.c_void_p,  # expires
        ctypes.c_void_p,  # out_slots
        ctypes.c_void_p,  # stats_out
    ]
    lib.git_set_expiry.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
    ]
    lib.git_remove.restype = ctypes.c_int32
    lib.git_remove.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.git_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.git_key_for_slot.restype = ctypes.c_int64
    lib.git_key_for_slot.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int32,
        ctypes.c_void_p,
        ctypes.c_int64,
    ]
    lib.git_contains.restype = ctypes.c_int64
    lib.git_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    _lib = lib
    return _lib


def _ptr(a: np.ndarray):
    # Bare data address (int, passed as c_void_p) — see
    # net/wire_codec._ptr for the measured cost of the ctypes-view
    # variant on per-RPC paths.
    return a.ctypes.data


class NativeInternTable:
    """Drop-in InternTable backed by the C++ table."""

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        lib = load_library()
        if lib is None:
            raise RuntimeError("native intern table unavailable")
        self._lib = lib
        self.capacity = capacity
        self._t = lib.git_new(capacity)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.unexpired_evictions = 0
        # Discounts subtracted from the C++ cumulative counters when
        # mirroring (warmup traffic exclusion — engine.warmup).
        self._stat_off = [0, 0, 0, 0]

    def __del__(self):
        t = getattr(self, "_t", None)
        if t:
            self._lib.git_free(t)
            self._t = None

    def __len__(self) -> int:
        return int(self._lib.git_len(self._t))

    # -- batch fast path ----------------------------------------------

    def schedule(
        self, keys: List[bytes], now_ms: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Intern a batch: returns (slots, rounds, evicted_slots,
        evict_rounds) — one FFI call for the whole batch."""
        from gubernator_tpu.core.engine import PackedKeys

        packed = PackedKeys.from_list(keys)
        return self.schedule_packed(packed.buf, packed.offsets, now_ms)

    def schedule_packed(
        self,
        buf_arr: np.ndarray,  # uint8 concatenated key bytes
        offsets: np.ndarray,  # int64 [total+1]
        now_ms: int,
        idx: Optional[np.ndarray] = None,  # int64 subset (None = all)
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Like schedule(), but over an already-packed key buffer (the
        native wire codec's output) — zero per-key Python.  `idx`
        selects a subset of items (the sharded engine's per-shard
        routing over one decoded batch)."""
        n = len(idx) if idx is not None else len(offsets) - 1
        buf_arr = np.ascontiguousarray(buf_arr, dtype=np.uint8)
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        if idx is not None:
            idx = np.ascontiguousarray(idx, dtype=np.int64)
        slots = np.empty(n, dtype=np.int32)
        rounds = np.empty(n, dtype=np.int32)
        evicted = np.empty(n if n else 1, dtype=np.int32)
        evict_rounds = np.empty(n if n else 1, dtype=np.int32)
        stats = np.zeros(4, dtype=np.int64)
        n_ev = self._lib.git_schedule_idx(
            self._t,
            _ptr(buf_arr),
            _ptr(offsets),
            _ptr(idx) if idx is not None else None,
            n,
            now_ms,
            _ptr(slots),
            _ptr(rounds),
            _ptr(evicted),
            _ptr(evict_rounds),
            _ptr(stats),
        )
        off = self._stat_off
        self.hits, self.misses, self.evictions, self.unexpired_evictions = (
            int(stats[0]) - off[0],
            int(stats[1]) - off[1],
            int(stats[2]) - off[2],
            int(stats[3]) - off[3],
        )
        return slots, rounds, evicted[:n_ev], evict_rounds[:n_ev]

    def load_rows(
        self,
        buf_arr: np.ndarray,  # uint8 concatenated key bytes
        offsets: np.ndarray,  # int64 [n+1]
        expires: np.ndarray,  # int64 [n] TTL mirror of each row
        now_ms: int,
    ) -> np.ndarray:
        """Bulk restore: intern the rows' keys in arrival order (first
        row least recently used) in one FFI call; returns their slots.
        Semantics in `InternTable.load_rows`, its Python twin."""
        n = len(offsets) - 1
        buf_arr = np.ascontiguousarray(buf_arr, dtype=np.uint8)
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        expires = np.ascontiguousarray(expires, dtype=np.int64)
        if len(expires) != n:
            raise ValueError(f"{len(expires)} expiries for {n} keys")
        slots = np.empty(n, dtype=np.int32)
        stats = np.zeros(4, dtype=np.int64)
        self._lib.git_load(
            self._t, _ptr(buf_arr), _ptr(offsets), n, now_ms,
            _ptr(expires), _ptr(slots), _ptr(stats),
        )
        off = self._stat_off
        self.evictions = int(stats[2]) - off[2]
        self.unexpired_evictions = int(stats[3]) - off[3]
        return slots

    def discount_stats(self, hits: int, misses: int, evictions: int = 0,
                       unexpired: int = 0) -> None:
        """Exclude (warmup) traffic from the mirrored metrics."""
        self._stat_off[0] += hits
        self._stat_off[1] += misses
        self._stat_off[2] += evictions
        self._stat_off[3] += unexpired
        self.hits -= hits
        self.misses -= misses
        self.evictions -= evictions
        self.unexpired_evictions -= unexpired

    # -- InternTable-compatible API -----------------------------------

    def intern(self, key: str, now_ms: int, cleared: list) -> int:
        slots, _rounds, evicted, _er = self.schedule([key.encode()], now_ms)
        cleared.extend(evicted.tolist())
        return int(slots[0])

    def contains(self, key: str) -> bool:
        k = key.encode()
        return bool(self._lib.git_contains(self._t, k, len(k)))

    def set_expiry(self, slots: np.ndarray, expires: np.ndarray) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        expires = np.ascontiguousarray(expires, dtype=np.int64)
        self._lib.git_set_expiry(self._t, _ptr(slots), _ptr(expires), len(slots))

    def remove(self, key: str) -> Optional[int]:
        k = key.encode()
        slot = self._lib.git_remove(self._t, k, len(k))
        return None if slot < 0 else int(slot)

    def release_slots(self, slots: np.ndarray) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        self._lib.git_release(self._t, _ptr(slots), len(slots))

    def key_for_slot(self, slot: int) -> Optional[str]:
        cap = 256
        while True:
            out = ctypes.create_string_buffer(cap)
            ln = self._lib.git_key_for_slot(self._t, slot, out, cap)
            if ln < 0:
                return None
            if ln <= cap:
                return out.raw[:ln].decode()
            cap = int(ln)


def _default_threads() -> int:
    """GUBER_MULTI_THREADS resolved ONCE (malformed values fail at
    first use, not per request); 0 = auto (ncpu-capped per call)."""
    global _DEFAULT_THREADS
    if _DEFAULT_THREADS is None:
        env = os.environ.get("GUBER_MULTI_THREADS", "")
        _DEFAULT_THREADS = int(env) if env else 0
    return _DEFAULT_THREADS


_DEFAULT_THREADS: Optional[int] = None


def multi_schedule(
    tables: List["NativeInternTable"],
    buf_arr: np.ndarray,  # uint8 concatenated key bytes
    offsets: np.ndarray,  # int64 [n+1]
    hashes: Optional[np.ndarray],  # uint64 fnv1a per key (None = compute)
    now_ms: int,
    expires: Optional[np.ndarray] = None,  # int64 [n] TTL mirror writes
    threads: Optional[int] = None,  # None = GUBER_MULTI_THREADS or ncpu
):
    """One FFI call for the sharded engine's whole host tier: shard
    routing, per-table interning/LRU/eviction, round assignment, TTL
    mirror, and the shard-grouped (slot, round)-sorted dispatch order.

    Returns (max_round, shard, slots, rounds, order, shard_counts,
    evicted, evict_shard, evict_rounds) — all numpy.  The caller must
    pass NATIVE tables only (the sharded engine gates on that)."""
    n_sh = len(tables)
    n = len(offsets) - 1
    lib = tables[0]._lib
    if threads is None:
        threads = _default_threads() or min(n_sh, os.cpu_count() or 1)
    buf_arr = np.ascontiguousarray(buf_arr, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    if hashes is not None:
        hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    if expires is not None:
        expires = np.ascontiguousarray(expires, dtype=np.int64)
    shard = np.empty(n, dtype=np.int32)
    slots = np.empty(n, dtype=np.int32)
    rounds = np.empty(n, dtype=np.int32)
    order = np.empty(n, dtype=np.int64)
    shard_counts = np.empty(n_sh, dtype=np.int64)
    evicted = np.empty(n if n else 1, dtype=np.int32)
    evict_shard = np.empty(n if n else 1, dtype=np.int32)
    evict_rounds = np.empty(n if n else 1, dtype=np.int32)
    n_evicted = np.zeros(1, dtype=np.int64)
    stats = np.zeros(4 * n_sh, dtype=np.int64)
    ptrs = (ctypes.c_void_p * n_sh)(*[t._t for t in tables])
    max_round = lib.git_multi_schedule(
        ptrs,
        n_sh,
        _ptr(buf_arr),
        _ptr(offsets),
        _ptr(hashes) if hashes is not None else None,
        n,
        now_ms,
        _ptr(expires) if expires is not None else None,
        _ptr(shard),
        _ptr(slots),
        _ptr(rounds),
        _ptr(order),
        _ptr(shard_counts),
        _ptr(evicted),
        _ptr(evict_shard),
        _ptr(evict_rounds),
        _ptr(n_evicted),
        _ptr(stats),
        int(threads),
    )
    for sh, t in enumerate(tables):
        off = t._stat_off
        t.hits = int(stats[4 * sh + 0]) - off[0]
        t.misses = int(stats[4 * sh + 1]) - off[1]
        t.evictions = int(stats[4 * sh + 2]) - off[2]
        t.unexpired_evictions = int(stats[4 * sh + 3]) - off[3]
    ne = int(n_evicted[0])
    return (
        int(max_round), shard, slots, rounds, order, shard_counts,
        evicted[:ne], evict_shard[:ne], evict_rounds[:ne],
    )


def make_intern_table(capacity: int):
    """Native table when buildable, Python fallback otherwise."""
    try:
        return NativeInternTable(capacity)
    except (RuntimeError, OSError):
        from gubernator_tpu.core.interning import InternTable

        return InternTable(capacity)
