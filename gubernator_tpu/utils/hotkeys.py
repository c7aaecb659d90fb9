"""Hot-key attribution: a space-saving top-K sketch over decision keys.

Metwally's space-saving algorithm with a fixed capacity of counters:
every offered (key, hits) either bumps its existing counter or evicts
the minimum counter, inheriting its count as the new entry's error
bound.  Guarantees: any key with true count > count_min is IN the
table, and each reported count over-estimates by at most its recorded
`err`.  That is exactly the contract /debug/hotkeys needs — "which
keys are the load" with an honest error bar — in O(capacity) memory
regardless of key cardinality.

Windowed decay: alongside the cumulative counters, every tracked key
carries a two-window hit counter (current + previous window of
`window_s` seconds, rotated lazily on touch/read), so `top_rates()`
reports the *current* offered rate — a key hot an hour ago reads ~0
even though its cumulative count still ranks it.  The replication
plane (cluster/replication.py) promotes and — crucially — demotes off
these rates; demotion on the cumulative counts would never happen.
Rates come with the last observed (limit, duration) when the offering
path carries them, which is what lets the promotion path split a hot
key's limit into replica leases without an engine export sweep.

Two tiers, one set of answers.  `SpaceSaving` owns the clock, the
window arithmetic and the ordering of reads; the table behind it is
the native one (core/native/hotkeys.cpp, through ctypes) whenever the
library loads and the Python one (`_PyTable`: a dict and a lazy heap)
when it does not — GUBERNATOR_TPU_NATIVE=0, no compiler.  There is no
setting between them; `stats()["tier"]` says which serves, and
tests/test_hotkeys.py holds the native table equal to the Python one
on every read.

Batch entry points pre-aggregate with numpy on the decoded wire
columns (one np.unique per batch), so a served RPC pays
O(batch log batch) numpy plus ONE native call over its unique keys
with the interpreter lock released: the per-key walk — a lookup and,
for a key not in the table, an eviction — no longer runs as Python on
the RPC's thread while the others wait for the lock (PERF.md §5).  A
call over a handful of keys (a single-item RPC, the ledger's one
offer, a read of one key) keeps the lock: it is shorter than a
hand-over of it.  On the Python tier that walk is O(unique)
interpreted code, milliseconds a 1,000-item RPC.  The whole surface
is gated by GUBER_HOTKEYS; disabled costs one attribute check per
batch.  GUBER_HOTKEYS_WINDOW sets the decay window.
"""

from __future__ import annotations

import ctypes
import heapq
import logging
import threading
import time
from itertools import repeat
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from gubernator_tpu.core.native_build import ensure_built

log = logging.getLogger("gubernator_tpu.hotkeys")

# Table row layout (a list in the Python table, a struct in the native
# one); a snapshot row is (key, *these).
_COUNT = 0   # cumulative estimated count (space-saving)
_ERR = 1     # over-estimate bound inherited at eviction
_WID = 2     # window id of the _WIN counter
_WIN = 3     # hits offered in window _WID
_PREV = 4    # hits offered in window _WID - 1
_LIMIT = 5   # last observed request limit (0 = never seen)
_DUR = 6     # last observed request duration ms (0 = never seen)
_FIELDS = 7

# A native call over fewer rows than this keeps the interpreter lock.
# The walk costs ~0.3 µs a key; letting go of the lock for less than a
# hand-over costs puts the thread at the back of the queue for it, and
# on single-item RPCs (100 callers, 32 listener threads) that read as
# `host.hotkeys_us` 228 → 512 and 5 % of the rate (my chip runs, PR 29).
_RELEASE_ROWS = 64

_I64, _PTR = ctypes.c_int64, ctypes.c_void_p
_SIGNATURES = {
    "hk_new": (_PTR, [_I64]),
    "hk_free": (None, [_PTR]),
    "hk_set_capacity": (None, [_PTR, _I64]),
    # (handle, key, len, n, wid, limit, duration)
    "hk_offer": (None, [_PTR, ctypes.c_char_p] + [_I64] * 5),
    # (handle, buf, buf_len, starts, lens, weight, limit, duration,
    #  rows, wid)
    "hk_offer_batch": (None, [_PTR, _PTR, _I64] + [_PTR] * 5 + [_I64, _I64]),
    "hk_stats": (None, [_PTR, _PTR]),
    # (handle, rotate, wid, fields, row_cap, key_buf, key_cap,
    #  key_offsets)
    "hk_snapshot": (
        _I64, [_PTR, ctypes.c_int32, _I64, _PTR, _I64, _PTR, _I64, _PTR]
    ),
    "hk_window": (ctypes.c_int32, [_PTR, ctypes.c_char_p, _I64, _I64, _PTR]),
}

_lib = None
_lib_lock = threading.Lock()


def load():
    """Load (building if needed) the native table: the library twice
    over one image, `(released, held)` — calls through the first drop
    the interpreter lock (ctypes.CDLL), calls through the second keep
    it (ctypes.PyDLL).  None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = ensure_built("hotkeys")
        if so is None:
            return None
        libs = (ctypes.CDLL(str(so)), ctypes.PyDLL(str(so)))
        for lib in libs:
            for name, (restype, argtypes) in _SIGNATURES.items():
                fn = getattr(lib, name)
                fn.restype, fn.argtypes = restype, argtypes
        _lib = libs
        return _lib


def _rotate(it: List[int], wid: int) -> None:
    """Lazily shift the two-window counters to window `wid`."""
    gap = wid - it[_WID]
    if gap == 0:
        return
    it[_PREV] = it[_WIN] if gap == 1 else 0
    it[_WIN] = 0
    it[_WID] = wid


def _i64(a) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=np.int64)


class _PyTable:
    """The reference table and the fallback tier (thread-safe).

    Eviction uses a LAZY MIN-HEAP of (count-at-push, key) entries
    instead of an O(capacity) min() scan: counts only grow, so a heap
    entry is either current (evictable) or stale (its key was bumped
    or already evicted — pop and, if live, re-push at the current
    count).  The loop therefore always ends on the live entry with
    the least (count, key bytes), which is the rule the native table
    implements directly.  Amortized O(log K) per eviction."""

    tier = "python"

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        # key -> [count, err, wid, win, prev, limit, duration]
        self._items: Dict[bytes, List[int]] = {}
        # guberlint: guard _heap by _lock
        self._heap: list = []  # lazy (count_at_push, key) min-heap
        self._lock = threading.Lock()  # guberlint: guards _items
        self.offered = 0  # guberlint: guarded-by _lock

    def _pop_min_locked(self) -> tuple:
        """(min_key, min_count) via the lazy heap; stale entries are
        dropped or refreshed on the way down."""
        while True:
            count, key = heapq.heappop(self._heap)
            it = self._items.get(key)
            if it is None:
                continue  # evicted earlier; stale entry
            if it[_COUNT] != count:
                # Bumped since pushed: refresh at the current count.
                heapq.heappush(self._heap, (it[_COUNT], key))
                continue
            return key, count

    def _offer_locked(
        self, key: bytes, n: int, wid: int, lim: int, dur: int
    ) -> None:
        self.offered += n
        it = self._items.get(key)
        if it is not None:
            it[_COUNT] += n  # heap entry goes stale; refreshed lazily
            _rotate(it, wid)
            it[_WIN] += n
            if lim:
                it[_LIMIT] = lim
                it[_DUR] = dur
            return
        if len(self._items) < self.capacity:
            self._items[key] = [n, 0, wid, n, 0, lim, dur]
            heapq.heappush(self._heap, (n, key))
            return
        # Evict the minimum counter; the newcomer inherits its count
        # as the over-estimate bound (Metwally et al. 2005).  The
        # window counters start fresh — rates carry no inherited
        # error, only the cumulative count does.
        min_key, min_count = self._pop_min_locked()
        del self._items[min_key]
        self._items[key] = [min_count + n, min_count, wid, n, 0, lim, dur]
        heapq.heappush(self._heap, (min_count + n, key))

    def offer_rows(self, rows: Iterable[tuple], wid: int) -> None:
        with self._lock:
            for key, n, lim, dur in rows:
                self._offer_locked(key, n, wid, lim, dur)

    def offer_grouped(self, buf, starts, lens, weight, lim, dur, wid) -> None:
        self.offer_rows(
            (
                (buf[a:a + l].tobytes(), w, li, du)
                for a, l, w, li, du in zip(
                    starts.tolist(), lens.tolist(), weight.tolist(),
                    lim.tolist() if lim is not None else repeat(0),
                    dur.tolist() if dur is not None else repeat(0),
                )
            ),
            wid,
        )

    def snapshot(self, wid: Optional[int] = None) -> List[tuple]:
        with self._lock:
            if wid is not None:
                for it in self._items.values():
                    _rotate(it, wid)
            return [(k, *it) for k, it in self._items.items()]

    def window(self, key: bytes, wid: int) -> Optional[Tuple[int, int]]:
        with self._lock:
            it = self._items.get(key)
            if it is None:
                return None
            _rotate(it, wid)
            return it[_PREV], it[_WIN]

    def stats(self) -> Tuple[int, int, int]:
        with self._lock:
            return self.capacity, len(self._items), self.offered


class _NativeTable:
    """The table in core/native/hotkeys.cpp.  No Python-side lock and
    nothing for guberlint to guard here: the handle is fixed at
    construction and every call is one native entry that takes the
    table's own mutex (its fields carry the annotations, in the .cpp).
    The walk over a batch's keys and the snapshot run with the
    interpreter lock released (`_released`); a call too short to be worth
    a hand-over of that lock keeps it (`_held`, see _RELEASE_ROWS)."""

    tier = "native"

    def __init__(self, libs, capacity: int) -> None:
        self._released, self._held = libs
        self._h = self._held.hk_new(capacity)
        if not self._h:
            raise MemoryError("hk_new failed")

    def __del__(self) -> None:
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._held.hk_free(h)

    @property
    def capacity(self) -> int:
        return self.stats()[0]

    @capacity.setter
    def capacity(self, value: int) -> None:
        self._held.hk_set_capacity(self._h, value)

    def offer_rows(self, rows: Iterable[tuple], wid: int) -> None:
        rows = list(rows)
        if len(rows) == 1:
            key, n, lim, dur = rows[0]
            self._held.hk_offer(self._h, key, len(key), n, wid, lim, dur)
            return
        if not rows:
            return
        keys = [r[0] for r in rows]
        lens = np.fromiter(map(len, keys), dtype=np.int64, count=len(keys))
        cols = np.array([r[1:] for r in rows], dtype=np.int64).T
        self.offer_grouped(
            np.frombuffer(b"".join(keys), dtype=np.uint8),
            np.cumsum(lens) - lens, lens, cols[0], cols[1], cols[2], wid,
        )

    def offer_grouped(self, buf, starts, lens, weight, lim, dur, wid) -> None:
        # Bounds are clamped natively (a Python slice's rule); dtype
        # and contiguity are what a pointer cannot check.
        buf = np.ascontiguousarray(buf, dtype=np.uint8)
        starts, lens, weight = _i64(starts), _i64(lens), _i64(weight)
        lim = _i64(lim) if lim is not None else None
        dur = _i64(dur) if dur is not None else None
        lib = self._released if len(starts) >= _RELEASE_ROWS else self._held
        lib.hk_offer_batch(
            self._h, buf.ctypes.data, buf.size, starts.ctypes.data,
            lens.ctypes.data, weight.ctypes.data,
            lim.ctypes.data if lim is not None else None,
            dur.ctypes.data if dur is not None else None,
            len(starts), wid,
        )

    def snapshot(self, wid: Optional[int] = None) -> List[tuple]:
        while True:
            # Sized from a stats read; the table may grow before the
            # snapshot takes its lock, which then says so (-1).
            _cap, tracked, _off, key_bytes = self._stats4()
            fields = np.empty((tracked + 16, _FIELDS), dtype=np.int64)
            keys = np.empty(key_bytes + 1024, dtype=np.uint8)
            offs = np.empty(len(fields) + 1, dtype=np.int64)
            n = self._released.hk_snapshot(
                self._h, wid is not None, wid or 0, fields.ctypes.data,
                len(fields), keys.ctypes.data, keys.size, offs.ctypes.data,
            )
            if n >= 0:
                break
        raw = keys.tobytes()
        o = offs[:n + 1].tolist()
        return [
            (raw[o[i]:o[i + 1]], *f) for i, f in enumerate(fields[:n].tolist())
        ]

    def window(self, key: bytes, wid: int) -> Optional[Tuple[int, int]]:
        out = np.empty(2, dtype=np.int64)
        if not self._held.hk_window(
            self._h, key, len(key), wid, out.ctypes.data
        ):
            return None
        return int(out[0]), int(out[1])

    def _stats4(self) -> List[int]:
        out = np.empty(4, dtype=np.int64)
        self._held.hk_stats(self._h, out.ctypes.data)
        return out.tolist()

    def stats(self) -> Tuple[int, int, int]:
        return tuple(self._stats4()[:3])


class SpaceSaving:
    """Fixed-capacity top-K counter table (thread-safe).

    `native=None` takes the native table when its library loads and
    the Python one otherwise; True / False pin a tier (the tests'
    handle on both — True raises where the library cannot be built)."""

    def __init__(
        self,
        capacity: int = 1024,
        *,
        window_s: float = 5.0,
        now=time.monotonic,
        native: Optional[bool] = None,
    ) -> None:
        capacity = max(1, capacity)
        # Decay window (seconds) for top_rates(); mutable so the bench
        # and the replication plane can tune responsiveness live.
        self.window_s = max(1e-3, window_s)
        self._now = now
        lib = load() if native is not False else None
        if native and lib is None:
            raise RuntimeError("native hot-key table unavailable")
        self._table = (
            _NativeTable(lib, capacity) if lib is not None
            else _PyTable(capacity)
        )

    @property
    def tier(self) -> str:
        return self._table.tier

    @property
    def capacity(self) -> int:
        return self._table.capacity

    @capacity.setter
    def capacity(self, value: int) -> None:
        self._table.capacity = max(1, value)

    def _wid(self) -> int:
        return int(self._now() / self.window_s)

    def offer(self, key: bytes, n: int = 1) -> None:
        self._table.offer_rows(((key, n, 0, 0),), self._wid())

    def offer_many(self, pairs) -> None:
        """(key bytes, hits) iterable under ONE lock acquisition."""
        self._table.offer_rows(
            ((key, n, 0, 0) for key, n in pairs), self._wid()
        )

    def offer_many_params(self, rows) -> None:
        """(key bytes, hits, limit, duration) iterable under ONE lock
        — the dataclass serving path's entry, carrying the request
        params the promotion plane sizes leases from."""
        self._table.offer_rows(rows, self._wid())

    def offer_columns(
        self, key_buf, key_offsets, hits, idx=None, hashes=None,
        limit=None, duration=None,
    ) -> None:
        """Decoded-wire-batch entry: with `hashes` (the decode's
        per-row fnv1a), rows group by hash in ONE np.unique pass and
        the table sees each UNIQUE key once, in ascending-hash order —
        a 1000-occurrence hot-key batch costs one update, which is
        what lets the zero-per-key-Python serve paths afford this
        hook.  (Hash identity: a 64-bit collision merges two keys'
        counts — noise far below the sketch's own error bound.)
        Without hashes every row is offered in order.  `idx`
        restricts to a subset of rows (the GLOBAL serve route's
        owned/non-owned splits reuse the same decode).
        `limit`/`duration` columns, when given, stamp each unique
        key's last-seen request params (lease sizing)."""
        offs = np.asarray(key_offsets)
        h = np.asarray(hits, dtype=np.int64)
        starts = offs[:-1]
        lens = offs[1:] - starts
        lim = np.asarray(limit) if limit is not None else None
        dur = np.asarray(duration) if duration is not None else None
        if idx is not None:
            starts, lens, h = starts[idx], lens[idx], h[idx]
            if lim is not None:
                lim, dur = lim[idx], dur[idx]
        if len(starts) == 0:
            return
        # Decisions with hits=0 are status reads; count them as one
        # observation each so read-hot keys still surface.
        weight = np.maximum(h, 1)
        if hashes is not None:
            hh = np.asarray(hashes)
            if idx is not None:
                hh = hh[idx]
            _u, first, inv = np.unique(
                hh, return_index=True, return_inverse=True
            )
            weight = np.bincount(inv, weights=weight).astype(np.int64)
            starts, lens = starts[first], lens[first]
            if lim is not None:
                lim, dur = lim[first], dur[first]
        self._table.offer_grouped(
            np.asarray(key_buf), starts, lens, weight, lim, dur, self._wid()
        )

    def top(self, n: int = 20) -> List[Tuple[bytes, int, int]]:
        """[(key, estimated count, error bound)] sorted descending."""
        rows = sorted(
            ((r[0], r[1 + _COUNT], r[1 + _ERR]) for r in self._table.snapshot()),
            key=lambda r: r[1],
            reverse=True,
        )
        return rows[:n]

    def top_rates(
        self, n: int = 20
    ) -> List[Tuple[bytes, float, int, int]]:
        """[(key, current offered hits/sec, last limit, last duration)]
        sorted by rate descending.  The rate is the sliding two-window
        estimate: the previous window's count weighted by its remaining
        overlap plus the current window's count, over one window — so a
        key that stopped being offered decays to ~0 within two windows
        regardless of its cumulative count (the demotion contract)."""
        now = self._now()
        w = self.window_s
        wid = int(now / w)
        frac = (now / w) - wid  # elapsed fraction of wid
        out: List[Tuple[bytes, float, int, int]] = []
        for r in self._table.snapshot(wid):
            rate = (r[1 + _PREV] * (1.0 - frac) + r[1 + _WIN]) / w
            if rate > 0.0:
                out.append((r[0], rate, r[1 + _LIMIT], r[1 + _DUR]))
        out.sort(key=lambda r: r[1], reverse=True)
        return out[:n]

    def rate(self, key: bytes) -> float:
        """Current offered rate (hits/sec) for one tracked key; 0.0
        when untracked or idle."""
        now = self._now()
        w = self.window_s
        wid = int(now / w)
        frac = (now / w) - wid
        pw = self._table.window(key, wid)
        if pw is None:
            return 0.0
        return (pw[0] * (1.0 - frac) + pw[1]) / w

    def stats(self) -> dict:
        capacity, tracked, offered = self._table.stats()
        return {
            "capacity": capacity,
            "tracked": tracked,
            "offered": offered,
            "tier": self.tier,
        }


def from_env() -> Optional[SpaceSaving]:
    """Build the instance-level sketch from GUBER_HOTKEYS /
    GUBER_HOTKEYS_K / GUBER_HOTKEYS_WINDOW (None when disabled), and
    say once which tier serves it."""
    import os

    if os.environ.get("GUBER_HOTKEYS", "1").strip().lower() in (
        "0", "false", "no", "off"
    ):
        return None
    try:
        k = int(os.environ.get("GUBER_HOTKEYS_K", "1024"))
    except ValueError:
        k = 1024
    try:
        window = float(os.environ.get("GUBER_HOTKEYS_WINDOW", "5.0"))
    except ValueError:
        window = 5.0
    sketch = SpaceSaving(capacity=k, window_s=window)
    if sketch.tier == "native":
        log.info(
            "hot-key sketch: native table (capacity=%d window=%gs)", k, window
        )
    else:
        log.warning(
            "hot-key sketch: native table unavailable, the Python table "
            "serves — a per-key loop under the interpreter lock on every "
            "RPC's thread (capacity=%d window=%gs)", k, window,
        )
    return sketch
