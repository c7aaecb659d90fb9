"""Paged device bucket state: page-table key capacity with LRU host
spill (PERF.md §30; ROADMAP item 1).

The dense plane allocates `capacity` bucket rows on device at boot and
can never serve more keys than that.  This plane splits the LOGICAL
slot space into fixed-size pages (GUBER_PAGE_SIZE rows) and keeps only
GUBER_PAGED_RESIDENT of them resident in the device state array (the
"frames"); the rest live as raw packed column words in a host-side
page store.  The layout follows the Ragged Paged Attention discipline
(PAPERS.md): the kernels never learn about pages — the host translates
logical slot → (page, row) → frame*page_size + row BEFORE packing a
batch, so every step program gathers and scatters through the same
indirection by construction, and keeps its dense shape at the (much
smaller) device-resident capacity.

Residency is a two-hand-clock over frames: every batch sets the
reference bit of the pages it touches; the eviction hand clears bits
as it sweeps and evicts the first unreferenced, unpinned frame
(pinned = resident pages of the batch currently being translated — a
fault can never evict a page the same batch needs).  Pages the
hot-key sketch (utils/hotkeys.py, via `hot_slots_provider`) currently
ranks hot get one extra pass of grace per refresh, so a burst of cold
scans cannot flush the measured working set.

Spill and refill reuse the bulk-fidelity machinery the handoff plane
proved: raw packed words move (ops/bucket_kernel.gather_page_words /
load_page_words), so an evict→spill→refill roundtrip is bit-exact —
including the leaky 32.32 fixed-point remaining — with ONE d2h (spill
rides the engine's readback combiner) and one donated h2d update
(refill) per page.  Faults are handled under the engine lock after a
pump flush (the core/pump.py ordering contract), and the refill is
enqueued BEFORE the faulting batch's kernel, so the answer is served
from the restored row in the same window; resident-only batches never
pay any of this.  Every fault/spill is counted
(gubernator_paged_{faults,spills,...}; `device.page_fault` in the
stage budget) — the plane is never silently slow.

The host page store also tracks what the device cannot: the expiry
sweep of NON-resident pages decodes occupancy + expire_at straight
from the host words (`sweep_host`), so TTL reclamation never faults a
cold page back in just to find it empty.
"""

from __future__ import annotations

import time as _time
from typing import Callable, List, Optional, Set, Tuple

import numpy as np

from gubernator_tpu.ops.bucket_kernel import (
    PAGE_WORD_ROWS,
    BucketState,
    _HI11,
    gather_page_words,
    load_page_words,
    pack_state_host,
    unpack_state_host,
)

_I32 = np.int32
_I64 = np.int64

# BucketState columns carried as uint32 (bitcast to int32 in the page
# word block; restored via .view on the host side).
_U32_FIELDS = frozenset(
    (
        "t0_lo",
        "expire_lo",
        "invalid_lo",
        "duration_lo",
        "limit_lo",
        "rem_lo",
        "burst_lo",
    )
)

# Row indexes of the fields sweep_host decodes (field order is the
# BucketState layout — pinned by PAGE_WORD_ROWS construction).
_ROW = {name: i for i, name in enumerate(BucketState._fields)}

# Non-resident pages scanned per sweep_host call (mirrors the device
# sweep's SWEEP_WINDOW bounding: incremental, cursor-resumed).
SWEEP_HOST_PAGES = 4096

# Consult the hot-slots provider at most once per this many faults —
# top_rates() walks the sketch; per-fault would tax the fault path it
# is meant to protect.
_HOT_REFRESH_FAULTS = 64


def words_as_state(words: np.ndarray) -> BucketState:
    """View a [PAGE_WORD_ROWS, P] int32 block as host state columns
    (uint32 views where the layout says so) — lets the host reuse
    unpack_state_host on spilled pages verbatim."""
    cols = {}
    for i, name in enumerate(BucketState._fields):
        c = words[i]
        cols[name] = c.view(np.uint32) if name in _U32_FIELDS else c
    return BucketState(**cols)


def state_as_words(cols: dict) -> np.ndarray:
    """Inverse of `words_as_state` for pack_state_host output: stack
    the 12 column arrays into one int32 word block."""
    rows = []
    for name in BucketState._fields:
        c = np.asarray(cols[name])
        rows.append(c.view(np.int32) if c.dtype == np.uint32 else c)
    return np.stack(rows).astype(np.int32, copy=False)


class PagePlane:
    """Page table + frame residency + host spill store for one engine.

    All mutating entry points run under the owning engine's lock (the
    engine calls them from its own locked sections); `collect`-style
    readers only touch plain ints/arrays.
    """

    def __init__(
        self,
        logical_capacity: int,
        page_size: int,
        resident_pages: int,
    ) -> None:
        if page_size < 16 or page_size & (page_size - 1):
            raise ValueError("page_size must be a power of two >= 16")
        self.page_size = page_size
        self.page_shift = page_size.bit_length() - 1
        self.page_mask = page_size - 1
        self.logical_capacity = logical_capacity
        self.num_pages = -(-logical_capacity // page_size)
        frames = resident_pages or self.num_pages
        self.frames = max(2, min(frames, self.num_pages))
        self.device_capacity = self.frames * page_size

        # Page table: logical page → device frame (-1 = non-resident),
        # and the inverse frame → page.  Boot residency is the first
        # `frames` pages: the intern free list allocates slots
        # ascending, so a cold node fills resident pages first and
        # never faults until the key space outgrows the frames.
        self.frame_of = np.full(self.num_pages, -1, dtype=_I32)
        self.frame_of[: self.frames] = np.arange(self.frames, dtype=_I32)
        self.page_of = np.arange(self.frames, dtype=_I64)
        # Two-hand-clock state.
        self._ref = np.zeros(self.frames, dtype=bool)
        self._hand = 0
        # Host page store: raw packed words per page.  Allocated in
        # full up front (48 B/row — the whole point is that host DRAM
        # is 10-100x cheaper than device HBM); pages that were never
        # touched spill as all-zeros without a device gather.
        self.host_words = np.zeros(
            (self.num_pages, PAGE_WORD_ROWS, page_size), dtype=_I32
        )
        self._ever_used = np.zeros(self.num_pages, dtype=bool)
        self._ever_used[: self.frames] = True  # boot-resident pages
        self._sweep_page_cursor = 0

        # Heat feed: a callable returning the currently-hot LOGICAL
        # slots (the service wires the hot-key sketch's top_rates()
        # through the intern table here); refreshed lazily on faults.
        self.hot_slots_provider: Optional[Callable[[], List[int]]] = None
        self._hot_pages: Set[int] = set()
        self._faults_since_hot_refresh = 0

        # Counters + stage timers (exported as gubernator_paged_* and
        # the device.page_fault stage — utils/metrics.py, service.py).
        self.faults = 0
        self.spills = 0
        self.refills = 0
        from gubernator_tpu.utils.metrics import DurationStat

        # Whole fault-path wall per faulted page (victim pick + spill
        # + refill): the `device.page_fault` stage budget entry.
        self.fault_duration = DurationStat()
        # The refill half alone (h2d + donated page write dispatch) —
        # what a faulting request actually waits on before its window.
        self.refill_wait = DurationStat()
        # The spill half alone (device gather + combined d2h) — the
        # bench artifact's spill-p99.
        self.spill_duration = DurationStat()

    # -- translation ----------------------------------------------------

    def pages_of(self, slots: np.ndarray) -> np.ndarray:
        return slots >> self.page_shift

    def translate(self, engine, slots: np.ndarray) -> np.ndarray:
        """Logical slots → device slots, faulting non-resident pages
        in first.  Engine lock held; flushes the pump before touching
        residency (ordering contract, core/pump.py)."""
        pages = slots >> self.page_shift
        upages = np.unique(pages)
        if len(upages) > self.frames:
            raise RuntimeError(
                f"batch touches {len(upages)} pages > {self.frames} "
                "resident frames (engine segmentation should have "
                "split it)"
            )
        frames = self.frame_of[upages]
        missing = upages[frames < 0]
        if len(missing):
            engine._flush_pump()
            pinned = set(int(p) for p in upages)
            for p in missing.tolist():
                self._fault_one(engine, int(p), pinned)
        touched = self.frame_of[upages]
        self._ref[touched] = True
        self._ever_used[upages] = True
        dev = (
            self.frame_of[pages].astype(_I64) << self.page_shift
        ) | (slots.astype(_I64) & self.page_mask)
        return dev.astype(_I32)

    def resident_rows(self, slots: np.ndarray) -> np.ndarray:
        """Device rows for logical slots KNOWN to be resident (no
        faulting) — callers must have translated this batch already."""
        pages = slots >> self.page_shift
        return (
            (self.frame_of[pages].astype(_I64) << self.page_shift)
            | (slots.astype(_I64) & self.page_mask)
        ).astype(_I32)

    def logical_of_device(self, dev_slots: np.ndarray) -> np.ndarray:
        """Device rows → logical slots (sweep release, export)."""
        frames = np.asarray(dev_slots, dtype=_I64) >> self.page_shift
        rows = np.asarray(dev_slots, dtype=_I64) & self.page_mask
        return (self.page_of[frames] << self.page_shift) | rows

    def is_resident(self, slot: int) -> bool:
        return self.frame_of[slot >> self.page_shift] >= 0

    # -- fault path -----------------------------------------------------

    def _fault_one(self, engine, page: int, pinned: Set[int]) -> None:
        t0 = _time.monotonic()
        frame = self._pick_victim(pinned)
        victim = int(self.page_of[frame])
        self._spill(engine, frame, victim)
        self._refill(engine, page, frame)
        self.faults += 1
        self.fault_duration.observe(_time.monotonic() - t0)

    def _pick_victim(self, pinned: Set[int]) -> int:
        """Two-hand clock: clear reference bits as the hand sweeps;
        evict the first unreferenced, unpinned, not-currently-hot
        frame.  Bounded at two full revolutions plus a forced pass."""
        self._maybe_refresh_hot()
        hot = self._hot_pages
        for _ in range(2 * self.frames):
            f = self._hand
            self._hand = (f + 1) % self.frames
            page = int(self.page_of[f])
            if page in pinned:
                continue
            if self._ref[f]:
                self._ref[f] = False  # first hand: strip the ref bit
                continue
            if page in hot:
                hot.discard(page)  # one grace pass per hot refresh
                continue
            return f
        # Every frame pinned or endlessly re-referenced within the
        # bound: force the first unpinned frame (translate() already
        # guarantees at least one exists).
        for f in range(self.frames):
            if int(self.page_of[f]) not in pinned:
                return f
        raise RuntimeError("no evictable frame (all pinned)")

    def _maybe_refresh_hot(self) -> None:
        if self.hot_slots_provider is None:
            return
        self._faults_since_hot_refresh += 1
        if (
            self._faults_since_hot_refresh < _HOT_REFRESH_FAULTS
            and self._hot_pages
        ):
            return
        self._faults_since_hot_refresh = 0
        try:
            slots = self.hot_slots_provider()
        except Exception:  # noqa: BLE001 — heat is advisory, never fatal
            return
        self._hot_pages = {int(s) >> self.page_shift for s in slots}

    def _spill(self, engine, frame: int, page: int) -> None:
        """Evict `page` from `frame`: raw words → host store.  Pages
        never touched on device spill as zeros without a gather."""
        if self._ever_used[page]:
            t0 = _time.monotonic()
            ticket = engine.readback.register(
                gather_page_words(
                    engine._state,
                    np.int32(frame << self.page_shift),
                    self.page_size,
                )
            )
            engine.dispatches_total += 1
            self.host_words[page] = ticket.fetch()
            self.spills += 1
            self.spill_duration.observe(_time.monotonic() - t0)
        self.frame_of[page] = -1

    def _refill(self, engine, page: int, frame: int) -> None:
        """Restore `page` from the host store into `frame` — one h2d
        + one donated in-place page write, enqueued ahead of the
        faulting batch's kernel (same-window answer)."""
        t0 = _time.monotonic()
        engine._state = load_page_words(
            engine._state,
            np.int32(frame << self.page_shift),
            self.host_words[page],
        )
        engine.dispatches_total += 1
        self.refills += 1
        self.frame_of[page] = frame
        self.page_of[frame] = page
        self._ref[frame] = True
        self.refill_wait.observe(_time.monotonic() - t0)

    # -- host-side mutations (non-resident pages) -----------------------

    def clear_host_slots(self, slots: np.ndarray) -> None:
        """Drop the occupied bit of non-resident logical slots in the
        host store (the eviction-clear twin of clear_occupied)."""
        pages = slots >> self.page_shift
        rows = slots & self.page_mask
        self.host_words[pages, _ROW["meta"], rows] &= ~np.int32(1)

    def host_restore(self, restores: List[Tuple[int, object]]) -> None:
        """Write restored CacheItems straight into non-resident pages'
        host words — checkpoint restore must NOT fault the whole key
        space through the frames (the core/engine.py:248 small fix).
        `restores` = [(logical_slot, CacheItem)]."""
        from gubernator_tpu.core.engine import build_restore_record

        n = len(restores)
        rec = build_restore_record(restores, self.logical_capacity, size=n)
        packed = pack_state_host(
            {
                "occupied": np.ones(n, dtype=bool),
                "algo": rec["algo"],
                "status": rec["status"],
                "t0": rec["t0"],
                "invalid": rec["invalid_at"],
                "expire": rec["expire_at"],
                "duration": rec["duration"],
                "limit": rec["limit"],
                "remaining": rec["remaining"],
                "remf_hi": rec["remf_hi"],
                "remf_lo": rec["remf_lo"],
                "burst": rec["burst"],
            }
        )
        words = state_as_words(packed)  # [12, n]
        slots = rec["slot"].astype(_I64)
        pages = slots >> self.page_shift
        rows = slots & self.page_mask
        self.host_words[pages, :, rows] = words.T
        self._ever_used[np.unique(pages)] = True

    def host_rows(self, page: int) -> dict:
        """Decode one non-resident page's host words into the logical
        columns of unpack_state_host (export/handoff of cold rows)."""
        return unpack_state_host(words_as_state(self.host_words[page]))

    def nonresident_used_pages(self) -> np.ndarray:
        """Pages whose rows exist only in the host store."""
        return np.nonzero((self.frame_of < 0) & self._ever_used)[0]

    def sweep_host(self, now_ms: int) -> np.ndarray:
        """TTL sweep of non-resident pages from the host words alone:
        returns the freed LOGICAL slots (caller releases them from the
        intern table) and drops their occupied bits.  Incremental —
        at most SWEEP_HOST_PAGES pages per call, cursor-resumed — and
        never faults a page in (the whole point: the device sweep
        skips what this one covers)."""
        cand = self.nonresident_used_pages()
        if len(cand) == 0:
            return np.empty(0, dtype=_I64)
        if len(cand) > SWEEP_HOST_PAGES:
            start = self._sweep_page_cursor % len(cand)
            take = np.roll(cand, -start)[:SWEEP_HOST_PAGES]
            self._sweep_page_cursor = start + SWEEP_HOST_PAGES
        else:
            take = cand
            self._sweep_page_cursor = 0
        w = self.host_words[take]  # [K, 12, P]
        meta = w[:, _ROW["meta"], :]
        occ = (meta & 1) != 0
        exp_lo = w[:, _ROW["expire_lo"], :].view(np.uint32).astype(_I64)
        hi2 = w[:, _ROW["hi2"], :]
        expire = ((hi2 & _HI11).astype(_I64) << 32) | exp_lo
        # Same boundary as the device sweep: expire_at < now is dead,
        # equality still serves (lrucache.go semantics).
        dead = occ & (expire < now_ms)
        pk, rows = np.nonzero(dead)
        if len(pk) == 0:
            return np.empty(0, dtype=_I64)
        pages = take[pk]
        self.host_words[pages, _ROW["meta"], rows] &= ~np.int32(1)
        return (pages.astype(_I64) << self.page_shift) | rows
