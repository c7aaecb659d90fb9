"""Readback combiner: many device→host copies, ONE transfer.

Every device→host read carries a fixed cost whatever its payload, and
a serving step's compute is small next to it, so the design reason is
fewer transfers per decision.

This module batches outstanding readbacks engine-wide: every dispatched
step output registers a Ticket instead of calling `np.asarray` itself;
the first caller that needs a result becomes the LEADER, stacks all
outstanding same-shape outputs on device with one tiny jitted
`jnp.stack` program, reads the stack back in ONE transfer, and
distributes host slices to every ticket it covered.

Group shapes are bounded for XLA: stacks cover pow-of-two counts
(1..MAX_GROUP) of identical [rows, width] outputs (counts are rounded
up by repeating the last handle — duplicate transfer bytes are cheap
next to the per-transfer fixed cost), so the program universe is
{widths} × {2,4,8,16}, all precompilable in warmup.

The reference has no analog: its decisions are host-memory reads
(lrucache.go); this is the TPU-first replacement for "the cache is in
HBM on the far side of the host↔device link".

Page spills (GUBER_PAGED, core/paging.py) ride the same combiner: a
cold page's [12, page_size] word gather registers a Ticket like any
step output, so an eviction that lands while decision readbacks are
outstanding shares their transfer instead of paying its own (the
spill is itself one more same-shape handle in the stack).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.utils.metrics import stage

MAX_GROUP = 16


def read_wait(wait_stat) -> stage:
    """The stage of a serving thread blocked on a device array: the
    leader's transfer and a follower's wait for it, here and in the
    pump's group read (core/pump.py) — one observation per blocked
    thread, so an RPC's budget adds up.  A wait: histogram and request
    span, never a profiler annotation (a waiting thread would claim
    every idle gap of the device)."""
    return stage("device.readback", wait_stat, work=False)


class Ticket:
    """One registered readback.  `fetch()` returns the host ndarray."""

    __slots__ = ("handle", "host", "error", "combiner", "event")

    def __init__(self, combiner: "ReadbackCombiner", handle) -> None:
        self.combiner = combiner
        self.handle = handle  # device array until materialized
        self.host: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.event = threading.Event()

    def fetch(self) -> np.ndarray:
        if self.host is None and self.error is None:
            self.combiner._fetch(self)
        if self.error is not None:
            raise self.error
        return self.host


class ReadbackCombiner:
    """Engine-wide queue of pending device→host readbacks."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._queue: List[Ticket] = []  # guberlint: guarded-by _lock
        self._draining = False  # guberlint: guarded-by _lock
        # Program cache: deliberately unguarded — concurrent leaders
        # may race-build the same stack program; dict assignment is
        # atomic and last-wins costs one duplicate compile (warmup
        # precompiles the whole universe anyway).
        self._stack_cache: Dict[Tuple, object] = {}
        # Double-buffered device→host windows (GUBER_WINDOW_DEPTH ≥ 2,
        # shared knob with core/pump.py): a leader that drains a group
        # also stacks the NEXT full group and starts its async copy
        # before distributing the first, so window N+1's transfer
        # overlaps window N's host-side distribution (PERF.md §24).
        from gubernator_tpu.config import env_window_depth

        self.window_depth = env_window_depth()
        # Telemetry (PERF.md): transfers saved = registered -
        # transfers.
        self.registered = 0  # guberlint: guarded-by _lock
        self.transfers = 0  # guberlint: guarded-by _lock
        self.stacked = 0  # guberlint: guarded-by _lock
        from gubernator_tpu.utils.metrics import DurationStat

        # Wall time of the blocking d2h materialization (the
        # device.readback stage of the §24 device budget).
        self.transfer_duration = DurationStat()

    def register(self, handle) -> Ticket:
        """Called at dispatch time (engine lock held is fine — this
        only appends).  The handle's transfer is DEFERRED: no
        copy_to_host_async here, the stacked read would transfer the
        same bytes twice."""
        t = Ticket(self, handle)
        with self._lock:
            self._queue.append(t)
            self.registered += 1
            overflow = (
                len(self._queue) > 4 * MAX_GROUP and not self._draining
            )
            if overflow:
                self._draining = True
        if overflow:
            # Fire-and-forget callers never fetch; bound device memory
            # by draining the oldest group on their behalf — OFF this
            # thread, which may hold the engine lock (a blocking d2h
            # here would stall every serving thread for the transfer).
            # guberlint: ok thread — one-shot bounded drain (a single
            # d2h transfer); completion is tracked by _draining under _lock,
            # and at most one is in flight at a time.
            threading.Thread(
                target=self._drain_detached,
                name="guber-readback-drain",
                daemon=True,
            ).start()
        return t

    def _drain_detached(self) -> None:
        try:
            self._drain_oldest()
        finally:
            with self._lock:
                self._draining = False

    # -- leader path ---------------------------------------------------

    def _stack_program(self, count: int, shape, dtype):
        # By the dtype's name: warm-up says `jnp.int32`, a handle says
        # `dtype('int32')`, and `str()` of the two differs — the ladder
        # warmed programs serving never found (PR 32).
        key = (count, tuple(shape), np.dtype(dtype).name)
        prog = self._stack_cache.get(key)
        if prog is None:
            # guberlint: shapes fan-in/shape/dtype pinned by the cache key; universe {widths} x {2,4,8,16}, precompiled in warmup_stacks
            def stack_outputs(*xs):
                return jnp.stack(xs)

            prog = jax.jit(stack_outputs)
            self._stack_cache[key] = prog
        return prog

    def _take_group_locked(self, want: Optional[Ticket]) -> List[Ticket]:
        """Pick up to MAX_GROUP queued tickets sharing one shape class
        (the caller's if it is still queued, else the oldest entry's)
        and remove them from the queue.  Caller holds the lock."""
        anchor = want if want in self._queue else (
            self._queue[0] if self._queue else None
        )
        if anchor is None:
            return []
        shape, dtype = anchor.handle.shape, anchor.handle.dtype
        group = [
            t for t in self._queue
            if t.handle.shape == shape and t.handle.dtype == dtype
        ][:MAX_GROUP]
        if want is not None and want in self._queue and want not in group:
            # More than MAX_GROUP older same-shape entries: make sure
            # the caller's own ticket rides this transfer.
            group[-1] = want
        taken = set(map(id, group))
        self._queue = [t for t in self._queue if id(t) not in taken]
        return group

    def _take_same_shape_locked(self, shape, dtype) -> List[Ticket]:
        """Claim up to MAX_GROUP queued tickets of exactly this shape
        class (the window-prefetch path: a leader must NOT steal other
        shape classes — concurrent leaders materialize those in
        parallel).  Caller holds the lock."""
        group = [
            t for t in self._queue
            if t.handle.shape == shape and t.handle.dtype == dtype
        ][:MAX_GROUP]
        if group:
            taken = set(map(id, group))
            self._queue = [t for t in self._queue if id(t) not in taken]
        return group

    def _fetch(self, ticket: Ticket) -> None:
        while ticket.host is None and ticket.error is None:
            with self._lock:
                if ticket.host is not None or ticket.error is not None:
                    return
                in_queue = ticket in self._queue
                group = self._take_group_locked(ticket) if in_queue else None
                extra: List[List[Ticket]] = []
                if group is not None and self.window_depth >= 2:
                    # Window prefetch: claim up to depth-1 FURTHER
                    # windows of the SAME shape class so their
                    # transfers start before this one distributes.
                    # Other shape classes stay queued for their own
                    # leaders (concurrent materialization preserved).
                    shape = group[0].handle.shape
                    dtype = group[0].handle.dtype
                    while len(extra) < self.window_depth - 1:
                        nxt = self._take_same_shape_locked(shape, dtype)
                        if not nxt:
                            break
                        extra.append(nxt)
            if group is None:
                # Another leader holds this ticket in its group: its
                # materialize ALWAYS sets host or error, then the
                # event.  Wait outside the lock.
                with read_wait(self.transfer_duration):
                    ticket.event.wait()
                continue
            self._materialize_windows([group] + extra)
            # Our group may not have included `ticket` only if shapes
            # raced; loop re-checks.

    # -- window-scoped read (the native front's serve thread) ----------

    def start_own(self, tickets: List[Ticket]):
        """Claim exactly `tickets` — one batch's own, registered during
        its dispatch — stack the same-shape ones and start their copies
        to the host; `land` takes what this returns.  Unlike a leader
        (`_fetch`), it leaves every other queued ticket where it is: a
        thread that keeps one batch in flight while it launches the
        next must not wait for the next one's step when it reads the
        first.  A ticket some leader has already claimed stays that
        leader's; its `fetch` waits for it as ever."""
        mine = set(map(id, tickets))
        with self._lock:
            own = [t for t in self._queue if id(t) in mine]
            self._queue = [t for t in self._queue if id(t) not in mine]
        classes: Dict[Tuple, List[Ticket]] = {}
        for t in own:
            classes.setdefault((t.handle.shape, t.handle.dtype), []).append(t)
        groups = [
            c[i : i + MAX_GROUP]
            for c in classes.values()
            for i in range(0, len(c), MAX_GROUP)
        ]
        try:
            return groups, [self._stack_async(g) for g in groups]
        except BaseException as e:  # noqa: BLE001
            for t in own:  # off the queue: fail them closed
                t.error = e
                t.event.set()
            raise

    def land(self, started) -> None:
        """Materialize what `start_own` started, and nothing else."""
        groups, staged = started
        self._materialize_windows(groups, staged)

    def _drain_oldest(self) -> None:
        with self._lock:
            group = self._take_group_locked(None)
        if group:
            self._materialize(group)

    def _materialize(self, group: List[Ticket]) -> None:
        self._materialize_windows([group])

    def _materialize_windows(
        self, groups: List[List[Ticket]], staged: Optional[list] = None
    ) -> None:
        """Stack every claimed window and start ALL their async device→
        host copies first (unless `start_own` already has: `staged`),
        then distribute in order: window N+1's transfer overlaps window
        N's host-side slicing.  Any failure fails every unfulfilled
        ticket of every claimed window (they are already off the queue;
        conservative, matches the old single-group contract)."""
        try:
            if staged is None:
                staged = [self._stack_async(g) for g in groups]
            for g, stacked in zip(groups, staged):
                self._distribute(g, stacked)
        except BaseException as e:  # noqa: BLE001
            for g in groups:
                for t in g:
                    if t.host is None and t.error is None:
                        t.error = e
            raise
        finally:
            for g in groups:
                for t in g:
                    t.event.set()

    def _stack_async(self, group: List[Ticket]):
        """Stack one group on device (singletons pass through) and
        start its async copy; returns the handle to materialize."""
        k = len(group)
        with self._lock:
            # Concurrent leaders (different shape groups) materialize
            # in parallel: unlocked `+= 1` here lost increments and
            # under-reported the transfer savings PERF.md is based on.
            self.transfers += 1
        if k == 1:
            stacked = group[0].handle
        else:
            # Round the stack fan-in up to a power of two by repeating
            # the last handle — bounded program universe (module doc).
            size = 2
            while size < k:
                size *= 2
            handles = [t.handle for t in group]
            handles += [handles[-1]] * (size - k)
            prog = self._stack_program(
                size, handles[0].shape, handles[0].dtype
            )
            stacked = prog(*handles)
            with self._lock:
                self.stacked += k
        try:
            stacked.copy_to_host_async()
        except AttributeError:
            pass  # non-jax handle (tests stub arrays)
        return stacked

    def _distribute(self, group: List[Ticket], stacked) -> None:
        # Hot path under feeder-driven load: ONE call, and ONE
        # transfer, for the whole group.
        with read_wait(self.transfer_duration):
            host = np.asarray(stacked)
        if len(group) == 1:
            group[0].host = host
            group[0].handle = None
            return
        for i, t in enumerate(group):
            t.host = host[i]
            t.handle = None

    # -- warmup --------------------------------------------------------

    def warmup_stacks(self, shape, dtype) -> None:
        """Precompile the stack programs for one output shape (called
        from engine warmup per ladder width so serving never pays an
        XLA compile)."""
        z = jnp.zeros(shape, dtype=dtype)
        size = 2
        while size <= MAX_GROUP:
            np.asarray(self._stack_program(size, shape, dtype)(
                *([z] * size)
            ))
            size *= 2
