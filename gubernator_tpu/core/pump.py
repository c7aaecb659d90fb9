"""Step pump: cross-call device dispatch batching.

The readback combiner (core/readback.py) collapses device→host reads;
this module collapses the OTHER two per-step costs — the h2d upload
and the program dispatch — by queueing packed round buffers across
apply calls and running up to MAX_GROUP of them through ONE
`multi_fused_step` (lax.scan) dispatch: one h2d of [R, 16, W], one
dispatch, one prefetched d2h of [R, 5, W].  The design reason is fewer
transfers and dispatches per decision.

Ordering contract: buffers are applied in submission order (scan
order = queue order), so per-slot sequential semantics are exactly
those of the per-round path.  Any OTHER state access (clears,
restores, collapse dispatch, sweep, bulk load/save) must call
`flush_locked()` first — the engine does, under its lock — so state
mutations interleave in program order.

Queued work is applied lazily: every observation of engine state
(ticket fetch, sweep, save) forces a flush, so results are never
stale; `now_ms` rides inside each packed buffer, so delayed
application cannot shift timestamps.

Paged state (GUBER_PAGED, core/paging.py) rides this contract
unchanged: packed buffers carry DEVICE rows (the engine translates
logical slots before packing), and a page fault's spill/refill counts
as "other state access" — PagePlane.translate flushes the queue
before moving any page, so queued rounds never read a frame after its
page was swapped out from under them.
"""

from __future__ import annotations

import threading
import time as _time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.core.readback import read_wait

MAX_GROUP = 16


class _Group:
    """Shared host-side result of one flushed multi-step dispatch."""

    __slots__ = ("handle", "wait_stat", "host", "error", "lock")

    def __init__(self, handle, wait_stat) -> None:
        self.handle = handle  # device [R, 5, W] (or [5, W] singles)
        self.wait_stat = wait_stat  # the engine's device.readback
        self.host: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.lock = threading.Lock()

    def materialize(self) -> np.ndarray:
        if self.host is None and self.error is None:
            # The first thread reads; the others of a fused group wait
            # for it on the lock: every one of them observes its wait.
            with read_wait(self.wait_stat), self.lock:
                if self.host is None and self.error is None:
                    try:
                        # Prefetched at flush: usually a cache hit.
                        self.host = np.asarray(self.handle)
                        self.handle = None
                    except BaseException as e:  # noqa: BLE001
                        self.error = e
                        raise
        if self.error is not None:
            raise self.error
        return self.host


class PumpTicket:
    """One queued packed round.  `fetch()` → host [rows, W] output."""

    __slots__ = ("pump", "buf", "dev", "t_submit", "group", "index", "error")

    def __init__(self, pump: "StepPump", buf: np.ndarray) -> None:
        self.pump = pump
        self.buf: Optional[np.ndarray] = buf  # until dispatched
        # Double-buffered window (GUBER_WINDOW_DEPTH): the h2d upload
        # of this round, started AT SUBMIT so it overlaps the device
        # compute of the group currently executing.
        self.dev = None
        self.t_submit: float = 0.0
        self.group: Optional[_Group] = None
        self.index: Optional[int] = None
        self.error: Optional[BaseException] = None

    def fetch(self) -> np.ndarray:
        if self.group is None and self.error is None:
            self.pump.flush_for(self)
        if self.error is not None:
            raise self.error
        arr = self.group.materialize()
        return arr if self.index is None else arr[self.index]


class StepPump:
    """Per-engine queue of packed rounds awaiting a fused dispatch.

    Shared state rides the ENGINE's RLock (dispatch order = queue
    order is exactly the engine's serialization):
    """

    # guberlint: guard _queue, _noop, _noop_dev, _dev_stack_cache, submitted, flushes, fused_rounds, prestaged by engine._lock

    def __init__(self, engine, max_group: int = MAX_GROUP) -> None:
        self.engine = engine
        self.max_group = max_group
        self._queue: List[PumpTicket] = []
        self._noop: Dict[int, np.ndarray] = {}  # width → no-op buffer
        # The fused lax.scan dispatch exists to amortize per-dispatch
        # overhead that only accelerator backends have; on CPU, groups
        # dispatch as ordered singles — same semantics, and none of
        # the scan compiles that intermittently segfault XLA:CPU under
        # full-suite load (both scan programs are pinned by dedicated
        # equality tests).  GUBER_PUMP_SCAN=1 forces the scan path on
        # for targeted CPU testing of the grouped dispatch.
        import os

        self._scan_ok = (
            jax.default_backend() != "cpu"
            or os.environ.get("GUBER_PUMP_SCAN") == "1"
        )
        # Double-buffered host→device windows (PERF.md §24): while
        # batch N computes on device, batch N+1's packed buffer is
        # already transferring — submit() starts the h2d immediately
        # for up to GUBER_WINDOW_DEPTH × max_group outstanding rounds
        # (0 restores upload-at-flush).  The flush then stacks the
        # already-device-resident buffers with one tiny cached program
        # instead of paying a synchronous h2d on the critical path.
        from gubernator_tpu.config import env_window_depth

        self.window_depth = env_window_depth()
        self._dev_stack_cache: Dict[tuple, object] = {}
        self._noop_dev: Dict[tuple, object] = {}  # shape → device buf
        # Telemetry (PERF.md).
        self.submitted = 0
        self.flushes = 0
        self.fused_rounds = 0
        self.prestaged = 0
        from gubernator_tpu.utils.metrics import DurationStat

        # Queue wait: submit → flush dispatch (the device plane's
        # window-wait stage in the §10b/§24 budget).
        self.window_wait = DurationStat()

    # -- engine-lock-held API ------------------------------------------

    def submit(self, buf: np.ndarray) -> PumpTicket:  # guberlint: holds engine._lock
        """Queue one packed [PACKED_IN_ROWS, W] round.  Caller holds
        the engine lock (dispatch order = queue order).  Hot path for
        the columnar feeder's ring windows: every window that reaches
        the device enters here, so the per-call imports this method
        used to carry are hoisted to module level."""
        t = PumpTicket(self, buf)
        t.t_submit = _time.monotonic()
        if (
            self.window_depth > 0
            and len(self._queue) < self.window_depth * self.max_group
        ):
            # Start the h2d NOW: the transfer rides the device queue
            # behind the currently executing group, so upload(N+1)
            # overlaps compute(N) instead of serializing at flush.
            # guberlint: ok drift — the pump uploads on the engine's behalf: engine.py's device.h2d stage, the engine's stat
            with self.engine._stage("device.h2d"):
                t.dev = jax.device_put(buf)
            self.prestaged += 1
        self._queue.append(t)
        self.submitted += 1
        if len(self._queue) >= self.max_group:
            self.flush_locked()
        return t

    def flush_locked(self) -> None:  # guberlint: holds engine._lock
        """Dispatch everything queued, in order, grouping maximal runs
        of equal shape (width AND format: the 16-row general and 2-row
        uniform buffers run different programs).  Caller holds the
        engine lock."""
        q, self._queue = self._queue, []
        i = 0
        while i < len(q):
            j = i + 1
            shape = q[i].buf.shape
            while (
                j < len(q)
                and j - i < self.max_group
                and q[j].buf.shape == shape
            ):
                j += 1
            try:
                self._flush_group(q[i:j])
            except BaseException as e:  # noqa: BLE001
                # The donated state went into the failed dispatch —
                # every swapped-out ticket (this group AND the ones
                # behind it) must fail closed rather than strand
                # fetchers on group=None.
                for t in q[i:]:
                    if t.group is None and t.error is None:
                        t.error = e
                raise
            i = j

    # -- leader path (engine lock held) --------------------------------

    def _noop_buf(self, shape) -> np.ndarray:  # guberlint: holds engine._lock
        buf = self._noop.get(shape)
        if buf is None:
            from gubernator_tpu.ops.bucket_kernel import (
                UNIFORM_IN_ROWS,
                pack_batch_host,
                pack_uniform_host,
            )

            width = shape[1]
            if shape[0] == UNIFORM_IN_ROWS:
                buf = pack_uniform_host(
                    width, 0, self.engine.capacity,
                    np.empty(0, dtype=np.int32), 0, 0, 0, 1, 1, 0,
                )
            else:
                e64 = np.empty(0, dtype=np.int64)
                buf = pack_batch_host(
                    width, 0, self.engine.capacity,
                    np.empty(0, dtype=np.int32),
                    e64, e64, e64, e64, e64, e64, e64, e64,
                )
            self._noop[shape] = buf
        return buf

    def _noop_dev_buf(self, shape):  # guberlint: holds engine._lock
        buf = self._noop_dev.get(shape)
        if buf is None:
            buf = jax.device_put(self._noop_buf(shape))
            self._noop_dev[shape] = buf
        return buf

    def _dev_stack(self, count: int, shape):  # guberlint: holds engine._lock
        """Cached device-side stack program: R pre-staged [rows, W]
        buffers → one [R, rows, W] scan input without a flush-time h2d
        (the double-buffered-window counterpart of np.stack)."""
        key = (count, shape)
        prog = self._dev_stack_cache.get(key)
        if prog is None:
            # guberlint: shapes fan-in/shape pinned by the cache key; universe {widths} x {2,4,8,16}, precompiled in warmup
            def stack_rounds(*xs):
                return jnp.stack(xs)

            prog = jax.jit(stack_rounds)
            self._dev_stack_cache[key] = prog
        return prog

    def _flush_group(self, group: List[PumpTicket]) -> None:  # guberlint: holds engine._lock
        from gubernator_tpu.ops.bucket_kernel import (
            UNIFORM_IN_ROWS,
            multi_fused_step,
            multi_uniform_step,
        )

        eng = self.engine
        wait_stat = eng.readback.transfer_duration
        self.flushes += 1
        now_mono = _time.monotonic()
        for t in group:
            self.window_wait.observe(max(now_mono - t.t_submit, 0.0))
        shape = group[0].buf.shape
        is_uniform = shape[0] == UNIFORM_IN_ROWS
        if len(group) == 1 or not self._scan_ok:
            for t in group:
                src = t.dev if t.dev is not None else t.buf
                pout = (
                    eng._dispatch_uniform(src) if is_uniform
                    else eng._dispatch_packed(src)
                )
                pout.copy_to_host_async()
                t.index = None
                t.buf = None
                t.dev = None
                t.group = _Group(pout, wait_stat)
            return
        k = len(group)
        r = 2
        while r < k:
            r *= 2
        t0 = _time.monotonic()
        if all(t.dev is not None for t in group):
            # Every round is already on device (pre-staged at submit):
            # stack there — no h2d on the flush critical path at all.
            devs = [t.dev for t in group]
            devs += [self._noop_dev_buf(shape)] * (r - k)
            stack = self._dev_stack(r, shape)
            # guberlint: ok drift — the pump dispatches on the engine's behalf: engine.py's device.launch stage, the engine's stat
            with eng._stage("device.launch"):
                pins = stack(*devs)
                eng.dispatches_total += 1  # the stack program
        else:
            # Mixed staging (some rounds past the pre-stage depth):
            # one host stack + h2d; a ticket's host buf is always
            # retained until its flush, so no d2h round trip here.
            bufs = [t.buf for t in group]
            bufs += [self._noop_buf(shape)] * (r - k)
            pins = eng._h2d(np.stack(bufs))
        step = multi_uniform_step if is_uniform else multi_fused_step
        with eng._stage("device.launch"):
            eng._state, pouts = step(eng._state, pins)
            eng.dispatches_total += 1
            if eng.write_form(shape[-1]) == "rows":
                eng.row_write_dispatches_total += 1
        eng.round_duration.observe(_time.monotonic() - t0)
        pouts.copy_to_host_async()  # background transfer starts now
        self.fused_rounds += k
        g = _Group(pouts, wait_stat)
        for i, t in enumerate(group):
            # index BEFORE group: fetch()'s lock-free fast path keys on
            # `group is not None`, so group must be the LAST field set.
            t.index = i
            t.buf = None
            t.dev = None
            t.group = g

    # -- lock-free API -------------------------------------------------

    def flush_for(self, ticket: PumpTicket) -> None:
        """Called from fetch() without the engine lock."""
        with self.engine._lock:
            if ticket.group is None:
                self.flush_locked()

    # -- warmup --------------------------------------------------------

    def warmup(self, width: int) -> None:  # guberlint: holds engine._lock
        """Precompile the multi-step scan families {2,4,8,16} at one
        width — general AND uniform formats — plus the single uniform
        step (engine warmup calls this per ladder width).

        The SCAN families are skipped on the CPU backend: the pump is
        disabled there in production (no dispatch cost to amortize), and that
        rapid-fire ~8 scan-compile sequence per daemon spawn is where
        the full test suite intermittently segfaulted inside XLA:CPU's
        compiler — the same programs compile lazily without issue when
        tests force GUBER_PUMP=1.  The SINGLE uniform step still warms
        everywhere: with the pump forced on, the first forwarded
        request otherwise pays its compile inside the peer batch
        window ("timeout waiting for batched response")."""
        from gubernator_tpu.ops.bucket_kernel import (
            PACKED_IN_ROWS,
            UNIFORM_IN_ROWS,
            multi_fused_step,
            multi_uniform_step,
        )

        eng = self.engine
        pout = eng._dispatch_uniform(
            self._noop_buf((UNIFORM_IN_ROWS, width))
        )
        np.asarray(pout)
        if not self._scan_ok:
            # Same gate as _flush_group: never warm programs the
            # dispatch path will not run.
            return
        for rows, step in (
            (PACKED_IN_ROWS, multi_fused_step),
            (UNIFORM_IN_ROWS, multi_uniform_step),
        ):
            r = 2
            while r <= self.max_group:
                pins = jnp.asarray(
                    np.stack([self._noop_buf((rows, width))] * r)
                )
                eng._state, pouts = step(eng._state, pins)
                np.asarray(pouts)
                if self.window_depth > 0:
                    # Device-stack family for the pre-staged window
                    # path (same {2,4,8,16} universe as the scans).
                    dev = self._noop_dev_buf((rows, width))
                    np.asarray(self._dev_stack(r, (rows, width))(*([dev] * r)))
                r *= 2
