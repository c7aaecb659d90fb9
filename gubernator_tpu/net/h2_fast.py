"""Native h2 serving front: one method, zero per-RPC Python.

`H2FastFront` runs the C server (core/native/h2_server.cpp) on a
dedicated cleartext port serving exactly
/pb.gubernator.V1/GetRateLimits.  The C side owns accept/framing/
group-commit/response-encode; Python is entered ONCE per window with
the concatenated request bodies (protobuf repeated-field semantics
make the concatenation of N GetRateLimitsReq messages one valid
GetRateLimitsReq), runs the columnar engine path, and hands decision
columns back.

Scope, documented for operators: the front answers plain rate-limit
checks — requests that decode on the columnar path and whose
responses carry no error/metadata fields.  Batches containing
behaviors the columnar route declines (GLOBAL and friends) or any
per-item validation error are answered with grpc-status
UNIMPLEMENTED(12); point such traffic at the full gRPC listener
(`GUBER_GRPC_ADDRESS`).  The grpc-python wall this removes is
~160 µs/RPC of framework Python (PERF.md §13).

Enable with GUBER_H2_FAST_ADDRESS=127.0.0.1:<port> (0 = ephemeral);
GUBER_H2_FAST_WINDOW tunes the C-side group-commit window (default
2 ms, the §13 knee).

Native decision plane (GUBER_NATIVE_LEDGER, default on when the
decision ledger runs): the ledger's exact fast path — sticky
over-limit answers and credit-lease drains — delegated into a C table
(core/native/decision_plane.cpp) probed inside the connection threads,
so hot-key RPCs complete with zero GIL acquisitions and zero Python
frames; only cold/fall-through traffic enters the per-window Python
path.  The plane anchors to CLOCK_REALTIME, so it only attaches when
the engine runs on the live SYSTEM_CLOCK (frozen test clocks keep the
Python-only ledger).

Event front (GUBER_H2_EVENT_FRONT, default on; PERF.md §26): the C
side multiplexes ALL connections over a small pool of epoll reactor
threads (GUBER_H2_REACTORS, default ncpu−1 — one core stays reserved
for the Python serve plane) instead of one detached thread per
connection, with writev-batched egress and idle-connection reaping
(GUBER_H2_IDLE_TIMEOUT; GOAWAY + close).  GUBER_H2_EVENT_FRONT=0
restores the thread-per-connection plane, where GUBER_H2_LANES
(default: CPU count) shards the listener across SO_REUSEPORT accept
lanes.
"""

from __future__ import annotations

import ctypes
import inspect
import logging
import os
import threading
from typing import Optional

import numpy as np

from gubernator_tpu.core.native_build import ensure_built

log = logging.getLogger("gubernator_tpu.h2_fast")

_CALLBACK = ctypes.CFUNCTYPE(
    ctypes.c_int64,
    ctypes.c_void_p,  # concat bodies
    ctypes.c_int64,  # len
    ctypes.c_void_p,  # item_counts [n_rpcs]
    ctypes.c_void_p,  # body_lens [n_rpcs]
    ctypes.c_int64,  # n_rpcs
    ctypes.c_int64,  # total_items
    ctypes.c_void_p,  # out_cols [4 * total]
    ctypes.c_void_p,  # out_rpc_status [n_rpcs]
)

_lib = None


def load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    so = ensure_built("h2_server")
    if so is None:
        return None
    lib = ctypes.CDLL(str(so))
    lib.h2s_start.restype = ctypes.c_void_p
    lib.h2s_start.argtypes = [
        ctypes.c_int32, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
        _CALLBACK,
    ]
    lib.h2s_port.restype = ctypes.c_int32
    lib.h2s_port.argtypes = [ctypes.c_void_p]
    lib.h2s_lanes.restype = ctypes.c_int32
    lib.h2s_lanes.argtypes = [ctypes.c_void_p]
    lib.h2s_reactors.restype = ctypes.c_int32
    lib.h2s_reactors.argtypes = [ctypes.c_void_p]
    lib.h2s_stats.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.h2s_attach_plane.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.h2s_attach_ring.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.h2s_attach_feeder.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.h2s_stop.argtypes = [ctypes.c_void_p]
    # Event ring (core/native/event_ring.cpp, same .so).
    lib.evr_create.restype = ctypes.c_void_p
    lib.evr_create.argtypes = [ctypes.c_int64]
    lib.evr_free.argtypes = [ctypes.c_void_p]
    lib.evr_drain.restype = ctypes.c_int64
    lib.evr_drain.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.evr_stats.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.evr_record.restype = ctypes.c_int64
    lib.evr_record.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64,
    ]
    _lib = lib
    return _lib


def native_events_capacity() -> int:
    """GUBER_NATIVE_EVENTS / GUBER_NATIVE_EVENTS_CAP: 0 disables the
    event ring; otherwise the ring's record capacity (rounded up to a
    power of two by the C side; default 65536)."""
    if os.environ.get("GUBER_NATIVE_EVENTS", "1").strip().lower() in (
        "0", "false", "no", "off"
    ):
        return 0
    v = os.environ.get("GUBER_NATIVE_EVENTS_CAP", "").strip()
    try:
        return int(v) if v else 65536
    except ValueError:
        log.warning("GUBER_NATIVE_EVENTS_CAP=%r not an integer", v)
        return 65536


def default_lanes() -> int:
    """GUBER_H2_LANES, defaulting to the CPU count — the SO_REUSEPORT
    sharding only helps while there are cores to spread accept/framing/
    decide across.  0 (config.py's documented auto value) and
    malformed values mean auto, not one lane."""
    v = os.environ.get("GUBER_H2_LANES", "").strip()
    try:
        n = int(v) if v else 0
    except ValueError:
        log.warning("GUBER_H2_LANES=%r not an integer; using CPU count", v)
        n = 0
    if n > 0:
        return n
    return max(1, os.cpu_count() or 1)


def event_front_enabled() -> bool:
    """GUBER_H2_EVENT_FRONT (default on): epoll reactor connection
    multiplexing instead of thread-per-connection (PERF.md §26)."""
    return os.environ.get("GUBER_H2_EVENT_FRONT", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def default_reactors() -> int:
    """GUBER_H2_REACTORS: epoll reactor threads for the event front.
    0 (default) = auto, resolved by the C side to ncpu−1 (min 1) so
    one core stays reserved for the serve/dispatch plane — the §25
    starvation fix."""
    v = os.environ.get("GUBER_H2_REACTORS", "").strip()
    try:
        n = int(v) if v else 0
    except ValueError:
        log.warning("GUBER_H2_REACTORS=%r not an integer; using auto", v)
        n = 0
    return max(0, n)


def idle_timeout_ms() -> int:
    """GUBER_H2_IDLE_TIMEOUT (event front): reap connections silent
    this long (GOAWAY + close; Go-style duration or float seconds).
    Default 300s; 0 disables — the threaded front (and the pre-§26
    event front) held dead client connections forever."""
    raw = os.environ.get("GUBER_H2_IDLE_TIMEOUT", "").strip()
    if not raw:
        return 300_000
    try:
        from gubernator_tpu.config import parse_duration

        return max(0, int(parse_duration(raw) * 1000))
    except ValueError:
        log.warning(
            "GUBER_H2_IDLE_TIMEOUT=%r is not a duration; using 300s", raw
        )
        return 300_000


def native_ledger_enabled() -> bool:
    """GUBER_NATIVE_LEDGER (default on): delegate the ledger fast path
    to the C decision plane."""
    return os.environ.get("GUBER_NATIVE_LEDGER", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def native_feeder_enabled() -> bool:
    """GUBER_NATIVE_FEEDER (default on): pack fall-through RPCs into
    the columnar feeder ring inside the C connection threads instead
    of queueing wire bytes for the Python window path."""
    return os.environ.get("GUBER_NATIVE_FEEDER", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def retry_hints_enabled() -> bool:
    """GUBER_RETRY_HINTS (default on): retry_after_ms metadata on
    natively answered OVER_LIMIT items (reset_time-derived), so herds
    back off instead of hammering."""
    return os.environ.get("GUBER_RETRY_HINTS", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )


def _int_knob(env: str, default: int) -> int:
    v = os.environ.get(env, "").strip()
    try:
        return int(v) if v else default
    except ValueError:
        log.warning("%s=%r not an integer; using %d", env, v, default)
        return default


def _feeder_ring_params() -> dict:
    """GUBER_FEEDER_RING_SLOTS / _ROWS / _KEYBYTES — the ring's window
    count, per-window row capacity, and per-window key-byte capacity
    (clamped by the C side's cursor field widths)."""
    return {
        "n_slots": _int_knob("GUBER_FEEDER_RING_SLOTS", 4),
        "max_rows": _int_knob("GUBER_FEEDER_RING_ROWS", 8192),
        "key_cap": _int_knob("GUBER_FEEDER_RING_KEYBYTES", 1 << 20),
    }


class H2FastFront:
    """The native front bound to a V1Instance's columnar serve path."""

    def __init__(
        self,
        instance,
        *,
        port: int = 0,
        window_s: float = 0.002,
        max_batch: int = 16384,
        flush_items: int = 4096,  # early-flush: an engine-batch-worth
        lanes: Optional[int] = None,
        native_ledger: Optional[bool] = None,
        native_feeder: Optional[bool] = None,
        event_front: Optional[bool] = None,
        reactors: Optional[int] = None,
        idle_timeout_s: Optional[float] = None,
    ):
        lib = load()
        if lib is None:
            raise RuntimeError("native h2 server unavailable")
        self._lib = lib
        self.instance = instance
        self.window_s = window_s
        self.flush_items = flush_items
        # Serializes conn_stats() (the metrics collector's scrape
        # thread) against close(): the handle must not be freed while
        # an FFI stats call is in flight.
        self._teardown_mu = threading.Lock()
        if event_front is None:
            event_front = event_front_enabled()
        if reactors is None:
            reactors = default_reactors()
        idle_ms = (
            idle_timeout_ms()
            if idle_timeout_s is None
            else max(0, int(idle_timeout_s * 1000))
        )
        # The ctypes callback object must outlive the server.
        self._cb = _CALLBACK(self._window)
        self._handle = lib.h2s_start(
            port, int(window_s * 1e6), max_batch, flush_items,
            default_lanes() if lanes is None else max(1, int(lanes)),
            1 if event_front else 0, int(reactors), idle_ms,
            self._cb,
        )
        if not self._handle:
            raise RuntimeError("h2 fast front failed to bind")
        self.port = int(lib.h2s_port(self._handle))
        self.address = f"127.0.0.1:{self.port}"
        self.lanes = int(lib.h2s_lanes(self._handle))
        self.reactors = int(lib.h2s_reactors(self._handle))
        self.event_front = bool(event_front)
        self.plane = None
        self._attach_plane(native_ledger)
        # Columnar feeder plane (core/native/columnar_feeder.cpp):
        # fall-through RPCs pack into device-ready column windows in
        # the C connection threads; Python enters once per window with
        # zero-copy views and the C side scatters the responses.
        # GUBER_NATIVE_FEEDER=0 restores the byte window path exactly.
        self.feeder = None
        if native_feeder is None:
            native_feeder = native_feeder_enabled()
        if native_feeder and not self._engine_columnar_ok():
            # An engine that can never serve columnar (write-through
            # store, or no apply_columnar entry) would make every ring
            # window a futile decode+decline round trip — don't build
            # the ring at all; the byte path's cheap guard-first
            # decline handles such fronts.
            native_feeder = False
        if native_feeder:
            try:
                import gubernator_tpu.service as svc
                from gubernator_tpu.core.native_plane import (
                    NativeColumnarFeeder,
                )

                # An engine whose apply_columnar can hand back its
                # batch still on the device lets the serve thread keep
                # one window in flight (_feeder_window); one that cannot
                # serves each window in its single entry, as ever.
                self._engine_async = "want_async" in inspect.signature(
                    instance.engine.apply_columnar
                ).parameters
                self.feeder = NativeColumnarFeeder(
                    disqualify_mask=svc.COLUMNAR_DISQUALIFIERS,
                    window_s=window_s,
                    flush_rows=flush_items,
                    hints=retry_hints_enabled(),
                    window_handler=self._feeder_window,
                    window_complete=self._feeder_complete,
                    **_feeder_ring_params(),
                )
                lib.h2s_attach_feeder(self._handle, self.feeder.handle)
            except (RuntimeError, OSError) as e:
                log.warning("native columnar feeder unavailable: %s", e)
        # Event ring: the C threads publish per-stage latency events
        # (utils/native_events.py drains them).  Created unless
        # GUBER_NATIVE_EVENTS=0 — an unattached front pays nothing,
        # an attached one pays two clock reads + one lock-free write
        # per event.
        self._ring = None
        cap = native_events_capacity()
        if cap > 0:
            ring = lib.evr_create(cap)
            if ring:
                self._ring = ctypes.c_void_p(ring)
                lib.h2s_attach_ring(self._handle, self._ring)
                if self.feeder is not None:
                    # The feeder publishes feeder.pack/ring_wait/serve
                    # stages into the same ring.
                    self.feeder.attach_ring(self._ring)

    def _attach_plane(self, native_ledger: Optional[bool]) -> None:
        """Create and attach the native decision plane when the ledger
        runs on a live clock.  `native_ledger` False = off, True = on,
        None = GUBER_NATIVE_LEDGER (the direct-construction default);
        either way frozen/managed clocks refuse the plane — it
        compares entry deadlines against CLOCK_REALTIME, and a clock
        racing ahead of realtime would let stale leases answer (tests
        that manage the clock themselves attach via
        ledger.attach_native directly)."""
        ledger = getattr(self.instance, "ledger", None)
        if ledger is None:
            return
        if native_ledger is None:
            native_ledger = native_ledger_enabled()
        if not native_ledger:
            return
        from gubernator_tpu.clock import SYSTEM_CLOCK

        clock = self.instance.engine.clock
        if clock is not SYSTEM_CLOCK or clock.frozen:
            log.info(
                "native decision plane disabled: engine clock is "
                "not the live system clock"
            )
            return
        try:
            import gubernator_tpu.service as svc
            from gubernator_tpu.core.native_plane import NativeDecisionPlane

            self.plane = NativeDecisionPlane(
                max_keys=getattr(ledger, "max_keys", 65536),
                disqualify_mask=svc.COLUMNAR_DISQUALIFIERS,
            )
        except (RuntimeError, OSError) as e:
            log.warning("native decision plane unavailable: %s", e)
            return
        ledger.attach_native(self.plane)
        # reset_time-derived retry hints on OVER answers served by the
        # plane (the feeder's scatter applies the same knob).
        self.plane.set_hints(retry_hints_enabled())
        self._lib.h2s_attach_plane(self._handle, self.plane.handle)

    # -- the per-window entry ------------------------------------------

    def _window(
        self, buf, length, counts_ptr, lens_ptr, n_rpcs, total, out_ptr,
        status_ptr,
    ) -> int:
        try:
            n = int(total)
            nr = int(n_rpcs)
            if n == 0:
                # A zero-item window (e.g. one empty GetRateLimitsReq)
                # is a valid request and answers empty-OK, like the
                # reference's zero-request batches.  out_ptr (and
                # possibly buf) back empty C vectors whose data() may
                # be NULL — touching them through np.ctypeslib raises
                # and would fail the window INTERNAL(13) (ADVICE r5).
                if nr > 0 and status_ptr:
                    np.ctypeslib.as_array(
                        ctypes.cast(
                            status_ptr, ctypes.POINTER(ctypes.c_int64)
                        ),
                        shape=(nr,),
                    )[:] = 0
                return 0
            payload = ctypes.string_at(buf, length)
            cols = np.ctypeslib.as_array(
                ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_int64)),
                shape=(4 * n,),
            )
            rpc_status = np.ctypeslib.as_array(
                ctypes.cast(status_ptr, ctypes.POINTER(ctypes.c_int64)),
                shape=(nr,),
            )
            out = self._serve(payload, n)
            if out is not None:
                st, lim, rem, rst = out
                cols[0 * n : 0 * n + n] = np.asarray(st, dtype=np.int64)
                cols[1 * n : 1 * n + n] = np.asarray(lim, dtype=np.int64)
                cols[2 * n : 2 * n + n] = np.asarray(rem, dtype=np.int64)
                cols[3 * n : 3 * n + n] = np.asarray(rst, dtype=np.int64)
                rpc_status[:] = 0
                return 0
            # The combined window declined (one RPC out of scope must
            # not fail its window-mates): re-serve each RPC alone and
            # mark only the decliners UNIMPLEMENTED.
            counts = np.ctypeslib.as_array(
                ctypes.cast(counts_ptr, ctypes.POINTER(ctypes.c_int64)),
                shape=(nr,),
            )
            lens = np.ctypeslib.as_array(
                ctypes.cast(lens_ptr, ctypes.POINTER(ctypes.c_int64)),
                shape=(nr,),
            )
            b_off = 0
            i_off = 0
            for r in range(nr):
                body = payload[b_off : b_off + int(lens[r])]
                k = int(counts[r])
                one = self._serve(body, k)
                if one is None:
                    rpc_status[r] = 12  # UNIMPLEMENTED
                else:
                    st, lim, rem, rst = one
                    cols[0 * n + i_off : 0 * n + i_off + k] = np.asarray(
                        st, dtype=np.int64
                    )
                    cols[1 * n + i_off : 1 * n + i_off + k] = np.asarray(
                        lim, dtype=np.int64
                    )
                    cols[2 * n + i_off : 2 * n + i_off + k] = np.asarray(
                        rem, dtype=np.int64
                    )
                    cols[3 * n + i_off : 3 * n + i_off + k] = np.asarray(
                        rst, dtype=np.int64
                    )
                    rpc_status[r] = 0
                b_off += int(lens[r])
                i_off += k
            return 0
        except Exception:  # noqa: BLE001 — never unwind into C
            from gubernator_tpu.utils.metrics import record_swallowed

            record_swallowed("h2_fast.window")
            log.exception("h2 fast window failed")
            return 13  # INTERNAL

    def _engine_columnar_ok(self) -> bool:
        """The engine guards serve_decoded_local re-checks — hoisted
        here so both ingest paths can decline BEFORE paying a decode
        (a write-through store or a stub engine makes every window
        UNIMPLEMENTED; the decode would be pure waste)."""
        engine = self.instance.engine
        return (
            getattr(engine, "apply_columnar", None) is not None
            and getattr(engine, "store", None) is None
        )

    def _serve(self, payload: bytes, total: int):
        """Columnar decode + engine apply for one byte window; None if
        the batch needs the pb path (caller answers UNIMPLEMENTED).
        The post-decode serve is service.serve_decoded_local — shared
        with the feeder's ring windows so the ownership gate and
        ledger semantics cannot drift between the two ingest paths."""
        import gubernator_tpu.service as svc
        from gubernator_tpu.net import wire_codec

        if not self._engine_columnar_ok():
            return None  # guard-first: decline before decoding
        mask = svc.COLUMNAR_DISQUALIFIERS
        dec = wire_codec.decode_reqs(payload, max(total, 1), mask)
        if dec is None or dec.n != total:
            return None
        return self.instance.serve_decoded_local(dec)

    # -- the per-window feeder entries (columnar_feeder.cpp) ------------

    def _feeder_window(self, slot, n_rows, n_rpcs, key_bytes) -> int:
        """A sealed ring window's SUBMIT entry: build a DecodedBatch of
        ZERO-COPY views over the slot's C-resident columns (no decode,
        no allocation — the C conn threads already packed them) and run
        the shared columnar serve.  Where that hands back the batch
        still on the device — launched, its readback started — the
        window is IN_FLIGHT: the serve thread submits the next window,
        if one is worth a dispatch, while this one's step runs, then calls
        `_feeder_complete`.  Where it hands back finished columns (the
        ledger route, the per-RPC re-serve below) the verdict lanes are
        written here and the feeder thread encodes + scatters the
        responses in C at once.
        """
        from gubernator_tpu.core.native_plane import IN_FLIGHT
        from gubernator_tpu.net.wire_codec import DecodedBatch

        # Engine-domain "now" for the scatter's retry-hint encode:
        # reset_time verdicts are written in the ENGINE clock domain,
        # so the hint math must subtract the same domain's now (a raw
        # wall clock in C would skew every hint by the engine/host
        # offset — frozen test clocks included).
        slot.hint_now_ms[0] = self.instance.engine.clock.now_ms()
        dec = DecodedBatch(
            n=n_rows,
            key_buf=slot.key_buf[:key_bytes],
            key_offsets=slot.key_offsets[: n_rows + 1],
            algo=slot.algo[:n_rows],
            behavior=slot.behavior[:n_rows],
            hits=slot.hits[:n_rows],
            limit=slot.limit[:n_rows],
            duration=slot.duration[:n_rows],
            burst=slot.burst[:n_rows],
            fnv1=slot.fnv1[:n_rows],
            fnv1a=slot.fnv1a[:n_rows],
            name_len=slot.name_lens[:n_rows],
        )
        out = self.instance.serve_decoded_local(
            dec, want_async=self._engine_async
        )
        if hasattr(out, "get"):
            # The slot stays sealed until complete: the pending echoes
            # its `limit` view.
            slot.pending = out
            return IN_FLIGHT
        if out is not None:
            self._write_verdicts(slot, n_rows, n_rpcs, out)
            return 0
        # The combined window declined (ownership, engine guards): one
        # RPC out of scope must not fail its window-mates — re-serve
        # each RPC alone off the same views and mark only the
        # decliners UNIMPLEMENTED.  Rare path: per-RPC slicing may
        # allocate the rebased offsets.
        rows = slot.rpc_row
        counts = slot.rpc_items
        for r in range(n_rpcs):
            row0 = int(rows[r])
            k = int(counts[r])
            off0 = int(slot.key_offsets[row0])
            offk = int(slot.key_offsets[row0 + k])
            sub = DecodedBatch(
                n=k,
                key_buf=slot.key_buf[off0:offk],
                key_offsets=slot.key_offsets[row0 : row0 + k + 1] - off0,
                algo=slot.algo[row0 : row0 + k],
                behavior=slot.behavior[row0 : row0 + k],
                hits=slot.hits[row0 : row0 + k],
                limit=slot.limit[row0 : row0 + k],
                duration=slot.duration[row0 : row0 + k],
                burst=slot.burst[row0 : row0 + k],
                fnv1=slot.fnv1[row0 : row0 + k],
                fnv1a=slot.fnv1a[row0 : row0 + k],
                name_len=slot.name_lens[row0 : row0 + k],
            )
            one = self.instance.serve_decoded_local(sub)
            if one is None:
                slot.rpc_status[r] = 12  # UNIMPLEMENTED
            else:
                st, lim, rem, rst = one
                slot.out_status[row0 : row0 + k] = st
                slot.out_limit[row0 : row0 + k] = lim
                slot.out_remaining[row0 : row0 + k] = rem
                slot.out_reset[row0 : row0 + k] = rst
                slot.rpc_status[r] = 0
        return 0

    def _feeder_complete(self, slot, n_rows, n_rpcs, key_bytes) -> int:
        """An in-flight window's COMPLETE entry: read its own answers
        back and write the verdict lanes."""
        pending, slot.pending = slot.pending, None
        self._write_verdicts(slot, n_rows, n_rpcs, pending.get())
        return 0

    @staticmethod
    def _write_verdicts(slot, n_rows, n_rpcs, out) -> None:
        st, lim, rem, rst = out
        slot.out_status[:n_rows] = st
        slot.out_limit[:n_rows] = lim
        slot.out_remaining[:n_rows] = rem
        slot.out_reset[:n_rows] = rst
        slot.rpc_status[:n_rpcs] = 0

    # -- event ring (core/native/event_ring.cpp) ------------------------

    def drain_events(self, out) -> int:
        """Drain ring records into `out` (int64 numpy array, 4 slots
        per record: kind, t_end_ns, dur_ns, items); returns records
        read.  SINGLE consumer by contract — only the
        NativeEventCollector thread calls this."""
        if self._ring is None:
            return 0
        return int(
            self._lib.evr_drain(
                self._ring, out.ctypes.data_as(ctypes.c_void_p),
                len(out) // 4,
            )
        )

    def ring_stats(self) -> dict:
        if self._ring is None:
            return {"written": 0, "dropped": 0, "enabled": False}
        out = np.zeros(2, dtype=np.int64)
        self._lib.evr_stats(
            self._ring, out.ctypes.data_as(ctypes.c_void_p)
        )
        return {
            "written": int(out[0]),
            "dropped": int(out[1]),
            "enabled": True,
        }

    def abandon_ring(self) -> None:
        """Detach the ring and forget it WITHOUT freeing: the
        collector's drain thread outlived its join, and a freed ring
        under a live consumer is a native use-after-free — leak over
        UAF (same rule as h2s_stop's conn-thread bound)."""
        if self._ring is not None:
            if self._handle:
                self._lib.h2s_attach_ring(self._handle, None)
            self._ring = None

    # -- lifecycle ------------------------------------------------------

    def conn_stats(self) -> dict:
        """The connection-plane slice alone (cheap: one FFI call) —
        the gubernator_h2_conns gauge scrapes this per collect.
        Serialized against close() by _teardown_mu: a bare truthiness
        check would be check-then-use (the argument re-read could see
        None → NULL deref in C, or a captured handle could be freed
        mid-call)."""
        out = np.zeros(16, dtype=np.int64)
        with self._teardown_mu:
            handle = self._handle
            if handle:
                self._lib.h2s_stats(
                    handle, out.ctypes.data_as(ctypes.c_void_p)
                )
        return {
            "conns_open": int(out[7]),
            "conns_idle_reaped": int(out[8]),
            "reactors": int(out[9]),
            "event_front": bool(out[10]),
        }

    def stats(self) -> dict:
        with self._teardown_mu:
            return self._stats_locked()

    def _stats_locked(self) -> dict:  # guberlint: holds _teardown_mu
        out = np.zeros(16, dtype=np.int64)
        if self._handle:
            self._lib.h2s_stats(
                self._handle, out.ctypes.data_as(ctypes.c_void_p)
            )
        stats = {
            "rpcs": int(out[0]),
            "windows": int(out[1]),
            "errors": int(out[2]),
            "native_rpcs": int(out[3]),
            "native_items": int(out[4]),
            "feeder_front_rpcs": int(out[5]),
            "feeder_front_items": int(out[6]),
            "conns_open": int(out[7]),
            "conns_idle_reaped": int(out[8]),
            "reactors": int(out[9]),
            "event_front": bool(out[10]),
            "declined_rpcs": int(out[11]),
            "window_items": int(out[12]),
            "lanes": self.lanes,
        }
        # Mid-teardown (handle gone) the plane and the feeder may be
        # freed already: their counters are read only beside a live
        # handle.
        if self._handle and self.plane is not None:
            stats.update(self.plane.stats())
        if self._handle and self.feeder is not None:
            stats.update(self.feeder.stats())
        return stats

    def settings(self) -> dict:
        """What this front serves with — the start line's summary and
        the `settings` of /debug/vars `h2_front`."""
        return {
            "address": self.address,
            "window_ms": self.window_s * 1e3,
            "flush_items": self.flush_items,
            "event_front": self.event_front,
            "reactors": self.reactors,
            "lanes": self.lanes,
            "feeder": self.feeder is not None,
            "decision_plane": self.plane is not None,
            "retry_hints": retry_hints_enabled(),
            "event_ring": self._ring is not None,
        }

    def debug_vars(self) -> dict:
        """/debug/vars `h2_front`: the settings and the front's
        monotonic counters, summed over its paths so that a reader
        takes a window's difference — `rpcs` answered OK, `errors`
        answered with a grpc status (of them `declined_rpcs`
        UNIMPLEMENTED: out of the columnar path's scope), `windows` and
        `items` entered into Python (byte windows + feeder windows;
        the decision plane's RPCs enter none), `windows_overlapped`
        feeder windows submitted while the one before was still in
        flight on the device, `ring_dropped` events the ring was too
        full to take."""
        # One hold of _teardown_mu over every FFI read: close() frees
        # the feeder and the ring only after it has taken the handle
        # away under the same lock.
        with self._teardown_mu:
            if not self._handle:
                return {"settings": self.settings(), "closed": True}
            st = self._stats_locked()
            dropped = self.ring_stats()["dropped"]
        return {
            "settings": self.settings(),
            "rpcs": st["rpcs"],
            "errors": st["errors"],
            "declined_rpcs": st["declined_rpcs"],
            "windows": st["windows"] + st.get("feeder_windows", 0),
            "items": st["window_items"] + st.get("feeder_served_rows", 0),
            "feeder_rpcs": st["feeder_front_rpcs"],
            "windows_overlapped": st.get("feeder_windows_overlapped", 0),
            "feeder_ring_full": st.get("feeder_ring_full", 0),
            "feeder_declined": st.get("feeder_declined", 0),
            "plane_rpcs": st["native_rpcs"],
            "ring_dropped": dropped,
        }

    def close(self) -> None:
        if self._handle:
            # Null the public handle under _teardown_mu: the metrics
            # collector's conn_stats() can race this teardown from the
            # gateway thread, and h2s_stats on a freed server is a
            # native use-after-free.  After this block any scrape sees
            # None and reports zeros; an in-flight one finished before
            # the handle is stopped/freed below.
            with self._teardown_mu:
                handle, self._handle = self._handle, None
            if self.plane is not None:
                # Detach before stop: conn threads re-read the plane
                # pointer per RPC, so no new native serves start; stop
                # then joins/drains them before the ledger pulls its
                # credit back and the table is freed.
                self._lib.h2s_attach_plane(handle, None)
            if self.feeder is not None:
                # Feeder teardown is drain-then-close: detach (conn
                # threads stop packing at the next RPC), stop (the
                # serve thread drains every claimed window — pending
                # RPCs answer UNAVAILABLE through still-live conns —
                # then joins), and free only after h2s_stop below has
                # also joined the conn threads.
                self._lib.h2s_attach_feeder(handle, None)
                self.feeder.stop()
            if self._ring is not None:
                # Same contract as the plane: detach first, free only
                # after h2s_stop joined/drained the writer threads.
                self._lib.h2s_attach_ring(handle, None)
            self._lib.h2s_stop(handle)
            if self.plane is not None:
                ledger = getattr(self.instance, "ledger", None)
                if ledger is not None:
                    ledger.detach_native()
                self.plane.close()
                self.plane = None
            if self.feeder is not None:
                self.feeder.close()
                self.feeder = None
            if self._ring is not None:
                self._lib.evr_free(self._ring)
                self._ring = None
