"""ctypes wrapper for the native decision plane (decision_plane.cpp).

The plane is the C-resident twin of the ledger's exact fast path:
sticky over-limit records and delegated credit leases, probed inside
the h2 server's connection threads with zero GIL acquisitions.  This
wrapper is the *bridge* side: core/ledger.py pushes grants down
(`install_over` / `install_lease`), pulls drained counts back
(`pull`), and peeks for read-only overlays — all of it under the
ledger's own lock, so the lock order is always ledger lock → plane
mutex and a lease lives in exactly one tier at a time.

The .so is the combined h2_server build (native_build._EXTRA_SOURCES):
the server calls dp_try_serve in-image; Python talks to the same table
through these entry points.

Like the Python ledger, the plane is paged-state-agnostic
(GUBER_PAGED, core/paging.py): its table is keyed by hash key and its
traffic reaches the engine as keyed batch rows, so device page
residency never appears in this interface.
"""

from __future__ import annotations

import ctypes
import time
from typing import Optional, Tuple

import numpy as np

from gubernator_tpu.core.native_build import ensure_built
from gubernator_tpu.types import Algorithm, Behavior, Status

_lib = None

# Columnar window callback (columnar_feeder.cpp ColumnarCallback):
# (slot, n_rows, n_rpcs, key_bytes) -> 0 | grpc status for the window
# | IN_FLIGHT from the submit entry (the complete entry then owes the
# verdicts).
_FEEDER_CALLBACK = ctypes.CFUNCTYPE(
    ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ctypes.c_int64,
)

# What a window's submit entry returns when it launched the window's
# device work and left the answers there (columnar_feeder.cpp
# kInFlight): the serve thread calls the complete entry for the same
# slot later, after it has submitted the next window.
IN_FLIGHT = -1

# Same breaker set as core/ledger._BREAKERS — the two tiers must agree
# on what falls through, or a native answer could cover a row the
# Python ledger would have revoked on.  Pinned numerically equal by
# guberlint's contract pass (CONTRACT_CONSTANTS), so editing one side
# alone fails CI.
_BREAKERS = int(Behavior.DURATION_IS_GREGORIAN) | int(Behavior.RESET_REMAINING)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the combined h2_server/decision-plane
    .so and register the dp_* signatures."""
    global _lib
    if _lib is not None:
        return _lib
    so = ensure_built("h2_server")
    if so is None:
        return None
    lib = ctypes.CDLL(str(so))
    i64, i32, vp = ctypes.c_int64, ctypes.c_int32, ctypes.c_void_p
    lib.dp_create.restype = vp
    lib.dp_create.argtypes = [i64, i64, i64, i64, i32, i32]
    lib.dp_free.argtypes = [vp]
    lib.dp_set_clock_offset.argtypes = [vp, i64]
    lib.dp_install_over.restype = i64
    lib.dp_install_over.argtypes = [vp, ctypes.c_char_p, i64, i64, i64, i64]
    lib.dp_install_lease.restype = i64
    lib.dp_install_lease.argtypes = [
        vp, ctypes.c_char_p, i64, i64, i64, i64, i64, i64, i64, i64,
    ]
    lib.dp_pull.restype = i64
    lib.dp_pull.argtypes = [vp, ctypes.c_char_p, i64, vp]
    lib.dp_peek.restype = i64
    lib.dp_peek.argtypes = [vp, ctypes.c_char_p, i64, vp]
    lib.dp_clear.argtypes = [vp]
    lib.dp_probe.restype = i64
    lib.dp_probe.argtypes = [
        vp, ctypes.c_char_p, i64, i32, i32, i64, i64, i64, i64, vp,
    ]
    lib.dp_try_serve.restype = i64
    lib.dp_try_serve.argtypes = [vp, ctypes.c_char_p, i64, i64, i64, vp, i64]
    lib.dp_stats.argtypes = [vp, vp]
    lib.dp_set_hints.argtypes = [vp, i64]
    # Columnar feeder plane (columnar_feeder.cpp, same .so).
    lib.cf_create.restype = vp
    lib.cf_create.argtypes = [i64, i64, i64, i64, i64, i64, i64, i32,
                              _FEEDER_CALLBACK, _FEEDER_CALLBACK]
    lib.cf_attach_ring.argtypes = [vp, vp]
    lib.cf_set_hints.argtypes = [vp, i64]
    lib.cf_slot_ptrs.argtypes = [vp, i64, vp]
    lib.cf_pack.restype = i64
    lib.cf_pack.argtypes = [vp, ctypes.c_char_p, i64, i64, vp, i64, i64]
    lib.cf_flush.argtypes = [vp]
    lib.cf_stats.argtypes = [vp, vp]
    lib.cf_stop.argtypes = [vp]
    lib.cf_free.argtypes = [vp]
    lib.cf_bench_pack.restype = i64
    lib.cf_bench_pack.argtypes = [vp, ctypes.c_char_p, i64, i64, i64, i64]
    _lib = lib
    return _lib


class NativeDecisionPlane:
    """One native table, owned by the attaching front / ledger pair."""

    def __init__(self, *, max_keys: int = 65536, disqualify_mask: int = 0):
        lib = load()
        if lib is None:
            raise RuntimeError("native decision plane unavailable")
        self._lib = lib
        self._handle = lib.dp_create(
            max_keys,
            int(Algorithm.TOKEN_BUCKET),
            _BREAKERS,
            disqualify_mask,
            int(Status.OVER_LIMIT),
            int(Status.UNDER_LIMIT),
        )
        if not self._handle:
            raise RuntimeError("dp_create failed")

    # -- grant / pull bridge (called under the ledger lock) ------------

    def set_clock_offset(self, ledger_now_ms: int) -> None:
        """Anchor the plane's realtime clock to the ledger's domain."""
        self._lib.dp_set_clock_offset(
            self._handle, int(ledger_now_ms) - int(time.time() * 1000)
        )

    def install_over(
        self, key: bytes, limit: int, duration: int, reset: int
    ) -> bool:
        return bool(
            self._lib.dp_install_over(
                self._handle, key, len(key), limit, duration, reset
            )
        )

    def install_lease(
        self,
        key: bytes,
        limit: int,
        duration: int,
        reset: int,
        rem: int,
        credit: int,
        consumed: int,
        expiry: int,
    ) -> bool:
        return bool(
            self._lib.dp_install_lease(
                self._handle, key, len(key), limit, duration, reset,
                rem, credit, consumed, expiry,
            )
        )

    def pull(self, key: bytes) -> Optional[Tuple[int, int, int, int, int]]:
        """Remove the record; returns (kind, consumed, credit, rem,
        reset) or None when absent.  Linearizes every native answer for
        the key before the caller's next step."""
        out = np.zeros(4, dtype=np.int64)
        kind = self._lib.dp_pull(
            self._handle, key, len(key),
            out.ctypes.data_as(ctypes.c_void_p),
        )
        if kind == 0:
            return None
        return (int(kind), int(out[0]), int(out[1]), int(out[2]),
                int(out[3]))

    def peek(self, key: bytes) -> Optional[Tuple[int, int, int, int, int]]:
        out = np.zeros(4, dtype=np.int64)
        kind = self._lib.dp_peek(
            self._handle, key, len(key),
            out.ctypes.data_as(ctypes.c_void_p),
        )
        if kind == 0:
            return None
        return (int(kind), int(out[0]), int(out[1]), int(out[2]),
                int(out[3]))

    def clear(self) -> None:
        self._lib.dp_clear(self._handle)

    def set_hints(self, on: bool) -> None:
        """retry_after_ms metadata on natively answered OVER items
        (GUBER_RETRY_HINTS; reset_time-derived herd-backoff hint)."""
        self._lib.dp_set_hints(self._handle, 1 if on else 0)

    # -- serve entries (tests drive these; the h2 server calls the C
    # -- twin in-image) ------------------------------------------------

    def probe(
        self,
        key: bytes,
        algo: int,
        behavior: int,
        hits: int,
        limit: int,
        duration: int,
        now_ms: int,
    ) -> Optional[Tuple[int, int, int]]:
        """One item against the table at an explicit clock; commits the
        drain.  Returns (status, remaining, reset) or None."""
        out = np.zeros(3, dtype=np.int64)
        ok = self._lib.dp_probe(
            self._handle, key, len(key), algo, behavior, hits, limit,
            duration, now_ms, out.ctypes.data_as(ctypes.c_void_p),
        )
        if not ok:
            return None
        return int(out[0]), int(out[1]), int(out[2])

    def try_serve(
        self, body: bytes, max_items: int = 1000, now_ms: int = -1
    ) -> Optional[bytes]:
        """Whole-RPC serve of a GetRateLimitsReq payload: the exact
        code path the h2 connection threads run.  Returns the
        GetRateLimitsResp bytes, or None on decline."""
        # Sized for the retry-hint encode, like the C caller.
        cap = 96 * max(1, max_items) + 16
        out = ctypes.create_string_buffer(cap)
        n = self._lib.dp_try_serve(
            self._handle, body, len(body), max_items, now_ms, out, cap
        )
        if n < 0:
            return None
        return out.raw[:n]

    # ------------------------------------------------------------------

    def stats(self) -> dict:
        out = np.zeros(8, dtype=np.int64)
        self._lib.dp_stats(
            self._handle, out.ctypes.data_as(ctypes.c_void_p)
        )
        return {
            "native_answered": int(out[0]),
            "native_rpcs": int(out[1]),
            "native_declined": int(out[2]),
            "native_entries": int(out[3]),
            "native_installs": int(out[4]),
            "native_pulls": int(out[5]),
        }

    @property
    def handle(self) -> int:
        """Raw dp handle for h2s_attach_plane."""
        return self._handle

    def close(self) -> None:
        if self._handle:
            self._lib.dp_free(self._handle)
            self._handle = None


class FeederSlot:
    """Zero-copy numpy views over one ring window's C-resident column
    arrays — mapped ONCE at feeder creation, so the per-window Python
    cost is array slicing, not allocation or copying."""

    __slots__ = (
        "key_buf", "key_offsets", "algo", "behavior", "hits", "limit",
        "duration", "burst", "fnv1", "fnv1a", "name_lens", "out_status",
        "out_limit", "out_remaining", "out_reset", "rpc_row",
        "rpc_items", "rpc_status", "hint_now_ms", "pending",
    )

    _DTYPES = (
        ("key_buf", np.uint8), ("key_offsets", np.int64),
        ("algo", np.int32), ("behavior", np.int32),
        ("hits", np.int64), ("limit", np.int64),
        ("duration", np.int64), ("burst", np.int64),
        ("fnv1", np.uint64), ("fnv1a", np.uint64),
        ("name_lens", np.int32), ("out_status", np.int32),
        ("out_limit", np.int64), ("out_remaining", np.int64),
        ("out_reset", np.int64), ("rpc_row", np.int64),
        ("rpc_items", np.int64), ("rpc_status", np.int64),
        ("hint_now_ms", np.int64),
    )

    def __init__(self, lib, handle, slot, max_rows, key_cap, max_rpcs):
        ptrs = (ctypes.c_void_p * 19)()
        lib.cf_slot_ptrs(handle, slot, ptrs)
        sizes = {
            "key_buf": key_cap, "key_offsets": max_rows + 1,
            "rpc_row": max_rpcs, "rpc_items": max_rpcs,
            "rpc_status": max_rpcs, "hint_now_ms": 1,
        }
        for i, (name, dtype) in enumerate(self._DTYPES):
            size = sizes.get(name, max_rows)
            arr = np.ctypeslib.as_array(
                ctypes.cast(
                    ptrs[i],
                    ctypes.POINTER(np.ctypeslib.as_ctypes_type(dtype)),
                ),
                shape=(size,),
            )
            object.__setattr__(self, name, arr)
        # What the owner's submit entry leaves for its complete entry
        # (the window's in-flight batch); None between windows.
        self.pending = None


class NativeColumnarFeeder:
    """The columnar feeder ring's bridge side (columnar_feeder.cpp).

    Owns the ring handle, the per-slot zero-copy views, and the ctypes
    window-callback trampoline.  The owner (net/h2_fast.H2FastFront)
    provides `window_handler(slot: FeederSlot, n_rows, n_rpcs,
    key_bytes) -> int` — it serves the window through the engine
    columnar path, writes the verdict lanes + per-RPC status in place,
    and returns 0 (or a grpc status failing the whole window).
    With `window_complete` (same signature) the handler is the
    window's SUBMIT entry and may return IN_FLIGHT instead: the serve
    thread then submits the next window, if one is worth a dispatch
    (sealed, or holding `flush_rows` / 8 rows), before it calls
    `window_complete` for this slot — which owes the
    verdict lanes and the return value.  The slot stays sealed between
    the two.  `window_handler=None` creates a SINK feeder (bench/tests:
    windows seal and recycle in C, no Python per window)."""

    def __init__(
        self,
        *,
        n_slots: int = 4,
        max_rows: int = 8192,
        key_cap: int = 1 << 20,
        max_rpcs: int = 4096,
        disqualify_mask: int = 0,
        window_s: float = 0.002,
        flush_rows: int = 4096,
        hints: bool = True,
        window_handler=None,
        window_complete=None,
    ):
        lib = load()
        if lib is None:
            raise RuntimeError("native columnar feeder unavailable")
        self._lib = lib
        # The ctypes callback objects must outlive the ring.
        self._cb = self._callback(window_handler, "feeder.window")
        self._complete_cb = self._callback(window_complete, "feeder.complete")
        self._handle = lib.cf_create(
            n_slots, max_rows, key_cap, max_rpcs, disqualify_mask,
            int(window_s * 1e6), flush_rows, int(Status.OVER_LIMIT),
            self._cb, self._complete_cb,
        )
        if not self._handle:
            raise RuntimeError("cf_create failed")
        st = self.stats()
        # The C side clamps every shape to its cursor field widths —
        # the views below must map the CLAMPED capacities, never the
        # raw constructor arguments.
        self.n_slots = st["feeder_slots"]
        self.max_rows = st["feeder_max_rows"]
        self.key_cap = st["feeder_key_cap"]
        self.max_rpcs = st["feeder_max_rpcs"]
        self.slots = [
            FeederSlot(lib, self._handle, i, self.max_rows,
                       self.key_cap, self.max_rpcs)
            for i in range(self.n_slots)
        ]
        lib.cf_set_hints(self._handle, 1 if hints else 0)

    # -- the per-window trampolines (feeder serve thread → Python) -----

    def _callback(self, handler, site: str):
        """One window entry as C calls it: the owner's handler over the
        slot's views; a handler that raises fails that window's RPCs
        INTERNAL and nothing else.  No handler: a null pointer."""
        if handler is None:
            return ctypes.cast(None, _FEEDER_CALLBACK)

        def entry(slot, n_rows, n_rpcs, key_bytes) -> int:
            try:
                return int(
                    handler(
                        self.slots[int(slot)], int(n_rows), int(n_rpcs),
                        int(key_bytes),
                    )
                )
            except Exception:  # noqa: BLE001 — never unwind into C
                from gubernator_tpu.utils.metrics import record_swallowed

                record_swallowed(site)
                return 13  # INTERNAL

        return _FEEDER_CALLBACK(entry)

    # -- test/bench entries --------------------------------------------

    def pack(
        self, body: bytes, max_items: int = 1000, stream: int = 0,
    ) -> int:
        """Pack one request body with no connection attached (parity
        tests / benches); returns rows packed or a negative decline."""
        return int(
            self._lib.cf_pack(
                self._handle, body, len(body), max_items, None, stream, 0
            )
        )

    def flush(self) -> None:
        """Seal + serve every claimed window (bounded wait)."""
        self._lib.cf_flush(self._handle)

    def bench_pack(
        self, body: bytes, max_items: int, reps: int, threads: int
    ) -> int:
        """C-threaded pack microbench; returns rows packed."""
        return int(
            self._lib.cf_bench_pack(
                self._handle, body, len(body), max_items, reps, threads
            )
        )

    # ------------------------------------------------------------------

    def attach_ring(self, ring) -> None:
        self._lib.cf_attach_ring(self._handle, ring)

    def stats(self) -> dict:
        out = np.zeros(16, dtype=np.int64)
        self._lib.cf_stats(
            self._handle, out.ctypes.data_as(ctypes.c_void_p)
        )
        return {
            "feeder_rpcs": int(out[0]),
            "feeder_rows": int(out[1]),
            "feeder_windows": int(out[2]),
            "feeder_served_rows": int(out[3]),
            "feeder_ring_full": int(out[4]),
            "feeder_declined": int(out[5]),
            "feeder_window_errors": int(out[6]),
            "feeder_open_slot": int(out[7]),
            "feeder_open_rows": int(out[8]),
            "feeder_slots": int(out[9]),
            "feeder_max_rows": int(out[10]),
            "feeder_key_cap": int(out[11]),
            "feeder_max_rpcs": int(out[12]),
            "feeder_windows_overlapped": int(out[13]),
        }

    @property
    def handle(self) -> int:
        """Raw cf handle for h2s_attach_feeder."""
        return self._handle

    def stop(self) -> None:
        """Drain-then-stop the serve thread.  The owner must detach
        from the h2 server FIRST (h2s_attach_feeder(None)) and free
        AFTER (close)."""
        if self._handle:
            self._lib.cf_stop(self._handle)

    def close(self) -> None:
        """Stop (idempotent — cf_stop joins once) then free.  The slot
        views die with the ring: the owner must not touch them after
        close."""
        if self._handle:
            self._lib.cf_stop(self._handle)
            self._lib.cf_free(self._handle)
            self._handle = None
            self.slots = []
