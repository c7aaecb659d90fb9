"""Native event collector: drain the C front's event ring into
histograms, metrics, and span stubs.

The C h2 front (core/native/h2_server.cpp) publishes per-stage latency
events into a lock-free ring (core/native/event_ring.cpp) from its
connection/dispatch threads — zero mutex, zero Py* calls on the serve
side.  This module's ONE background thread drains the ring every
``GUBER_NATIVE_EVENTS_INTERVAL`` seconds and turns the records into:

- per-stage DurationStat histograms (count/sum/max + streaming
  p50/p99), exported as ``gubernator_native_stage_duration`` and the
  ``native_*`` rows of ``gubernator_stage_quantile_seconds``;
- event counts per stage (``gubernator_native_events{stage}``) and the
  ring's overflow drops (``gubernator_native_ring_dropped``);
- when in-memory tracing is active, bounded NATIVE SPAN STUBS
  (``native.decide``) reconstructed from the records' monotonic
  timestamps — the first spans ever emitted for decisions that never
  touch Python.  The fast front skips header decoding entirely (the
  port is the route), so there is no traceparent to join: stubs are
  roots grouped per drain, attributed by stage/items, and the flight
  recorder's window-path traces carry the cross-process stitching
  (OBSERVABILITY.md documents the split).

Stage ids mirror h2_server.cpp's kEv* constants.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict

from gubernator_tpu.utils.metrics import DurationStat, record_swallowed

log = logging.getLogger("gubernator_tpu.native_events")

# kind -> stage name (h2_server.cpp kEvNativeServe/kEvWindowWait/
# kEvWindowServe/kEvRpcTotal; columnar_feeder.cpp kEvFeederPack/
# kEvFeederRingWait/kEvFeederServe/kEvFeederScatter/kEvFeederInflight/
# kEvFeederWindowWait).
STAGES = {
    1: "native_serve",
    2: "window_wait",
    3: "window_serve",
    # Columnar feeder plane: per-RPC wire→columns pack (conn thread),
    # pack → window-callback queue wait (the feeder's analog of
    # window_wait — the stage the §23 p99 tail lived in), and the
    # per-window columnar serve wall: one event a window, the sum of
    # its submit and complete entries' walls.
    4: "feeder_pack",
    5: "feeder_ring_wait",
    6: "feeder_serve",
    # Event front (PERF.md §26): one epoll wake's processing wall
    # (items = ready events), one connection's budgeted read drain
    # (items = bytes), and one EPOLLOUT writev resumption (items =
    # bytes moved) — the egress backpressure path, not the common
    # inline flush.
    7: "reactor_wake",
    8: "reactor_read",
    9: "reactor_write",
    # Per RPC, whichever path answered it: body deframed → response
    # handed to the connection's write path (items = the RPC's items).
    # On the feeder path it is tiled by feeder_ring_wait (which starts
    # at the same instant and so contains feeder_pack), the window's
    # feeder_serve, its feeder_inflight where it had one, and the
    # RPC's place in feeder_scatter.
    10: "rpc_total",
    # Per feeder window: the C response encode + scatter of all its
    # RPCs, after the columnar callback returned (items = its RPCs).
    11: "feeder_scatter",
    # Per feeder window whose submit left it in flight: its submit's
    # return → its complete's entry — the next window's submit where
    # rows were waiting, two clock readings where none were (items =
    # its rows).  None for a window answered with finished columns.
    12: "feeder_inflight",
    # Per submitted feeder window: the serve thread's time blocked in
    # its two waits (for a window to hold rows, then the group-commit
    # window) since its last submit — 0 for a window taken while
    # another was in flight (items = its RPCs).  A wait: histogram only.
    # With feeder_serve and feeder_scatter it tiles the thread's cycle.
    13: "feeder_window_wait",
}

# Span stubs recorded per drain tick, bounded: under a 9k/s native
# herd an unbounded stub stream would evict every interesting span
# from the tracer's deque.
_MAX_STUBS_PER_DRAIN = 32


class NativeEventCollector:
    """One daemon's ring-drain thread + the derived stats."""

    def __init__(
        self,
        front,
        *,
        interval: float = 0.05,
        max_drain: int = 8192,
    ) -> None:
        import numpy as np

        self._front = front
        self.interval = interval
        self._max_drain = max_drain
        self._out = np.zeros(4 * max_drain, dtype=np.int64)
        self._hists: Dict[str, DurationStat] = {
            name: DurationStat() for name in STAGES.values()
        }
        self._counts: Dict[str, int] = {name: 0 for name in STAGES.values()}
        self._lock = threading.Lock()  # guberlint: guards _counts
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="guber-native-events", daemon=True
        )
        self._thread.start()

    @classmethod
    def from_env(cls, front) -> "NativeEventCollector":
        import os

        raw = os.environ.get("GUBER_NATIVE_EVENTS_INTERVAL", "").strip()
        interval = 0.05
        if raw:
            try:
                # Go-style duration strings ("50ms") or float seconds —
                # the same surface every other GUBER_* duration speaks.
                from gubernator_tpu.config import parse_duration

                interval = parse_duration(raw)
            except ValueError:
                log.warning(
                    "GUBER_NATIVE_EVENTS_INTERVAL=%r is not a duration;"
                    " using 0.05s", raw,
                )
        return cls(front, interval=max(0.005, interval))

    # -- the drain loop ------------------------------------------------

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.drain_once()
            except Exception:  # noqa: BLE001 — the tap must not die
                record_swallowed("native_events.drain")
                log.exception("native event drain failed")
        # Final drain so short-lived runs (benches, tests) keep the
        # tail events published just before close.
        try:
            self.drain_once()
        except Exception:  # noqa: BLE001 — teardown best-effort
            record_swallowed("native_events.drain")

    def drain_once(self) -> int:
        """Drain the ring until it is empty (a tick's worth can exceed
        one buffer: a single-item herd publishes several events an
        RPC); returns the records read."""
        total = 0
        while True:
            n = self._drain_buffer()
            total += max(n, 0)
            if n < self._max_drain:
                return total

    def _drain_buffer(self) -> int:
        """One buffer's worth: bin durations into the per-stage
        histograms (vectorized) beside their exact sum and maximum,
        count events, emit bounded span stubs."""
        import numpy as np

        n = self._front.drain_events(self._out)
        if n <= 0:
            return 0
        rec = self._out[: 4 * n].reshape(n, 4)
        kinds = rec[:, 0]
        dur_s = rec[:, 2].astype(np.float64) / 1e9
        # Vectorized log2 binning, matching DurationStat.bucket_of.
        idx = np.floor(
            np.log2(np.maximum(dur_s, DurationStat._BASE) / DurationStat._BASE)
        ).astype(np.int64)
        np.clip(idx, 0, DurationStat.N_BUCKETS - 1, out=idx)
        for kind, stage in STAGES.items():
            mask = kinds == kind
            m = int(mask.sum())
            if not m:
                continue
            counts = np.bincount(
                idx[mask], minlength=DurationStat.N_BUCKETS
            )
            of_stage = dur_s[mask]
            self._hists[stage].observe_bucket_counts(
                counts.tolist(), total=float(of_stage.sum()),
                top=float(of_stage.max()),
            )
            with self._lock:
                self._counts[stage] += m
        self._emit_stubs(rec)
        return n

    def _emit_stubs(self, rec) -> None:
        from gubernator_tpu.utils import tracing

        tracer = tracing.current_tracer()
        if tracer is None or not hasattr(tracer, "record_span"):
            return
        native = rec[rec[:, 0] == 1][:_MAX_STUBS_PER_DRAIN]
        for kind, t_end, dur, items in native.tolist():
            tracer.record_span(
                "native.decide",
                start_ns=int(t_end - dur),
                end_ns=int(t_end),
                items=int(items),
                stage=STAGES[int(kind)],
            )

    # -- read side (metrics / debug vars / bench artifacts) ------------

    def histograms(self) -> Dict[str, DurationStat]:
        return self._hists

    def event_counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def ring_stats(self) -> dict:
        return self._front.ring_stats()

    def stats(self) -> dict:
        """Bench-artifact / /debug/vars shape: counts, drops, and
        per-stage latency summaries."""
        out = {"events": self.event_counts(), "ring": self.ring_stats()}
        out["stages"] = {
            stage: h.snapshot_ms(digits=4)
            for stage, h in self._hists.items()
        }
        return out

    def close(self) -> bool:
        """Stop the drain thread; returns False if it outlived the
        join — the caller must then LEAK the ring instead of freeing
        it (H2FastFront.abandon_ring), or the straggler's next
        evr_drain is a native use-after-free."""
        self._stop.set()
        self._thread.join(timeout=5.0)
        return not self._thread.is_alive()
