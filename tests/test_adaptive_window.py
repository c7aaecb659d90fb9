"""Adaptive batching windows + the GLOBAL stage-budget instrumentation
(round 6, VERDICT r5 weak #2 / next-round #3).

The *_wait knobs are CAPS: an idle batcher fires immediately instead of
waiting out its window, and the wait grows toward the cap only while
batches actually fill.  The five-stage pipeline budget (client window,
engine serve, hit window, owner RPC, broadcast age) is measured where
it happens and exported as gubernator_stage_duration{stage=...}.
"""

import time

import numpy as np
import pytest

from gubernator_tpu.cluster.batch_loop import AdaptiveWait, IntervalBatcher
from gubernator_tpu.net.wire_window import WireWindow
from gubernator_tpu.utils.metrics import ENGINE_STAGES


def _combine(existing, item):
    return (existing or 0) + item


# ---------------------------------------------------------------------
# AdaptiveWait semantics


def test_adaptive_wait_starts_immediate_grows_with_fill():
    aw = AdaptiveWait(0.5, 1000)
    assert aw.next_wait() == 0.0  # cold start: no wait
    for _ in range(20):
        aw.observe(1000)  # windows fill completely
    assert aw.next_wait() == pytest.approx(0.5)  # full cap
    for _ in range(40):
        aw.observe(1)  # traffic stops filling windows
    assert aw.next_wait() < 0.01  # decays back toward immediate


def test_adaptive_wait_zero_cap_stays_zero():
    aw = AdaptiveWait(0.0, 1000)
    aw.observe(1000)
    assert aw.next_wait() == 0.0


# ---------------------------------------------------------------------
# IntervalBatcher: idle windows must not wait out their cap


def test_idle_interval_batcher_fires_without_cap_wait():
    """One item into an idle ADAPTIVE batcher with a huge cap must
    flush in milliseconds, not sync_wait (the cluster-tier p50
    mechanism: fixed windows stack in series on the GLOBAL path)."""
    import threading

    flushed = threading.Event()

    def flush(batch):
        flushed.set()

    b = IntervalBatcher(30.0, 1000, _combine, flush)
    try:
        t0 = time.monotonic()
        b.add("k", 1)
        assert flushed.wait(5.0), "idle window never fired"
        assert time.monotonic() - t0 < 2.0  # nowhere near the 30s cap
    finally:
        b.close()


def test_interval_batcher_current_wait_gauge():
    b = IntervalBatcher(0.5, 100, _combine, lambda batch: None)
    try:
        assert b.current_wait() == 0.0  # idle: fires immediately
    finally:
        b.close()
    fixed = IntervalBatcher(
        0.5, 100, _combine, lambda batch: None, adaptive=False
    )
    try:
        assert fixed.current_wait() == 0.5
    finally:
        fixed.close()


# ---------------------------------------------------------------------
# WireWindow: a single caller must not pay the window


class _Dec:
    def __init__(self, key=b"k"):
        self.n = 1
        self.key_buf = np.frombuffer(key, dtype=np.uint8).copy()
        self.key_offsets = np.asarray([0, len(key)], dtype=np.int64)
        for f in ("algo", "behavior"):
            setattr(self, f, np.zeros(1, dtype=np.int32))
        for f in ("hits", "limit", "duration", "burst"):
            setattr(self, f, np.ones(1, dtype=np.int64))
        self.fnv1a = np.zeros(1, dtype=np.uint64)


class _Engine:
    def apply_columnar(self, packed, algo, behavior, hits, limit,
                       duration, burst):
        n = len(algo)
        z = np.zeros(n, dtype=np.int64)
        return z, z, z, z


def test_wire_window_single_caller_no_wait():
    """An isolated submit through an adaptive window with a huge cap
    must return ~immediately (VERDICT r5: the client window was one of
    the serial stages taxing the GLOBAL median)."""
    ww = WireWindow(_Engine(), wait=5.0)
    t0 = time.monotonic()
    assert ww.submit(_Dec()) is not None
    assert time.monotonic() - t0 < 1.0, "single caller paid the window"
    assert ww.next_wait() == 0.0  # occupancy stayed at one RPC


def test_wire_window_wait_grows_under_grouping():
    ww = WireWindow(_Engine(), wait=0.002)
    # Simulate sustained grouped windows (what a herd produces).
    for _ in range(10):
        ww._observe(8)
    assert ww.next_wait() == pytest.approx(0.002)


# ---------------------------------------------------------------------
# The five-stage budget: reported end to end on the GLOBAL pipeline


STAGES = (
    "wire_window_wait",
    "engine_serve",
    "hits_window_wait",
    "owner_rpc",
    "broadcast_age",
    # Device-plane stages (ISSUE 10 / PERF.md §24).  device.window_wait
    # joins only when the step pump is live (conftest forces
    # GUBER_PUMP=1, so in-process cluster nodes carry it).
    "device.step",
    "device.readback",
    "device.window_wait",
    # Cross-region hop budget (ISSUE 14 / RESILIENCE.md §12).
    "multiregion.window_wait",
    "multiregion.region_rpc",
    # The served wire route, stage by stage (ISSUE 24 /
    # OBSERVABILITY.md §3): the listener's and the service's here, the
    # engine's own from utils/metrics.ENGINE_STAGES.
    "listener.queue_wait",
    "wire.decode",
    "service.hotkeys",
    "wire.encode",
    # The single-device engine's restore at start (PR 28); the cluster
    # harness runs one device a node.
    "engine.load",
) + ENGINE_STAGES


def test_global_pipeline_reports_all_stage_timers():
    from gubernator_tpu.cluster.harness import ClusterHarness
    from gubernator_tpu.net import wire_codec
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior, RateLimitReq

    if wire_codec.load() is None:
        pytest.skip("native codec unavailable")
    h = ClusterHarness().start(2, cache_size=4096)
    try:
        inst0 = h.daemon_at(0).instance
        # Every stage timer exists on every node.
        for inst in (inst0, h.daemon_at(1).instance):
            assert set(inst.stage_timers) == set(STAGES)
        # Drive non-owner GLOBAL wire traffic from node 0 so hits
        # forward to node 1 and its broadcast comes back.
        keys = [
            f"{i}sb" for i in range(400)
            if not inst0.get_peer(
                RateLimitReq(name="sb", unique_key=f"{i}sb").hash_key()
            ).info.is_owner
        ][:50]
        assert keys
        reqs = [
            pb.RateLimitReq(
                name="sb", unique_key=k, hits=1, limit=1000,
                duration=3_600_000, behavior=int(Behavior.GLOBAL),
            )
            for k in keys
        ]
        raw = pb.GetRateLimitsReq(requests=reqs).SerializeToString()
        for _ in range(3):
            assert inst0.serve_wire_bytes(raw) is not None
        inst0.global_mgr.flush_now()  # hits → owner
        h.daemon_at(1).instance.global_mgr.flush_now()  # broadcast
        t = inst0.stage_timers
        assert t["engine_serve"].count > 0  # local miss copies served
        assert t["hits_window_wait"].count > 0
        assert t["owner_rpc"].count > 0
        t1 = h.daemon_at(1).instance.stage_timers
        assert t1["broadcast_age"].count > 0
        # The daemon surfaces the budget (and /metrics exports it).
        budget = h.daemon_at(0).stage_budget()
        assert set(budget) == set(STAGES)
        assert budget["owner_rpc"]["count"] > 0
        from prometheus_client import generate_latest

        text = generate_latest(h.daemon_at(0).registry).decode()
        assert 'gubernator_stage_duration_count{stage="owner_rpc"}' in text
        assert "gubernator_adaptive_window_seconds" in text
    finally:
        h.stop()


def test_wire_window_wait_stage_counts():
    """A daemon with the client group-commit window enabled must
    observe the wire_window_wait stage on served wire batches."""
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net import wire_codec
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    if wire_codec.load() is None:
        pytest.skip("native codec unavailable")
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=4096,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        local_batch_wait=0.002,
    )
    d = spawn_daemon(conf)
    try:
        raw = pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="ws", unique_key="k", hits=1, limit=100,
                    duration=60_000,
                )
            ]
        ).SerializeToString()
        t0 = time.monotonic()
        assert d.instance.serve_wire_bytes(raw) is not None
        # Adaptive: the isolated caller did not pay the 2ms window
        # (and the stage recorded a ~zero wait).
        assert time.monotonic() - t0 < 1.0
        assert d.instance.stage_timers["wire_window_wait"].count >= 1
    finally:
        d.close()
