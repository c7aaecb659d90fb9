"""Cross-tier tracing: wire-propagated context, native event ring,
tail flight recorder, hot-key attribution (ISSUE 9).

The acceptance invariants, pinned:

- one same-host global4 decision produces ONE stitched trace spanning
  forwarder → owner → broadcast across processes boundaries (remote
  parents via the W3C traceparent metadata pair);
- chaos outcomes (degraded answers, circuit-open refusals) appear as
  span EVENTS, so a tail tree explains why it took the path it took;
- the native event ring drops (counted) instead of blocking when
  full, and the collector turns records into histograms + span stubs;
- natively-answered decisions produce `native.decide` span stubs —
  the first spans for the zero-Python fast path;
- /debug/trace, /debug/vars, /debug/hotkeys serve live data;
- DurationStat exports real streaming quantiles; the space-saving
  sketch obeys its error-bound contract.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.cluster.harness import ClusterHarness
from gubernator_tpu.types import Behavior, RateLimitReq
from gubernator_tpu.utils.tracing import (
    InMemoryTracer,
    TraceContext,
    format_traceparent,
    parse_traceparent,
    set_tracer,
)


@pytest.fixture
def tracer():
    t = InMemoryTracer()
    set_tracer(t)
    yield t
    set_tracer(None)


def _until(pred, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _req(name, key, behavior=0, hits=1, limit=1_000_000):
    return RateLimitReq(
        name=name, unique_key=key, hits=hits, limit=limit,
        duration=60_000, behavior=behavior,
    )


def _keys_not_owned_by(inst, name, n, tag):
    out, i = [], 0
    while len(out) < n and i < 4000:
        r = _req(name, f"{i}{tag}")
        if not inst.get_peer(r.hash_key()).info.is_owner:
            out.append(f"{i}{tag}")
        i += 1
    assert len(out) >= n, "expected remotely-owned keys"
    return out


# ----------------------------------------------------------------------
# Traceparent codec.


def test_traceparent_roundtrip():
    ctx = TraceContext(trace_id="ab" * 16, span_id="cd" * 8, sampled=True)
    tp = format_traceparent(ctx)
    assert tp == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(tp)
    assert back == ctx


def test_traceparent_rejects_malformed():
    for bad in (
        "", "00-zz-cd-01", "00-abc-def-01", "garbage",
        "00-" + "ab" * 16 + "-" + "cd" * 8,  # missing flags
        "00-" + "gg" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
    ):
        assert parse_traceparent(bad) is None


def test_remote_parent_and_parent_ctx(tracer):
    from gubernator_tpu.utils.tracing import current_context, span

    with span("outer.root") as root:
        ctx = current_context()
        assert ctx.trace_id == root.trace_id
    with span("cross.thread", parent_ctx=ctx) as child:
        assert child.trace_id == root.trace_id
        assert child.parent_span_id == root.span_id
        assert not child.remote
    remote = parse_traceparent(format_traceparent(ctx))
    with span("remote.server", remote_parent=remote) as srv:
        assert srv.trace_id == root.trace_id
        assert srv.parent_span_id == root.span_id
        assert srv.remote


# ----------------------------------------------------------------------
# The headline invariant: one global4 decision = one stitched trace.


def test_global_decision_single_stitched_trace(tracer):
    h = ClusterHarness().start(2, cache_size=1024)
    try:
        inst = h.daemon_at(0).instance
        keys = _keys_not_owned_by(inst, "stitch", 3, "g")
        tracer.clear()
        inst.get_rate_limits(
            [_req("stitch", k, behavior=Behavior.GLOBAL) for k in keys]
        )
        roots = tracer.spans("service.get_rate_limits")
        assert len(roots) == 1
        tid = roots[0].trace_id

        def _stitched():
            names = {s.name for s in tracer.trace(tid)}
            return (
                ("global.hits_window" in names
                 or "global.hits_window_columnar" in names)
                and "rpc.get_peer_rate_limits" in names
                and "global.broadcast" in names
                and "rpc.update_peer_globals" in names
            )

        assert _until(_stitched, timeout=60), sorted(
            {s.name for s in tracer.trace(tid)}
        )
        spans = {s.name: s for s in tracer.trace(tid)}
        # The owner-side handler crossed a process boundary: its
        # parent is REMOTE and is the hits fan-out task's span.
        owner = spans["rpc.get_peer_rate_limits"]
        assert owner.remote
        parent = next(
            s for s in tracer.trace(tid) if s.span_id == owner.parent_span_id
        )
        assert parent.name in ("global.owner_rpc", "global.owner_rpc_pb")
        # The broadcast landed back on the forwarder with a remote
        # parent under the broadcast fan-out.
        upd = spans["rpc.update_peer_globals"]
        assert upd.remote
        bparent = next(
            s for s in tracer.trace(tid) if s.span_id == upd.parent_span_id
        )
        assert bparent.name == "global.broadcast_push"
        # And the whole tree shares the ONE trace id (the point).
        assert all(s.trace_id == tid for s in tracer.trace(tid))
    finally:
        h.stop()


def test_forwarded_request_carries_context(tracer):
    """Plain (non-GLOBAL) forwarding: the owner's handler span joins
    the forwarder's trace via gRPC metadata."""
    h = ClusterHarness().start(2, cache_size=1024)
    try:
        inst = h.daemon_at(0).instance
        keys = _keys_not_owned_by(inst, "fwd_tp", 3, "f")
        tracer.clear()
        inst.get_rate_limits([_req("fwd_tp", k) for k in keys])
        roots = tracer.spans("service.get_rate_limits")
        assert len(roots) == 1
        tid = roots[0].trace_id
        names = {s.name for s in tracer.trace(tid)}
        assert "forward.group" in names
        assert "peer.batch_rpc" in names
        assert "rpc.get_peer_rate_limits" in names
        owner = next(
            s for s in tracer.trace(tid)
            if s.name == "rpc.get_peer_rate_limits"
        )
        assert owner.remote
    finally:
        h.stop()


# ----------------------------------------------------------------------
# Chaos outcomes surface as span events.


def test_degraded_and_circuit_open_span_events(tracer):
    h = ClusterHarness().start(3)
    try:
        inst = h.daemon_at(0).instance
        keys = _keys_not_owned_by(inst, "chaos_tp", 4, "c")
        h.install_faults(seed=5)
        h.partition(0, 1)
        h.partition(0, 2)

        def _events():
            evs = {
                name
                for s in tracer.spans()
                for name, _attrs in s.events
            }
            return "degraded_answer" in evs and "circuit_open" in evs

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not _events():
            inst.get_rate_limits([_req("chaos_tp", keys[0])])
            time.sleep(0.05)
        assert _events(), [
            (s.name, s.events) for s in tracer.spans() if s.events
        ]
        # The degraded event names the unreachable owner.
        ev = next(
            attrs
            for s in tracer.spans()
            for name, attrs in s.events
            if name == "degraded_answer"
        )
        assert ev["owner"]
        assert ev["items"] >= 1
    finally:
        h.stop()


# ----------------------------------------------------------------------
# Native event ring: overflow drops, never blocks; collector stitches.


def _ring_lib():
    from gubernator_tpu.net import h2_fast

    lib = h2_fast.load()
    if lib is None:
        pytest.skip("native h2 server unavailable")
    return lib


def test_event_ring_overflow_drops_counted():
    import ctypes

    lib = _ring_lib()
    ring = ctypes.c_void_p(lib.evr_create(8))
    t0 = time.monotonic()
    for i in range(1000):
        lib.evr_record(ring, 1, 123456789 + i, 1000, 1)
    elapsed = time.monotonic() - t0
    # Never blocks: 1000 writes into an 8-slot ring complete ~instantly.
    assert elapsed < 1.0
    st = np.zeros(2, dtype=np.int64)
    lib.evr_stats(ring, st.ctypes.data_as(ctypes.c_void_p))
    assert st[0] == 8  # written
    assert st[1] == 992  # dropped, counted
    out = np.zeros(4 * 64, dtype=np.int64)
    n = lib.evr_drain(ring, out.ctypes.data_as(ctypes.c_void_p), 64)
    assert n == 8
    # Drain frees the slots: the ring accepts new events again.
    assert lib.evr_record(ring, 2, 1, 2, 3) == 1
    lib.evr_free(ring)


def test_event_ring_concurrent_producers():
    """Multi-producer claim: concurrent writers never corrupt records
    (every drained record is one of the written shapes) and
    written + dropped == attempts."""
    import ctypes
    import threading

    lib = _ring_lib()
    ring = ctypes.c_void_p(lib.evr_create(1024))
    per_thread = 5000
    n_threads = 4

    def producer(kind):
        for _ in range(per_thread):
            lib.evr_record(ring, kind, 1000 * kind, 10 * kind, kind)

    threads = [
        threading.Thread(target=producer, args=(k + 1,))
        for k in range(n_threads)
    ]
    drained = []
    stop = threading.Event()

    def consumer():
        out = np.zeros(4 * 512, dtype=np.int64)
        while not stop.is_set() or True:
            n = lib.evr_drain(
                ring, out.ctypes.data_as(ctypes.c_void_p), 512
            )
            if n:
                drained.append(out[: 4 * n].reshape(n, 4).copy())
            elif stop.is_set():
                return
            else:
                time.sleep(0.001)

    c = threading.Thread(target=consumer)
    c.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    c.join()
    st = np.zeros(2, dtype=np.int64)
    lib.evr_stats(ring, st.ctypes.data_as(ctypes.c_void_p))
    total = sum(len(d) for d in drained)
    assert int(st[0]) == total
    assert int(st[0]) + int(st[1]) == per_thread * n_threads
    for d in drained:
        for kind, t_ns, dur, items in d.tolist():
            assert kind in (1, 2, 3, 4)
            assert (t_ns, dur, items) == (1000 * kind, 10 * kind, kind)
    lib.evr_free(ring)


class _FakeFront:
    """Collector unit-test stand-in for H2FastFront's ring surface."""

    def __init__(self, records):
        self._records = list(records)
        self._drops = 0

    def drain_events(self, out):
        n = min(len(self._records), len(out) // 4)
        for i in range(n):
            out[4 * i: 4 * i + 4] = self._records.pop(0)
        return n

    def ring_stats(self):
        return {"written": 3, "dropped": self._drops, "enabled": True}


def test_collector_histograms_and_span_stubs(tracer):
    from gubernator_tpu.utils.native_events import NativeEventCollector

    t_end = time.monotonic_ns()
    front = _FakeFront(
        [
            [1, t_end, 250_000, 2],       # native_serve 250µs
            [2, t_end, 2_000_000, 1],     # window_wait 2ms
            [3, t_end, 1_000_000, 3],     # window_serve 1ms
        ]
    )
    col = NativeEventCollector(front, interval=10.0)  # drain manually
    try:
        assert col.drain_once() == 3
        counts = col.event_counts()
        assert {k: v for k, v in counts.items() if v} == {
            "native_serve": 1, "window_wait": 1, "window_serve": 1,
        }
        h = col.histograms()["native_serve"]
        assert h.count == 1
        # Log2 buckets: 250µs lands within a factor of 2.
        assert 1e-4 < h.p50() < 1e-3
        stubs = tracer.spans("native.decide")
        assert len(stubs) == 1
        assert stubs[0].attributes["items"] == 2
        assert stubs[0].end_ns - stubs[0].start_ns == 250_000
        assert col.stats()["stages"]["window_wait"]["count"] == 1
    finally:
        col.close()


def test_native_answers_emit_span_stubs(tracer):
    """Harness-level: a hot key answered by the native decision plane
    yields native.decide span stubs via the ring collector — the
    first tracing signal from the zero-Python path."""
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net import h2_fast
    from gubernator_tpu.net.grpc_service import V1Stub, dial
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    if h2_fast.load() is None:
        pytest.skip("native h2 server unavailable")
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=1 << 12,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        h2_fast_address="127.0.0.1:0",
        h2_fast_window=0.001,
        ledger_hot_threshold=2,
    )
    d = spawn_daemon(conf)
    try:
        if d.h2_fast.plane is None:
            pytest.skip("native decision plane not attached")
        assert d.instance.native_events is not None
        stub = V1Stub(dial(d.h2_fast_address))
        payload = pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="natspan", unique_key="hot", hits=1,
                    limit=10**9, duration=3_600_000,
                )
            ]
        )

        def _stubbed():
            stub.GetRateLimits(payload)
            return (
                d.h2_fast.stats().get("native_rpcs", 0) > 0
                and tracer.spans("native.decide")
            )

        assert _until(_stubbed, timeout=30, interval=0.02), d.h2_fast.stats()
        # The ring actually carried the events (no silent bypass).
        assert d.instance.native_events.ring_stats()["written"] > 0
        assert d.instance.native_events.event_counts()["native_serve"] > 0
    finally:
        d.close()


# ----------------------------------------------------------------------
# /debug introspection surface.


def _get_json(http_address, path):
    return json.loads(
        urllib.request.urlopen(
            f"http://{http_address}{path}", timeout=10
        ).read().decode()
    )


def test_debug_endpoints_serve_live_data(tracer, monkeypatch):
    monkeypatch.setenv("GUBER_TRACE_TAIL_MIN_MS", "0")
    monkeypatch.setenv("GUBER_TRACE_TAIL_FACTOR", "0")
    h = ClusterHarness().start(1, cache_size=1024)
    try:
        inst = h.daemon_at(0).instance
        inst.get_rate_limits(
            [_req("dbg", f"k{i}", hits=3) for i in range(5)]
        )
        addr = h.daemon_at(0).http_address
        vars_ = _get_json(addr, "/debug/vars")
        assert vars_["counters"]["local"] >= 5
        assert "engine_serve" in vars_["stage_budget"]
        assert {"count", "mean_ms", "p50_ms", "p99_ms", "max_ms"} <= set(
            vars_["stage_budget"]["engine_serve"]
        )
        # Device-plane attribution (ISSUE 10): where device
        # milliseconds go, in the same budget table.
        assert "device.step" in vars_["stage_budget"]
        assert vars_["stage_budget"]["device.step"]["count"] >= 1
        assert "device.readback" in vars_["stage_budget"]
        # What the node serves on (ISSUE 21): platform, engine,
        # pump/scan state and every compile probe's verdict with
        # its reason.  conftest asked for the CPU by name.
        dev = vars_["device"]
        assert dev["platform"] == "cpu" and dev["device_count"] == 1
        assert dev["cpu_unrequested"] is False
        assert dev["engine"] == "DecisionEngine" and dev["rows"] == 1024
        assert dev["pump"] is True
        assert dev["pump_scan"] is False  # singles on the CPU backend
        assert set(dev["probes"]) == {"fused_step", "multi_step", "write_form"}
        assert all(v["ok"] and v["reason"] for v in dev["probes"].values())
        assert dev["counters"]["dispatches_total"] >= 1
        # a 1,024-row table: the hinted pass at every width, no row write
        assert dev["probes"]["write_form"]["reason"].startswith("64:pass ")
        assert dev["counters"]["row_write_dispatches_total"] == 0
        assert dev["compiles"]["backend_compiles"] >= 1
        # PR 13/14 planes (ISSUE 15 satellite): the replication and
        # multiregion managers' stats in the one-stop snapshot —
        # manager counters, per-region circuit state, held/requeued
        # accounting.
        mr = vars_["multiregion"]
        assert {
            "windows", "region_sends", "hits_requeued",
            "hits_dropped", "region_states", "pending",
            "pending_retry", "window_wait", "region_rpc",
        } <= set(mr)
        if vars_.get("replication") is not None:
            repl = vars_["replication"]
            assert {
                "promoted_keys", "replica_leases", "promoted",
                "demoted", "answered", "credit_granted",
            } <= set(repl)
        hot = _get_json(addr, "/debug/hotkeys")
        assert hot["enabled"]
        assert any(r["key"].startswith("dbg_") for r in hot["top"])
        assert all(
            {"key", "count", "err"} <= set(r) for r in hot["top"]
        )
        # Threshold 0 ⇒ every root records: the trace dump has trees.
        trace = _get_json(addr, "/debug/trace")
        assert trace["enabled"]
        assert trace["recorded"] >= 1
        assert trace["traces"], trace
        tree = trace["traces"][-1]
        assert tree["spans"] and tree["trace_id"]
        assert any(
            s["name"] == "service.get_rate_limits" for s in tree["spans"]
        )
    finally:
        h.stop()


def test_debug_endpoints_disabled_shapes(monkeypatch):
    """Without a tracer / with hotkeys off, the endpoints answer their
    disabled shapes instead of erroring."""
    monkeypatch.setenv("GUBER_HOTKEYS", "0")
    set_tracer(None)
    h = ClusterHarness().start(1, cache_size=256)
    try:
        addr = h.daemon_at(0).http_address
        assert _get_json(addr, "/debug/trace") == {
            "enabled": False, "traces": [],
        }
        hot = _get_json(addr, "/debug/hotkeys")
        assert hot == {"enabled": False, "top": []}
        vars_ = _get_json(addr, "/debug/vars")
        assert "stage_budget" in vars_
        # The PR 13/14 sections answer their shapes even when the
        # planes are idle (single node, no replication traffic).
        assert "multiregion" in vars_
        assert "replication" in vars_ or h.daemon_at(
            0
        ).replication is None
    finally:
        h.stop()


# ----------------------------------------------------------------------
# DurationStat streaming quantiles.


def test_duration_stat_quantiles():
    from gubernator_tpu.utils.metrics import DurationStat

    s = DurationStat()
    assert s.p50() == 0.0 and s.p99() == 0.0
    for _ in range(90):
        s.observe(0.001)
    for _ in range(10):
        s.observe(0.512)
    # p50 within the 1ms octave, p99 within the 512ms octave.
    assert 0.0005 < s.p50() < 0.002
    assert 0.25 < s.p99() < 1.1
    assert s.max == 0.512
    assert s.count == 100
    # Bucket merge (the collector's path) agrees with observe.
    m = DurationStat()
    counts = [0] * DurationStat.N_BUCKETS
    counts[DurationStat.bucket_of(0.001)] = 90
    counts[DurationStat.bucket_of(0.512)] = 10
    m.observe_bucket_counts(counts)
    assert m.count == 100
    assert 0.0005 < m.p50() < 0.002
    assert 0.25 < m.p99() < 1.1


def test_duration_stat_bucket_edges():
    from gubernator_tpu.utils.metrics import DurationStat

    assert DurationStat.bucket_of(0.0) == 0
    assert DurationStat.bucket_of(1e-9) == 0
    assert DurationStat.bucket_of(1e6) == DurationStat.N_BUCKETS - 1
    # Monotone non-decreasing over magnitudes.
    prev = -1
    for e in range(-7, 3):
        b = DurationStat.bucket_of(10.0 ** e)
        assert b >= prev
        prev = b


# ----------------------------------------------------------------------
# Space-saving hot-key sketch.


def test_space_saving_topk_contract():
    from gubernator_tpu.utils.hotkeys import SpaceSaving

    sk = SpaceSaving(capacity=8)
    true = {}
    # A heavy hitter + a long tail larger than capacity.
    for i in range(200):
        key = b"hot" if i % 2 == 0 else f"tail{i}".encode()
        n = 5 if key == b"hot" else 1
        true[key] = true.get(key, 0) + n
        sk.offer(key, n)
    top = sk.top(3)
    assert top[0][0] == b"hot"
    hot_est, hot_err = top[0][1], top[0][2]
    # Estimate bounds: true <= est <= true + err.
    assert true[b"hot"] <= hot_est <= true[b"hot"] + hot_err
    assert sk.stats()["tracked"] <= 8
    assert sk.stats()["offered"] == sum(true.values())


def test_space_saving_offer_columns():
    from gubernator_tpu.utils.hotkeys import SpaceSaving

    keys = [b"aa_1", b"bb_2", b"aa_1", b"cc_3"]
    buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
    offs = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offs[1:])
    hits = np.array([2, 1, 3, 0], dtype=np.int64)
    sk = SpaceSaving(capacity=16)
    sk.offer_columns(buf, offs, hits)
    table = {k: c for k, c, _e in sk.top(10)}
    assert table[b"aa_1"] == 5
    assert table[b"bb_2"] == 1
    assert table[b"cc_3"] == 1  # hits=0 counts as one observation
    # idx subset restriction.
    sk2 = SpaceSaving(capacity=16)
    sk2.offer_columns(buf, offs, hits, idx=np.array([0, 1]))
    assert {k for k, _c, _e in sk2.top(10)} == {b"aa_1", b"bb_2"}


# ----------------------------------------------------------------------
# Flight recorder semantics.


def test_flight_recorder_adaptive_threshold(tracer):
    from gubernator_tpu.utils.flight_recorder import FlightRecorder
    from gubernator_tpu.utils.tracing import span

    fr = FlightRecorder(tracer, factor=2.0, min_ms=20.0, cap=4)
    # Fast roots stay below the 20ms floor: not recorded.
    for _ in range(5):
        with span("fast.root"):
            pass
    assert fr.dump()["recorded"] == 0
    # A slow root records its whole tree, children included.
    with span("slow.root"):
        with span("slow.child"):
            time.sleep(0.03)
    dump = fr.dump()
    assert dump["recorded"] == 1
    tree = dump["traces"][0]
    assert {s["name"] for s in tree["spans"]} == {
        "slow.root", "slow.child",
    }
    assert tree["duration_ms"] >= 20
    # Bounded retention: the ring keeps at most `cap` trees.
    for _ in range(10):
        with span("slow.root2"):
            time.sleep(0.025)
    assert len(fr.dump()["traces"]) <= 4
    fr.close()
    assert tracer.on_root_finish is None


def test_log_lines_carry_trace_id(tracer, capsys):
    import logging
    import os

    from gubernator_tpu.utils.logging_setup import configure_logging
    from gubernator_tpu.utils.tracing import span

    os.environ["GUBER_LOG_FORMAT"] = "json"
    try:
        configure_logging()
        log = logging.getLogger("stitch.test")
        with span("logged.op") as s:
            log.warning("inside")
            tid = s.trace_id
        log.warning("outside")
        lines = [
            json.loads(l)
            for l in capsys.readouterr().err.strip().splitlines()
            if l
        ]
        inside = next(l for l in lines if l["msg"] == "inside")
        outside = next(l for l in lines if l["msg"] == "outside")
        assert inside["trace_id"] == tid
        assert "trace_id" not in outside
    finally:
        os.environ.pop("GUBER_LOG_FORMAT")
        logging.getLogger().handlers[:] = []
