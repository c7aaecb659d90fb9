"""Event-driven native front (PERF.md §26): the epoll reactor plane.

RPC correctness through the reactors (parity with the thread-per-conn
plane, across the native decision plane and the columnar feeder),
partial/coalesced frame delivery under edge-triggered reads, writev
short-write resumption and backpressure when a client stops reading,
idle-connection reaping, teardown under live load, the reactor
stages in the event ring, and the hand-off of a window's responses to
the reactors that own their connections.
"""

import socket
import struct
import threading
import time

import pytest

from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.core import h2_client
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.net import h2_fast
from gubernator_tpu.net.grpc_service import V1Stub, dial
from gubernator_tpu.net.h2_fast import H2FastFront
from gubernator_tpu.net.pb import gubernator_pb2 as pb

PATH = "/pb.gubernator.V1/GetRateLimits"


@pytest.fixture
def daemon():
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
    )
    d = spawn_daemon(conf)
    yield d
    d.close()


def _req(name, key, hits=1, limit=100, n=1):
    return pb.GetRateLimitsReq(
        requests=[
            pb.RateLimitReq(
                name=name, unique_key=f"{key}{i}", hits=hits,
                limit=limit, duration=60_000,
            )
            for i in range(n)
        ]
    )


def _h2_frames(sock, deadline):
    """Yield (type, flags, stream, payload) until timeout/close."""
    buf = b""
    while True:
        while len(buf) < 9:
            sock.settimeout(max(0.05, deadline - time.monotonic()))
            try:
                chunk = sock.recv(65536)
            except (socket.timeout, TimeoutError):
                return
            if not chunk:
                return
            buf += chunk
        flen = (buf[0] << 16) | (buf[1] << 8) | buf[2]
        ftype, flags = buf[3], buf[4]
        stream = struct.unpack(">I", buf[5:9])[0] & 0x7FFFFFFF
        while len(buf) < 9 + flen:
            sock.settimeout(max(0.05, deadline - time.monotonic()))
            try:
                chunk = sock.recv(65536)
            except (socket.timeout, TimeoutError):
                return
            if not chunk:
                return
            buf += chunk
        yield ftype, flags, stream, buf[9 : 9 + flen]
        buf = buf[9 + flen :]


def _frame(ftype, flags, stream, payload=b""):
    return (
        struct.pack(">I", len(payload))[1:]
        + bytes([ftype, flags])
        + struct.pack(">I", stream)
        + payload
    )


def _grpc_frame(body):
    return b"\x00" + struct.pack(">I", len(body)) + body


def _read_responses(sock, want_streams, timeout=5.0):
    """Collect {stream: (data, saw_trailers)} until every wanted
    stream finished."""
    out = {s: b"" for s in want_streams}
    done = set()
    deadline = time.monotonic() + timeout
    for ftype, flags, stream, payload in _h2_frames(sock, deadline):
        if stream not in out:
            continue
        if ftype == 0:
            out[stream] += payload
        elif ftype == 1 and flags & 0x1:
            done.add(stream)
            if done == set(want_streams):
                break
    return out, done


def test_event_front_is_default_and_serves(daemon):
    """spawn_daemon's front must come up on the reactor plane and
    serve a stock grpc client correctly."""
    cs = daemon.h2_fast.conn_stats()
    assert cs["event_front"] is True
    assert cs["reactors"] >= 1
    stub = V1Stub(dial(daemon.h2_fast_address))
    for expect in (99, 98, 97):
        got = stub.GetRateLimits(_req("ev", "k"))
        assert got.responses[0].remaining == expect


def test_event_vs_threaded_parity(daemon):
    """The two connection planes share one frame machine and one
    serve pipeline: alternating RPCs across an event front and a
    threaded front on the SAME instance must hit the same buckets."""
    threaded = H2FastFront(
        daemon.instance, window_s=0.001, event_front=False
    )
    try:
        ev = V1Stub(dial(daemon.h2_fast_address))
        th = V1Stub(dial(threaded.address))
        remaining = []
        for i in range(6):
            stub = ev if i % 2 == 0 else th
            got = stub.GetRateLimits(_req("par", "x"))
            remaining.append(got.responses[0].remaining)
        assert remaining == [99, 98, 97, 96, 95, 94]
        # the threaded plane's serving threads write through: no hand-off
        st = _settled(threaded, "rpcs", 3)
        assert st["rpcs"] == 3 and st["responses_handed_off"] == 0
    finally:
        threaded.close()


@pytest.mark.parametrize("feeder", [True, False], ids=["feeder", "bytepath"])
def test_event_front_feeder_attach_detach_parity(daemon, feeder):
    """Reactor-packed feeder windows and the byte window path must
    answer identically through the event front (attach-detach
    parity)."""
    front = H2FastFront(
        daemon.instance, window_s=0.001, native_feeder=feeder
    )
    try:
        stub = V1Stub(dial(front.address))
        got = stub.GetRateLimits(_req("fd" + str(int(feeder)), "k", n=7))
        assert [r.remaining for r in got.responses] == [99] * 7
        got = stub.GetRateLimits(_req("fd" + str(int(feeder)), "k", n=7))
        assert [r.remaining for r in got.responses] == [98] * 7
    finally:
        front.close()


def test_partial_frame_delivery(daemon):
    """Edge-triggered reads must reassemble a request delivered one
    dribble at a time: preface split mid-token, frame headers split
    mid-header, DATA split mid-payload."""
    host, port = daemon.h2_fast_address.rsplit(":", 1)
    body = _req("part", "k", n=3).SerializeToString()
    wire = (
        b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
        + _frame(1, 0x4, 1)  # HEADERS, empty block (port is the route)
        + _frame(0, 0x1, 1, _grpc_frame(body))
    )
    sock = socket.create_connection((host, int(port)), timeout=5)
    try:
        # 5-byte dribbles with pauses: every chunk crosses a frame or
        # preface boundary somewhere in the stream.
        for i in range(0, len(wire), 5):
            sock.sendall(wire[i : i + 5])
            time.sleep(0.002)
        out, done = _read_responses(sock, [1])
        assert done == {1}
        data = out[1]
        (ln,) = struct.unpack(">I", data[1:5])
        resp = pb.GetRateLimitsResp.FromString(data[5 : 5 + ln])
        assert [r.remaining for r in resp.responses] == [99] * 3
    finally:
        sock.close()


def test_coalesced_frames_one_read(daemon):
    """Multiple complete RPCs landing in ONE read (streams 1/3/5
    coalesced into a single send) must all answer."""
    host, port = daemon.h2_fast_address.rsplit(":", 1)
    wire = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
    for sid in (1, 3, 5):
        body = _req("coal", f"s{sid}_").SerializeToString()
        wire += _frame(1, 0x4, sid) + _frame(0, 0x1, sid, _grpc_frame(body))
    sock = socket.create_connection((host, int(port)), timeout=5)
    try:
        sock.sendall(wire)  # one send: the reactor sees them coalesced
        out, done = _read_responses(sock, [1, 3, 5])
        assert done == {1, 3, 5}
        for sid in (1, 3, 5):
            data = out[sid]
            (ln,) = struct.unpack(">I", data[1:5])
            resp = pb.GetRateLimitsResp.FromString(data[5 : 5 + ln])
            assert resp.responses[0].remaining == 99
    finally:
        sock.close()


def test_writev_short_write_resumption_backpressure(daemon):
    """A client that stops reading must park the response in the
    egress queue (short writev → EPOLLOUT resumption), NOT block a
    reactor — proven by a second client staying fully served during
    the stall — and the parked response must complete once the client
    resumes reading."""
    host, port = daemon.h2_fast_address.rsplit(":", 1)
    n_items = 900  # ~9KB response
    body = _req("bp", "k", n=n_items).SerializeToString()
    slow = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    # Tiny receive buffer: the response cannot fit in flight, so the
    # server's writev MUST short-write once the client stops reading.
    slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 2048)
    slow.connect((host, int(port)))
    try:
        slow.sendall(
            b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
            + _frame(1, 0x4, 1)
            + _frame(0, 0x1, 1, _grpc_frame(body))
        )
        # Stall: read NOTHING while a second client runs a full loop.
        time.sleep(0.3)
        fast = V1Stub(dial(daemon.h2_fast_address))
        for expect in (99, 98, 97):
            got = fast.GetRateLimits(_req("bp_fast", "k"), timeout=5)
            assert got.responses[0].remaining == expect
        # Resume: the parked response must drain completely.
        out, done = _read_responses(sock=slow, want_streams=[1], timeout=8.0)
        assert done == {1}, "parked response never resumed"
        data = out[1]
        (ln,) = struct.unpack(">I", data[1:5])
        resp = pb.GetRateLimitsResp.FromString(data[5 : 5 + ln])
        assert len(resp.responses) == n_items
        assert all(r.remaining == 99 for r in resp.responses)
    finally:
        slow.close()


def test_idle_connection_reaped(daemon):
    """A connection silent past GUBER_H2_IDLE_TIMEOUT gets GOAWAY +
    close, and the conns gauge books it — the pre-§26 front held dead
    connections forever."""
    front = H2FastFront(daemon.instance, window_s=0.001, idle_timeout_s=0.3)
    try:
        sock = socket.create_connection(("127.0.0.1", front.port), timeout=5)
        sock.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n")
        types = [
            t for t, _f, _s, _p in _h2_frames(sock, time.monotonic() + 3.0)
        ]
        sock.close()
        assert 7 in types, f"no GOAWAY before close (saw {types})"
        # The reactor books the reap after it has written the GOAWAY:
        # a client that read it first has to look again.
        deadline = time.monotonic() + 5.0
        while True:
            cs = front.conn_stats()
            if cs["conns_idle_reaped"] >= 1 or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert cs["conns_idle_reaped"] >= 1
        assert cs["conns_open"] == 0
    finally:
        front.close()


def test_active_connection_not_reaped(daemon):
    """The idle sweep must key on ACTIVITY, not connection age: a
    connection older than the timeout but still trafficking stays."""
    front = H2FastFront(daemon.instance, window_s=0.001, idle_timeout_s=0.4)
    try:
        stub = V1Stub(dial(front.address))
        deadline = time.monotonic() + 1.2  # 3× the timeout
        n = 0
        while time.monotonic() < deadline:
            got = stub.GetRateLimits(_req("alive", "k", limit=10**6))
            assert not got.responses[0].error
            n += 1
            time.sleep(0.1)
        assert front.conn_stats()["conns_idle_reaped"] == 0
        assert n >= 8
    finally:
        front.close()


def test_teardown_under_live_load(daemon):
    """close() with RPC traffic mid-flight must drain cleanly: no
    hang, no crash, and the daemon's shared engine stays serviceable
    through another front afterwards."""
    front = H2FastFront(daemon.instance, window_s=0.001)
    payload = _req("tear", "k", limit=10**9).SerializeToString()
    res = [None]

    def load():
        res[0] = h2_client.bench_unary(front.address, PATH, payload, 1.5, 4)

    t = threading.Thread(target=load)
    t.start()
    time.sleep(0.4)  # traffic is flowing
    front.close()
    t.join(timeout=20)
    assert not t.is_alive(), "client hung through server teardown"
    # The engine survived: a fresh front serves.
    front2 = H2FastFront(daemon.instance, window_s=0.001)
    try:
        stub = V1Stub(dial(front2.address))
        got = stub.GetRateLimits(_req("tear2", "k"))
        assert got.responses[0].remaining == 99
    finally:
        front2.close()


def test_reactor_stages_reach_event_ring(daemon):
    """reactor_wake / reactor_read must flow through the native event
    ring into the collector's histograms after traffic."""
    stub = V1Stub(dial(daemon.h2_fast_address))
    for _ in range(20):
        stub.GetRateLimits(_req("ring", "k", limit=10**6))
    ev = daemon.instance.native_events
    assert ev is not None
    ev.drain_once()
    counts = ev.event_counts()
    assert counts.get("reactor_wake", 0) > 0
    assert counts.get("reactor_read", 0) > 0
    stats = ev.stats()
    assert "reactor_wake" in stats["stages"]


def test_h2_conns_gauge_exported(daemon):
    """gubernator_h2_conns{state} must come out of the instance
    collector while a connection is held open."""
    from gubernator_tpu.utils.metrics import InstanceCollector

    host, port = daemon.h2_fast_address.rsplit(":", 1)
    sock = socket.create_connection((host, int(port)), timeout=5)
    try:
        sock.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n")
        time.sleep(0.1)
        metrics = {
            m.name: m for m in InstanceCollector(daemon.instance).collect()
        }
        assert "gubernator_h2_conns" in metrics
        samples = {
            s.labels["state"]: s.value
            for s in metrics["gubernator_h2_conns"].samples
        }
        assert samples["open"] >= 1
        assert "idle_reaped" in samples
    finally:
        sock.close()


def test_connscale_client_against_event_front(daemon):
    """The epoll connscale client holds hundreds of mostly-idle
    connections plus a closed active loop with zero errors — the
    C10K building block the §26 bench ramps."""
    payload = _req("cs", "hot", limit=10**12).SerializeToString()
    res = [None]

    def run():
        res[0] = h2_client.connscale(
            daemon.h2_fast_address, PATH, payload, 1.5, 200, 8, threads=1
        )

    t = threading.Thread(target=run)
    t.start()
    # The server must be HOLDING all 200 while the run is live (the
    # client closes them at its deadline, so sample mid-flight).
    peak = 0
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and peak < 200:
        peak = max(peak, daemon.h2_fast.conn_stats()["conns_open"])
        time.sleep(0.05)
    t.join(timeout=30)
    assert not t.is_alive()
    assert peak >= 200
    out = res[0]
    assert out is not None
    assert out["connected"] == 200
    assert out["alive_at_end"] == 200
    assert out["errors"] == 0
    assert out["rpcs"] > 0


# -- the hand-off: a window's responses are written by their reactors ----


def _settled(front, key, want, timeout=5.0):
    """front.stats() once `key` has reached `want`: a reactor counts a
    response after its writev, so a client may read it first."""
    deadline = time.monotonic() + timeout
    while True:
        st = front.stats()
        if st[key] >= want or time.monotonic() > deadline:
            return st
        time.sleep(0.01)


def _open_streams(port, streams):
    """A raw h2 connection with `streams` ({id: request}) sent in one
    write."""
    wire = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
    for sid, req in streams.items():
        wire += _frame(1, 0x4, sid) + _frame(
            0, 0x1, sid, _grpc_frame(req.SerializeToString()))
    sock = socket.create_connection(("127.0.0.1", port), timeout=5)
    sock.sendall(wire)
    return sock


def _resp(data):
    (ln,) = struct.unpack(">I", data[1:5])
    return pb.GetRateLimitsResp.FromString(data[5 : 5 + ln])


@pytest.mark.parametrize("feeder", [True, False], ids=["feeder", "bytepath"])
def test_window_responses_are_written_by_their_reactors(daemon, feeder):
    """A window's RPCs on 24 connections over four reactors, three
    streams each: the thread that serves the window (the feeder's, or
    the byte path's dispatch thread) queues every response on its
    connection and that connection's reactor writes it.  Each stream
    gets its own body, its frames in order (HEADERS, DATA, trailers),
    and `responses_handed_off` counts each response once."""
    front = H2FastFront(daemon.instance, window_s=0.02, native_feeder=feeder,
                        native_ledger=False, event_front=True, reactors=4)
    tag = "ho" + str(int(feeder))
    try:
        assert front.conn_stats()["reactors"] == 4
        before = front.stats()
        sids = (1, 3, 5)
        hits = {(c, sid): 1 + (3 * c + sid) % 7 for c in range(24) for sid in sids}
        socks = [
            _open_streams(front.port, {
                sid: _req(tag, f"c{c}s{sid}_", hits=hits[c, sid], n=2)
                for sid in sids})
            for c in range(24)
        ]
        try:
            for c, sock in enumerate(socks):
                frames = {sid: [] for sid in sids}
                data = {sid: b"" for sid in sids}
                done = set()
                for ftype, flags, sid, payload in _h2_frames(
                        sock, time.monotonic() + 5.0):
                    if sid not in frames:
                        continue
                    trailers = ftype == 1 and bool(flags & 0x1)
                    frames[sid].append("trailers" if trailers else
                                       {0: "data", 1: "headers"}[ftype])
                    data[sid] += payload if ftype == 0 else b""
                    if trailers:
                        done.add(sid)
                        if done == set(sids):
                            break
                for sid in sids:
                    assert frames[sid] == ["headers", "data", "trailers"], (c, sid)
                    assert [r.remaining for r in _resp(data[sid]).responses] == [
                        100 - hits[c, sid]] * 2, (c, sid)
        finally:
            for sock in socks:
                sock.close()
        n = len(hits)
        after = _settled(front, "rpcs", before["rpcs"] + n)
        assert after["rpcs"] - before["rpcs"] == n
        assert after["responses_handed_off"] - before["responses_handed_off"] == n
        assert after["errors"] == before["errors"]
    finally:
        front.close()


def test_a_response_past_the_peers_window_completes_through_its_reactor(daemon):
    """A response larger than the peer's stream window, handed off by
    the serve thread: its reactor writes HEADERS and what the window
    allows, and the rest once the peer's WINDOW_UPDATE reaches that
    reactor."""
    front = H2FastFront(daemon.instance, window_s=0.001, native_ledger=False)
    try:
        before = front.stats()
        sock = socket.create_connection(("127.0.0.1", front.port), timeout=5)
        try:
            sock.sendall(
                b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
                # SETTINGS: INITIAL_WINDOW_SIZE = 64
                + _frame(4, 0, 0, struct.pack(">HI", 4, 64))
                + _frame(1, 0x4, 1)
                + _frame(0, 0x1, 1, _grpc_frame(
                    _req("fc_ho", "k", n=200).SerializeToString())))
            data, trailers = b"", False
            for ftype, flags, sid, payload in _h2_frames(
                    sock, time.monotonic() + 1.0):
                if sid == 1 and ftype == 0:
                    data += payload
                trailers |= sid == 1 and ftype == 1 and bool(flags & 0x1)
            assert 0 < len(data) <= 64 and not trailers
            mid = _settled(front, "responses_handed_off",
                           before["responses_handed_off"] + 1)
            assert mid["responses_handed_off"] - before["responses_handed_off"] == 1
            sock.sendall(_frame(8, 0, 1, struct.pack(">I", 1 << 20)))
            for ftype, flags, sid, payload in _h2_frames(
                    sock, time.monotonic() + 5.0):
                if sid == 1 and ftype == 0:
                    data += payload
                if sid == 1 and ftype == 1 and flags & 0x1:
                    trailers = True
                    break
            assert trailers
            assert [r.remaining for r in _resp(data).responses] == [99] * 200
        finally:
            sock.close()
    finally:
        front.close()


@pytest.mark.parametrize("read_first", [False, True],
                         ids=["reset-at-once", "reset-after-a-read"])
def test_connections_reset_around_the_hand_off(daemon, read_first):
    """Peers that send three RPCs and reset (SO_LINGER 0) at once, or
    after the first DATA frame of an answer, while their responses are queued
    and handed to their reactors: whichever comes first, the death or
    the reactor's flush, a response to a dead connection is written and
    counted nowhere — `errors` does not move, each response counted is
    one its reactor wrote (`rpcs` = `responses_handed_off` =
    `feeder_front_rpcs`, all three settled at the writev) — every
    connection and its socket are released, and the reactors go on
    serving."""
    psutil = pytest.importorskip("psutil")
    front = H2FastFront(daemon.instance, window_s=0.001, native_ledger=False,
                        event_front=True, reactors=2)

    def front_sockets():
        return [c for c in psutil.Process().net_connections(kind="tcp")
                if c.raddr and c.laddr.port == front.port]

    tag = "rst" + str(int(read_first))
    n_conns = 60
    try:
        before = front.stats()
        for c in range(n_conns):
            sock = _open_streams(front.port, {
                sid: _req(tag, f"c{c}s{sid}_") for sid in (1, 3, 5)})
            if read_first:  # the first DATA frame of an answer, then reset
                next(f for f in _h2_frames(sock, time.monotonic() + 5.0)
                     if f[0] == 0)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            sock.close()
        deadline = time.monotonic() + 10.0
        while ((front.conn_stats()["conns_open"] or front_sockets())
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert front.conn_stats()["conns_open"] == 0
        assert front_sockets() == []
        after = front.stats()
        d = {k: after[k] - before[k] for k in
             ("rpcs", "errors", "responses_handed_off", "feeder_front_rpcs")}
        assert d["errors"] == 0, d
        assert d["rpcs"] == d["responses_handed_off"] == d["feeder_front_rpcs"], d
        assert d["rpcs"] <= 3 * n_conns, d
        if read_first:
            assert d["rpcs"] > 0, d  # every peer read an answer first
        stub = V1Stub(dial(f"127.0.0.1:{front.port}"))
        got = stub.GetRateLimits(_req(tag, "after"), timeout=10)
        assert got.responses[0].remaining == 99
    finally:
        front.close()


def test_the_reactors_own_responses_write_through():
    """A response the reactor produces itself — the native decision
    plane answering a delegated hot key inside the reactor — is written
    by that reactor at once: `responses_handed_off` counts the window
    path's responses and none of the plane's."""
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
        ledger_lease_ttl=30.0,
    )
    d = spawn_daemon(conf)
    try:
        assert d.h2_fast.plane is not None
        stub = V1Stub(dial(d.h2_fast_address))
        for _ in range(40):
            stub.GetRateLimits(_req("wt", "hot", limit=10**6), timeout=10)
        st = _settled(d.h2_fast, "rpcs", 40)
        assert st["rpcs"] == 40 and st["native_rpcs"] > 0
        assert st["responses_handed_off"] == st["rpcs"] - st["native_rpcs"] > 0
    finally:
        d.close()
