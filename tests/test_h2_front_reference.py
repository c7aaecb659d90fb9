"""The native front against the plain reference (models/spec.py) and
against the grpc listener: same payloads, same answers.

What configuration `node100m_ledger0_h2front` states, at a small table
on the CPU: the front changes who carries a request to the engine, not
what the answer is; RPCs that share a group-commit window are answered
each with its own rows, a key's duplicates across window-mates in
arrival order, through one Python entry and one dispatch; an RPC out of
the columnar path's scope is refused UNIMPLEMENTED alone; and the
front's events and counters agree with each other.
"""

import json
import os
import sys
import time
import urllib.request

import grpc
import pytest

from gubernator_tpu.clock import Clock
from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.models import spec
from gubernator_tpu.net import h2_fast
from gubernator_tpu.net.grpc_service import V1Stub, dial
from gubernator_tpu.net.pb import gubernator_pb2 as pb
from gubernator_tpu.types import Behavior

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import client, judge, traffic, wire  # noqa: E402

pytestmark = pytest.mark.skipif(
    h2_fast.load() is None, reason="native h2 server unavailable"
)

with open(os.path.join(BENCH, "mixes", "herd100.json")) as f:
    HERD = json.load(f)
with open(os.path.join(BENCH, "mixes", "batch1000_zipf.json")) as f:
    BATCH = json.load(f)
TABLE = traffic.LimitTable(HERD)
# every (algorithm, limit, duration) the two mixes send: the single
# limit of the `uni` callers, then one name's worth of the mixed ones
CASES = [TABLE.configs[0]] + TABLE.configs[1:1 + 2 * 3 * 3]


def conf(**kw) -> DaemonConfig:
    return DaemonConfig(**{
        "grpc_listen_address": "127.0.0.1:0", "http_listen_address": "127.0.0.1:0",
        "cache_size": 1 << 13, "peer_discovery_type": "none", "device_count": 1,
        "sweep_interval": 0.0, "ledger": False, **kw,
    })


@pytest.fixture(scope="module")
def frozen():
    """One frozen clock under two daemons: the front's and a plain
    one, so the same payloads meet the same empty buckets."""
    clock = Clock().freeze()
    front = spawn_daemon(conf(h2_fast_address="127.0.0.1:0"), clock=clock)
    plain = spawn_daemon(conf(), clock=clock)
    yield clock, V1Stub(dial(front.h2_fast_address)), V1Stub(dial(plain.grpc_address))
    front.close()
    plain.close()


@pytest.fixture(scope="module")
def live():
    """A daemon on the live clock, dialed through its front."""
    d = spawn_daemon(conf(h2_fast_address="127.0.0.1:0"))
    yield d
    d.close()


@pytest.fixture()
def wide_front(live):
    """A second front on the same instance whose window stays open
    0.4 s: what is sent together shares one window for certain."""
    front = h2_fast.H2FastFront(
        live.instance, port=0, window_s=0.4, native_ledger=False)
    yield front
    front.close()


def rows_of(resp):
    return [(r.status, r.limit, r.remaining, r.reset_time, r.error)
            for r in resp.responses]


@pytest.mark.parametrize(
    "case", CASES,
    ids=[f"{c.name}-{'leaky' if c.algorithm else 'token'}-{c.limit}-{c.duration}"
         for c in CASES])
def test_front_equals_grpc_listener_equals_spec(frozen, case):
    """One caller, a frozen clock: through the front, through the grpc
    listener and from models/spec.py, the same rows — under the limit,
    over it, after a drip and after expiry."""
    clock, front, plain = frozen
    key = f"ref-{case.algorithm}-{case.limit}-{case.duration}"
    item = pb.RateLimitReq(
        name=case.name, unique_key=key, hits=case.hits, limit=case.limit,
        duration=case.duration, algorithm=case.algorithm, burst=case.burst)
    inp = spec.SpecInput(
        hits=case.hits, limit=case.limit, duration=case.duration,
        burst=case.burst, algorithm=case.algorithm)
    state = None
    # (items in the RPC — duplicates of the key, applied in item order;
    #  ms the clock moves afterwards)
    schedule = [
        (case.limit // 2, case.duration // 3),
        (min(1000, case.limit // 2 + 3), case.duration // 2),
        (3, case.duration + 1),
        (2, 7),
        (1, 0),
    ]
    for n_items, advance_ms in schedule:
        req = pb.GetRateLimitsReq(requests=[item] * n_items)
        now = clock.now_ms()
        want = []
        for _ in range(n_items):
            state, out = spec.apply_spec(state, inp, now)
            want.append((out.status, out.limit, out.remaining, out.reset_time, ""))
        assert rows_of(front.GetRateLimits(req)) == want
        assert rows_of(plain.GetRateLimits(req)) == want
        clock.advance(ms=advance_ms)
    assert {w[0] for w in want} <= {0, 1}


def test_concurrent_herd_is_placed_by_the_judge(live):
    """Twenty callers of single-item RPCs on 300 shared ids through the
    front, judged by the benchmark's own search: every key's answers
    are one sequential history of the reference."""
    mix = dict(HERD, callers=20, keys=dict(HERD["keys"], ids=300))
    seed, n_rpcs = 3200000077, 60
    callers = [
        client.Caller(c, traffic.build_pool(mix, seed, c, n_rpcs, TABLE),
                      live.h2_fast_address)
        for c in range(mix["callers"])
    ]
    client.run_threads(callers, lambda c: c.warm(n_rpcs))
    for c in callers:
        c.channel.close()
    handed = judge.collect(
        wire.decode_response, {c.index: c.pool for c in callers},
        {c.index: c.records for c in callers}, 1, seed, 1.0,
        judge.hot_ids(300))
    assert handed["counts"]["unanswered_rpcs"] == 0
    assert handed["counts"]["failed_items"] == 0
    judged = judge.judge_answers(TABLE, judge.merge_columns([handed]))
    assert judged["mismatched"] == 0, judged["first_mismatches"]
    assert judged["checked"] == 20 * n_rpcs and judged["shared_keys"] > 10


def native_event_counts(daemon, settled) -> tuple:
    """/debug/vars once the collector has drained what `settled` wants."""
    deadline = time.monotonic() + 5.0
    while True:
        with urllib.request.urlopen(
                f"http://{daemon.http_address}/debug/vars", timeout=10) as r:
            doc = json.loads(r.read())
        if settled(doc) or time.monotonic() > deadline:
            return doc["h2_front"], doc["native_events"]["events"]
        time.sleep(0.05)


@pytest.mark.parametrize("ledger", [False, True], ids=["ledger0", "ledger1"])
def test_overlapped_windows_answer_as_the_reference(ledger, monkeypatch):
    """Eight callers of 100-item RPCs on 300 shared ids, judged by the
    benchmark's own search; a ring window holds two such RPCs, so rows
    are waiting whenever a submit ends.  The engine hands the front its
    batches still on the device, so the serve thread launches window
    k+1 before it reads window k back — and every key's answers are
    still one sequential history of the reference.

    With the ledger on, the serve hands back finished columns: the same
    loop, under the same callers, overlaps nothing.  Its answers under
    cross-caller contention are by design not the sequential
    reference's (leases; PERF.md §7.1), so there the callers are judged
    on the half they send one after another, and the concurrent half is
    held to every RPC answered, none refused."""
    monkeypatch.setenv("GUBER_FEEDER_RING_ROWS", "256")
    mix = dict(BATCH, items_per_rpc=100, keys=dict(BATCH["keys"], ids=300))
    seed, n_rpcs = 3300000011, 40
    d = spawn_daemon(conf(h2_fast_address="127.0.0.1:0", ledger=ledger))

    def judged_so_far(callers):
        handed = judge.collect(
            wire.decode_response, {c.index: c.pool for c in callers},
            {c.index: c.records for c in callers}, 100, seed, 1.0,
            judge.hot_ids(300))
        assert handed["counts"]["unanswered_rpcs"] == 0
        assert handed["counts"]["failed_items"] == 0
        return judge.judge_answers(TABLE, judge.merge_columns([handed]))

    try:
        callers = [
            client.Caller(c, traffic.build_pool(mix, seed, c, n_rpcs, TABLE),
                          d.h2_fast_address)
            for c in range(mix["callers"])
        ]
        if ledger:
            for c in callers:
                c.warm(n_rpcs // 2)
            judged = judged_so_far(callers)
            client.run_threads(callers, lambda c: c.warm(n_rpcs // 2))
            judged_so_far(callers)
        else:
            client.run_threads(callers, lambda c: c.warm(n_rpcs))
            judged = judged_so_far(callers)
        for c in callers:
            c.channel.close()
        assert judged["mismatched"] == 0, judged["first_mismatches"]
        assert judged["checked"] == 8 * n_rpcs * (50 if ledger else 100)
        assert judged["shared_keys"] > 100
        stats = d.h2_fast.stats()
        front, counts = native_event_counts(
            d, lambda doc: doc["native_events"]["events"]["feeder_scatter"]
            == stats["feeder_windows"] and doc["h2_front"]["rpcs"] == 8 * n_rpcs)
        assert front["rpcs"] == 8 * n_rpcs and front["errors"] == 0
        assert counts["feeder_serve"] == counts["feeder_scatter"] == stats["feeder_windows"] > 0
        if ledger:
            assert front["windows_overlapped"] == 0 and counts["feeder_inflight"] == 0
        else:
            assert 0 < front["windows_overlapped"] < stats["feeder_windows"]
            # every window was in flight once, from its submit to its complete
            assert counts["feeder_inflight"] == stats["feeder_windows"]
    finally:
        d.close()


def counted(front, answered: int) -> dict:
    """The front's counters once `rpcs` + `errors` hold `answered`: it
    counts an RPC after it has handed the response to the socket, so a
    caller can read its answer a moment before the counter moves."""
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        doc = front.debug_vars()
        if doc["rpcs"] + doc["errors"] >= answered:
            break
        time.sleep(0.005)
    return front.debug_vars()


def test_window_mates_get_their_own_rows_in_arrival_order(live, wide_front):
    """Eight RPCs on one connection inside one window: each gets the
    rows of its own items, the key they all hit counts down in the
    order they arrived, and the window was one Python entry and one
    dispatch."""
    n, shared_limit = 8, 50
    call = V1Stub(dial(wide_front.address)).GetRateLimits
    call(pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
        name="mates", unique_key="warm", hits=1, limit=5, duration=60_000)]))
    engine = live.instance.engine
    before = counted(wide_front, 1)
    dispatches = engine.dispatches_total
    futures = [
        call.future(pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name="mates", unique_key=f"own{i}", hits=1,
                            limit=100 + i, duration=60_000),
            pb.RateLimitReq(name="mates", unique_key="shared", hits=1,
                            limit=shared_limit, duration=60_000),
            pb.RateLimitReq(name="mates", unique_key=f"own{i}", hits=1,
                            limit=100 + i, duration=60_000),
        ]))
        for i in range(n)
    ]
    answers = [f.result(timeout=10) for f in futures]
    after = counted(wide_front, before["rpcs"] + before["errors"] + n)
    assert after["windows"] - before["windows"] == 1
    assert after["rpcs"] - before["rpcs"] == n
    assert after["items"] - before["items"] == 3 * n
    assert after["feeder_rpcs"] - before["feeder_rpcs"] == n
    assert engine.dispatches_total - dispatches == 1
    for i, resp in enumerate(answers):
        own_first, shared, own_second = resp.responses
        assert (own_first.limit, own_first.remaining) == (100 + i, 99 + i)
        assert (own_second.limit, own_second.remaining) == (100 + i, 98 + i)
        # one connection: streams arrive in the order they were opened
        assert (shared.limit, shared.remaining) == (shared_limit, shared_limit - 1 - i)
        assert not any(r.error for r in resp.responses)


def test_window_wider_than_the_warm_up_is_chunked_not_compiled(live, wide_front):
    """Five 1,000-item RPCs in one window are 5,000 rows: the warm-up
    holds the engine to the widths it compiled (4,096 lanes wherever a
    merging window is on), so the window is two dispatches and no
    compile — at 100 M rows on the chip the 8,192-lane
    program, compiled on demand, cost a client its deadline (PR 32)."""
    from gubernator_tpu.core import device_info

    engine = live.instance.engine
    assert engine.max_kernel_width == 4096
    call = V1Stub(dial(wide_front.address)).GetRateLimits
    compiles = device_info.describe(engine)["compiles"]["backend_compiles"]
    before, dispatches = wide_front.debug_vars(), engine.dispatches_total
    futures = [
        call.future(pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name="wide", unique_key=f"{r}-{i}", hits=1,
                            limit=7 + r, duration=60_000)
            for i in range(1000)]))
        for r in range(5)
    ]
    for r, f in enumerate(futures):
        rows = f.result(timeout=30).responses
        assert len(rows) == 1000
        assert {(x.status, x.limit, x.remaining) for x in rows} == {(0, 7 + r, 6 + r)}
    after = wide_front.debug_vars()
    assert after["windows"] - before["windows"] == 1
    assert after["items"] - before["items"] == 5000
    assert engine.dispatches_total - dispatches == 2
    assert device_info.describe(engine)["compiles"]["backend_compiles"] == compiles


def test_two_chunks_of_one_width_read_back_through_a_warm_stack():
    """A window of two same-width chunks reads both answers back in
    one stacked transfer; the stack program is the one the warm-up
    compiled.  (Its cache key held `str(dtype)`: `jnp.int32` at
    warm-up, `dtype('int32')` in serving — every first stack of a
    shape was a compile request, one or two inside each measured
    window of the batch cell on the chip, PR 32.)"""
    import numpy as np

    from gubernator_tpu.core.engine import DecisionEngine
    from gubernator_tpu.utils import jit_guard

    jit_guard.install()
    engine = DecisionEngine(capacity=4096)
    engine.warmup(max_width=128)
    assert engine.max_kernel_width == 128  # the constructor's 8,192 until then
    compiles, stacked, n = jit_guard.compile_count(), engine.readback.stacked, 256
    keys = [b"stack_k%d" % (i % 200) for i in range(n)]  # duplicates: the collapsed path
    status, limit, remaining, _ = engine.apply_columnar(
        keys, np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, np.int64),
        np.full(n, 10, np.int64), np.full(n, 60_000, np.int64), np.zeros(n, np.int64))
    assert engine.readback.stacked - stacked == 2
    assert jit_guard.compile_count() == compiles
    assert (status == 0).all() and (limit == 10).all()
    assert sorted(remaining[:200].tolist()) == [9] * 200 and (remaining[200:] == 8).all()
    engine.close()


@pytest.mark.parametrize("windows,width", [
    ({}, 1024), ({"h2_fast_address": "127.0.0.1:0"}, 4096),
    ({"global_serve_window": 0.0005}, 4096), ({"local_batch_wait": 0.0005}, 4096),
], ids=["none", "front", "global", "local"])
def test_any_merging_window_warms_the_wide_ladder(windows, width):
    """The front's window merges RPCs whatever the GLOBAL serve window
    is set to: its address alone asks the warm-up for 4,096 lanes."""
    from types import SimpleNamespace

    from gubernator_tpu.daemon import Daemon

    conf = SimpleNamespace(**{"global_serve_window": 0.0, "local_batch_wait": 0.0,
                              "h2_fast_address": "", **windows})
    asked = []
    Daemon._warmup(SimpleNamespace(conf=conf),
                   SimpleNamespace(warmup=lambda max_width: asked.append(max_width)))
    assert asked == [width]


@pytest.mark.parametrize("behavior", [
    Behavior.GLOBAL, Behavior.DURATION_IS_GREGORIAN], ids=["global", "gregorian"])
def test_out_of_scope_rpc_is_refused_alone(live, wide_front, behavior):
    """An RPC carrying one item the columnar path does not serve is
    refused UNIMPLEMENTED; the RPCs that share its window are
    answered."""
    call = V1Stub(dial(wide_front.address)).GetRateLimits

    def req(key, flags=0):
        return pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name="scope", unique_key=f"{key}-{int(behavior)}",
                            hits=1, limit=9, duration=60_000),
            pb.RateLimitReq(name="scope", unique_key=f"{key}b-{int(behavior)}",
                            hits=1, limit=9, duration=3, behavior=flags),
        ])

    before = wide_front.debug_vars()
    futures = [call.future(req("a")), call.future(req("x", int(behavior))),
               call.future(req("c"))]
    with pytest.raises(grpc.RpcError) as err:
        futures[1].result(timeout=10)
    assert err.value.code() == grpc.StatusCode.UNIMPLEMENTED
    for f in (futures[0], futures[2]):
        assert [(r.status, r.remaining) for r in f.result(timeout=10).responses] == [
            (0, 8), (0, 8)]
    after = counted(wide_front, before["rpcs"] + before["errors"] + 3)
    assert after["declined_rpcs"] - before["declined_rpcs"] == 1
    assert after["errors"] - before["errors"] == 1
    assert after["rpcs"] - before["rpcs"] == 2


def test_events_and_counters_agree(live):
    """One `rpc_total` event an RPC handed to a socket, one
    `feeder_scatter` and one `feeder_serve` a feeder window, sums kept
    exactly, nothing dropped — and /debug/vars says which listener
    serves and with what."""
    stub = V1Stub(dial(live.h2_fast_address))
    for i in range(40):
        stub.GetRateLimits(pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
            name="ev", unique_key=f"k{i % 7}", hits=1, limit=1000, duration=60_000)]))
    with pytest.raises(grpc.RpcError):
        stub.GetRateLimits(pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
            name="ev", unique_key="g", hits=1, limit=5, duration=60_000,
            behavior=int(Behavior.GLOBAL))]))
    events = live.instance.native_events
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        with urllib.request.urlopen(
                f"http://{live.http_address}/debug/vars", timeout=10) as r:
            doc = json.loads(r.read())
        front, counts = doc["h2_front"], doc["native_events"]["events"]
        if counts["rpc_total"] == front["rpcs"] + front["errors"]:
            break
        time.sleep(0.05)
    assert front["rpcs"] >= 40 and front["errors"] >= 1
    assert counts["rpc_total"] == front["rpcs"] + front["errors"]
    stats = live.h2_fast.stats()
    assert counts["feeder_scatter"] == stats["feeder_windows"] >= 1
    assert counts["feeder_serve"] == stats["feeder_windows"]
    assert counts["feeder_pack"] == stats["feeder_rpcs"] == front["feeder_rpcs"]
    assert front["ring_dropped"] == 0 and doc["native_events"]["ring"]["dropped"] == 0
    assert front["declined_rpcs"] >= 1
    assert front["windows"] == stats["windows"] + stats["feeder_windows"]
    hist = events.histograms()["rpc_total"]
    # the exact sum, not an octave's midpoints: no RPC took under 10 us,
    # and the maximum is a duration some RPC really had
    assert hist.total >= hist.count * 10e-6 and hist.max <= hist.total
    assert front["settings"] == {
        "address": live.h2_fast_address, "window_ms": 2.0, "flush_items": 4096,
        "event_front": True, "reactors": live.h2_fast.reactors,
        "lanes": live.h2_fast.lanes, "feeder": True, "decision_plane": False,
        "retry_hints": True, "event_ring": True,
    }
