"""The stage primitive (utils/metrics.stage) and its sites on the served
wire route, in both engines: histogram, request span and profiler
annotation under one name (OBSERVABILITY.md §3, PERF.md §3)."""

import glob
import json
import os
import threading
import urllib.error
import urllib.request

import grpc
import pytest

from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.net import wire_codec
from gubernator_tpu.net.pb import gubernator_pb2 as pb
from gubernator_tpu.utils import tracing
from gubernator_tpu.utils.metrics import ENGINE_STAGES, DurationStat, stage

METHOD = "/pb.gubernator.V1/GetRateLimits"
WAITS = {"listener.queue_wait", "engine.lock_wait", "device.readback"}
# The served wire route, as OBSERVABILITY.md §3 tables it; engine.sweep
# is the sweeper's, not an RPC's, and has its own test.
ROUTE = [
    "listener.queue_wait", "wire.decode", "service.hotkeys",
    "engine.lock_wait",
    "engine.lock_hold", "engine.intern", "engine.pack", "device.h2d",
    "device.launch", "device.readback", "engine.unpack", "wire.encode",
]
# The leaf stages under the engine lock.
LEAVES = ["engine.intern", "engine.pack", "device.h2d", "device.launch",
          "engine.set_expiry", "mesh.route"]
ENGINES = {"DecisionEngine": 1, "ShardedDecisionEngine": 4}


def payload(route: str, salt: int) -> bytes:
    """One RPC of the route: `single` is one item; `batch` is 1,000
    items, a hot key among them 40 times (so it collapses)."""
    if route == "single":
        keys = [f"one-{salt}"]
    else:
        keys = [f"k{salt}-{i}" for i in range(960)] + [f"hot-{salt}"] * 40
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name="stages", unique_key=k, hits=1, limit=1000,
                        duration=60_000)
        for k in keys
    ]).SerializeToString()


@pytest.fixture(scope="module", params=sorted(ENGINES))
def daemon(request):
    if wire_codec.load() is None:
        pytest.skip("native codec unavailable")
    d = spawn_daemon(DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        # room for every key the tiling test sends: an eviction's
        # clears are not the steady state the stages are laid over
        cache_size=400_000,
        peer_discovery_type="none",
        device_count=ENGINES[request.param],
        sweep_interval=0.0,
        ledger=False,  # every decision reaches the engine
    ))
    assert type(d.instance.engine).__name__ == request.param
    channel = grpc.insecure_channel(d.grpc_address)
    d.call = channel.unary_unary(METHOD)
    yield d
    channel.close()
    d.close()


def counts(d) -> dict:
    return {name: s.count for name, s in d.instance.stage_timers.items()}


def totals(d) -> dict:
    return {name: s.total for name, s in d.instance.stage_timers.items()}


# -- the primitive -----------------------------------------------------


def test_stage_observes_with_tracer_and_profiler_off():
    assert not tracing.active()
    stat = DurationStat()
    with stage("engine.pack", stat) as st:
        assert st.span is None
    waited = stage("engine.lock_wait", stat, work=False).start()
    waited.stop()
    assert stat.count == 2 and stat.total >= 0.0


def test_stage_observes_when_the_body_raises():
    stat = DurationStat()
    with pytest.raises(KeyError):
        with stage("engine.pack", stat):
            raise KeyError("boom")
    assert stat.count == 1


def test_stage_is_a_child_span_while_tracing():
    tracer = tracing.InMemoryTracer()
    tracing.set_tracer(tracer)
    try:
        stat = DurationStat()
        with tracing.span("rpc.get_rate_limits") as root:
            with stage("engine.intern", stat) as st:
                st.span.set_attribute("keys", 3)
            with stage("engine.lock_wait", stat, work=False):
                pass
    finally:
        tracing.set_tracer(None)
    intern, = tracer.spans("engine.intern")
    wait, = tracer.spans("engine.lock_wait")
    assert intern.parent_span_id == wait.parent_span_id == root.span_id
    assert intern.trace_id == root.trace_id
    assert intern.attributes == {"keys": 3} and stat.count == 2


def test_every_engine_stage_is_registered(daemon):
    timers = daemon.instance.stage_timers
    assert set(ENGINE_STAGES) <= set(timers)
    assert {"wire.decode", "wire.encode", "listener.queue_wait",
            "device.step", "device.readback"} <= set(timers)
    assert ("mesh.route" in timers) == hasattr(daemon.instance.engine, "tables")
    body = urllib.request.urlopen(
        f"http://{daemon.http_address}/metrics", timeout=10
    ).read().decode()
    for name in ROUTE:
        assert f'gubernator_stage_duration_count{{stage="{name}"}}' in body
    assert "gubernator_engine_round_duration" not in body


# -- the sites, through a real daemon ----------------------------------


@pytest.mark.parametrize("route", ["batch", "single"])
def test_route_leaves_a_count_in_every_stage(daemon, route):
    sharded = hasattr(daemon.instance.engine, "tables")
    before = counts(daemon)
    raw = daemon.call(payload(route, salt=1), timeout=30)
    answers = pb.GetRateLimitsResp.FromString(raw).responses
    assert len(answers) == (1 if route == "single" else 1000)
    assert not any(a.error for a in answers)
    after = counts(daemon)
    # On the mesh the router is a stage of its own and the TTL mirror's
    # writes ride the intern's one FFI call.
    moved = {"mesh.route"} if sharded else {"engine.set_expiry"}
    for name in set(ROUTE) | moved:
        assert after[name] - before[name] >= 1, (name, route)
    # per RPC where the table says so
    for name in {"wire.decode", "service.hotkeys", "wire.encode",
                 "engine.lock_wait", "engine.lock_hold", "engine.intern",
                 "engine.unpack"} | moved:
        assert after[name] - before[name] == 1, (name, route)


@pytest.mark.parametrize("path", ["rounds", "collapsed"])
@pytest.mark.parametrize("engine_name", sorted(ENGINES))
def test_pack_slices_interleave_with_the_dispatches(engine_name, path):
    """An RPC wider than the kernel dispatches chunk k before it packs
    chunk k+1 — the device runs while the host packs — so engine.pack
    is observed slice by slice, and never around a dispatch."""
    import jax
    import numpy as np

    from gubernator_tpu.core.engine import DecisionEngine
    from gubernator_tpu.parallel.mesh import make_mesh
    from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine

    if engine_name == "DecisionEngine":
        engine = DecisionEngine(capacity=4096, max_kernel_width=64)
    else:
        engine = ShardedDecisionEngine(
            shard_capacity=1024, mesh=make_mesh(jax.devices()[:4]),
            max_kernel_width=64,
        )
    keys = [b"stages_k%d" % i for i in range(600)]
    if path == "collapsed":
        keys += [b"stages_hot"] * 40
    n = len(keys)
    cols = dict(
        algo=np.zeros(n, np.int32), behavior=np.zeros(n, np.int32),
        hits=np.ones(n, np.int64), limit=np.full(n, 1000, np.int64),
        duration=np.full(n, 60_000, np.int64), burst=np.zeros(n, np.int64),
    )
    tracer = tracing.InMemoryTracer()
    tracing.set_tracer(tracer)
    try:
        with tracing.span("rpc.get_rate_limits"):
            status, _limit, remaining, _reset = engine.apply_columnar(
                keys, **cols)[:4]
    finally:
        tracing.set_tracer(None)
    assert (np.asarray(status) == 0).all()
    assert int(np.min(remaining)) == (960 if path == "collapsed" else 999)
    order = [s.name for s in tracer.spans()
             if s.name in ("engine.pack", "device.launch")]
    launches = order.count("device.launch")
    assert launches >= 3, order
    assert order[0] == "engine.pack" and order[-1] == "device.launch"
    assert engine.stages["engine.pack"].count >= launches
    if path == "rounds" and getattr(engine, "_pump", None) is not None:
        return  # the pump queues the rounds and fuses them at its flush
    # a pack slice between every two dispatches
    assert "device.launch device.launch" not in " ".join(order), order


def test_sweep_is_a_stage(daemon):
    before = counts(daemon)["engine.sweep"]
    daemon.instance.engine.sweep()
    assert counts(daemon)["engine.sweep"] == before + 1


def test_leaf_stages_tile_the_lock_hold(daemon):
    """No statement under the engine lock outside a stage but control
    flow: over 200 RPCs the leaves sum to >= 90 % of engine.lock_hold."""
    daemon.call(payload("batch", salt=2), timeout=30)  # shapes warm
    before = totals(daemon)
    for i in range(200):
        daemon.call(payload("batch", salt=100 + i), timeout=30)
    after = totals(daemon)
    held = after["engine.lock_hold"] - before["engine.lock_hold"]
    leaves = sum(
        after[name] - before[name] for name in LEAVES if name in after
    )
    assert held > 0
    assert leaves >= 0.9 * held, (leaves, held)


def test_rpc_tree_holds_the_stages(daemon):
    tracer = tracing.InMemoryTracer()
    tracing.set_tracer(tracer)
    try:
        daemon.call(payload("batch", salt=3), timeout=30)
    finally:
        tracing.set_tracer(None)
    root, = tracer.spans("rpc.get_rate_limits")
    tree = {s.name: s for s in tracer.trace(root.trace_id)}
    sharded = hasattr(daemon.instance.engine, "tables")
    for name in ("wire.decode", "service.hotkeys", "engine.lock_wait",
                 "engine.intern", "engine.pack", "device.h2d", "device.launch",
                 "mesh.route" if sharded else "engine.set_expiry",
                 "device.readback", "engine.unpack", "wire.encode"):
        assert name in tree, name
    # engine.lock_hold is a histogram only: the leaves' parent here is
    # the coarse engine.columnar span, which stayed
    assert "engine.lock_hold" not in tree
    assert tree["engine.pack"].parent_span_id == tree["engine.columnar"].span_id


# -- the profiler's timeline -------------------------------------------


def host_events(xplane: str) -> dict:
    """{thread line: [(name, start_ns, end_ns), ...]} of the host planes."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.setdefault((plane.name, line.name), []).extend(
                (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                for ev in line.events
            )
    return out


def test_debug_profile_captures_work_and_never_a_wait(daemon):
    """GET /debug/profile: 200 and an .xplane.pb under the path it
    names, 409 to a second concurrent call; the capture holds the work
    stages' annotations, none for a wait, none nested."""
    url = f"http://{daemon.http_address}/debug/profile?seconds=1.5"
    first = {}

    def capture():
        with urllib.request.urlopen(url, timeout=60) as r:
            first["code"], first["body"] = r.status, json.loads(r.read())

    t = threading.Thread(target=capture)
    t.start()
    second = None
    for i in range(40):  # traffic while it runs; one 409 along the way
        daemon.call(payload("batch" if i % 2 else "single", 500 + i), timeout=30)
        if second is None and i >= 2:
            try:
                urllib.request.urlopen(url, timeout=60).close()
            except urllib.error.HTTPError as e:
                second = e.code
    t.join(timeout=90)
    assert not t.is_alive()
    assert first["code"] == 200 and second == 409
    body = first["body"]
    assert body["device"]["start"]["engine"] == type(daemon.instance.engine).__name__
    assert (body["device"]["stop"]["counters"]["requests_total"]
            > body["device"]["start"]["counters"]["requests_total"])
    found = glob.glob(os.path.join(
        body["path"], "plugins", "profile", "*", "*.xplane.pb"))
    assert len(found) == 1

    sharded = hasattr(daemon.instance.engine, "tables")
    stages = set(daemon.instance.stage_timers)
    threads = host_events(found[0])
    seen = {name for evs in threads.values() for name, _s, _e in evs}
    work = {"wire.decode", "service.hotkeys", "engine.intern", "engine.pack",
            "device.h2d", "device.launch", "engine.unpack", "wire.encode",
            "mesh.route" if sharded else "engine.set_expiry"}
    assert work <= seen, work - seen
    assert not seen & (WAITS | {"engine.lock_hold", "device.step"})
    for evs in threads.values():
        ours = sorted((s, e, n) for n, s, e in evs if n in stages)
        for (s0, e0, n0), (s1, _e1, n1) in zip(ours, ours[1:]):
            assert e0 <= s1, f"{n1} starts inside {n0}"


def test_debug_profile_leaves_one_directory_behind(daemon):
    paths = []
    for _ in range(2):
        with urllib.request.urlopen(
            f"http://{daemon.http_address}/debug/profile?seconds=0.2",
            timeout=60,
        ) as r:
            paths.append(json.loads(r.read())["path"])
    assert not os.path.exists(paths[0]) and os.path.isdir(paths[1])


def test_debug_profile_refuses_a_bad_length(daemon):
    for seconds in ("0", "31", "x"):
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://{daemon.http_address}/debug/profile?seconds={seconds}",
                timeout=10,
            )
        assert e.value.code == 400
