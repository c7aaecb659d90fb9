"""The per-layer metrics that set CPU beside a wall: a stage's thread
CPU, the front serve thread's CPU and its wait for a window, the
daemon's CPU by thread role.  Each one's reader, called as
`benchmarks/run.py` calls it (`read_metric` on two scrapes of a live
daemon around served RPCs), has to find a number in every family of
cells its `BENCHMARK.json` entry lists — the grpc node, the node behind
its native front, the mesh — since a traced run whose last line lacks a
listed metric is refused.  On a program without these sinks (the parent
of the change that added them) each reader finds nothing and does not
raise."""

import json
import os
import threading
import time
import urllib.request

import grpc
import pytest

from conftest import ROOT
from lib.daemon_child import parse_prom
from lib.manifest import Manifest, read_metric

from gubernator_tpu.config import DaemonConfig
from gubernator_tpu.daemon import spawn_daemon
from gubernator_tpu.net import h2_fast, wire_codec
from gubernator_tpu.net.pb import gubernator_pb2 as pb
from gubernator_tpu.utils import metrics
from gubernator_tpu.utils.metrics import THREAD_ROLES

METHOD = "/pb.gubernator.V1/GetRateLimits"
NEW = (
    "host.lock_hold_cpu_us", "host.pack_cpu_us", "host.hotkeys_cpu_us",
    "pump.launch_cpu_us", "front.window_wait_us", "front.serve_cpu_ms",
    "front.reactors_cpu_ms_per_window", "host.daemon_cpu_cores",
)
ACCEPTED_READERS = {"prom_delta", "vars_delta", "ratio", "run_value"}

pytestmark = pytest.mark.skipif(
    wire_codec.load() is None or h2_fast.load() is None,
    reason="native codec or h2 front unavailable",
)


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def family(manifest, cell: str) -> str:
    """The daemon a cell's configuration starts: the mesh (four chips),
    the node behind its native front, or the grpc node."""
    conf = manifest.config(manifest.cell(cell)["config"])
    if conf["chips"] == 4:
        return "mesh"
    if conf.get("launcher", "").endswith("launch_h2front.py"):
        return "front"
    return "grpc"


def payload(salt: int) -> bytes:
    """CPU_MIN_ITEMS items — the smallest RPC whose stages read their
    thread's CPU clock, as the cells' 1,000-item RPCs do — a hot key
    among them 40 times (so it collapses)."""
    keys = [f"c{salt}-{i}" for i in range(metrics.CPU_MIN_ITEMS - 40)]
    keys += [f"hot{salt}"] * 40
    return pb.GetRateLimitsReq(requests=[
        pb.RateLimitReq(name="cpu", unique_key=k, hits=1, limit=1000,
                        duration=60_000)
        for k in keys
    ]).SerializeToString()


def scrape(d) -> dict:
    base = f"http://{d.http_address}"
    with urllib.request.urlopen(f"{base}/debug/vars", timeout=30) as r:
        doc = json.loads(r.read())
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        prom = parse_prom(r.read().decode())
    return {"vars": doc, "prom": prom}


def window(d, address: str, callers: int = 4, rpcs: int = 3) -> dict:
    """Two scrapes around `callers` threads sending `rpcs` RPCs each to
    `address`: the ctx run.py hands the readers."""
    before = scrape(d)
    t0 = time.monotonic()
    errors = []

    def caller(n):
        with grpc.insecure_channel(address) as ch:
            call = ch.unary_unary(METHOD)
            for i in range(rpcs):
                try:
                    resp = pb.GetRateLimitsResp.FromString(
                        call(payload(1000 * n + i), timeout=60))
                    assert not any(a.error for a in resp.responses)
                except Exception as e:  # noqa: BLE001 — reported below
                    errors.append(e)

    threads = [threading.Thread(target=caller, args=(n,)) for n in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    # the event ring drains every 50 ms: its histograms catch up
    time.sleep(0.3)
    seconds = time.monotonic() - t0
    after = scrape(d)
    return {
        "prom_before": before["prom"], "prom_after": after["prom"],
        "vars_before": before["vars"], "vars_after": after["vars"],
        "run": {"window_s": seconds},
    }


def spawn(**over):
    return spawn_daemon(DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=20_000,
        peer_discovery_type="none",
        sweep_interval=0.0,
        ledger=False,  # every decision reaches the engine
        **over,
    ))


@pytest.fixture(scope="module")
def front_daemon():
    """The node with its native front open: grpc-python serves beside
    it on its own port, so one daemon reads as both families."""
    d = spawn(device_count=1, h2_fast_address="127.0.0.1:0")
    yield d
    d.close()


@pytest.fixture(scope="module")
def windows(front_daemon):
    """One ctx a family: grpc RPCs to the grpc node, then RPCs through
    the front; the mesh on four virtual devices last."""
    out = {
        "grpc": window(front_daemon, front_daemon.grpc_address),
        "front": window(front_daemon, front_daemon.h2_fast_address,
                        callers=8),
    }
    mesh = spawn(device_count=4)
    try:
        out["mesh"] = window(mesh, mesh.grpc_address)
    finally:
        mesh.close()
    return out


# -- the entries ----------------------------------------------------------


def test_new_metrics_are_entries_over_accepted_readers(manifest):
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    cells = [w["name"] for w in manifest.doc["workloads"]]
    for name in NEW:
        entry, spec = by_name[name], manifest.layer_metric(name)
        assert spec["name"] == name and spec["reader"] in ACCEPTED_READERS
        assert (spec["layer"], spec["unit"]) == (entry["layer"], entry["unit"])
        assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
        for inner in (spec["args"].get("num"), spec["args"].get("den")):
            for part in inner if isinstance(inner, list) else [inner] * bool(inner):
                assert part["reader"] in ACCEPTED_READERS


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_sinks_reads_nothing(manifest, name):
    parent = {
        "prom_before": {}, "prom_after": {}, "vars_before": {},
        "vars_after": {}, "run": {"window_s": 20.0},
    }
    assert read_metric(manifest.layer_metric(name), parent) is None


# -- the readers over a live daemon's scrapes ------------------------------


@pytest.mark.parametrize("name", NEW)
def test_every_listed_family_reads_a_number(manifest, windows, name):
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    families = {family(manifest, cell) for cell in entry["workloads"]}
    spec = manifest.layer_metric(name)
    for fam in sorted(families):
        value = read_metric(spec, windows[fam])
        assert value is not None and value >= 0, (name, fam, value)


def test_stage_cpu_reads_under_its_wall(manifest, windows):
    """A stage's CPU is read inside its wall's interval: never more."""
    for fam, ctx in windows.items():
        for cpu, wall in (("host.lock_hold_cpu_us", "host.lock_hold_us"),
                          ("host.hotkeys_cpu_us", "host.hotkeys_us"),
                          ("pump.launch_cpu_us", "pump.launch_us")):
            c = read_metric(manifest.layer_metric(cpu), ctx)
            w = read_metric(manifest.layer_metric(wall), ctx)
            assert 0 < c <= w, (fam, cpu, c, w)


def test_the_front_serve_threads_cpu_sits_inside_its_serve_wall(manifest, windows):
    ctx = windows["front"]
    cpu = read_metric(manifest.layer_metric("front.serve_cpu_ms"), ctx)
    wall = read_metric(manifest.layer_metric("front.window_serve_ms"), ctx)
    assert 0 < cpu <= wall * 1.05, (cpu, wall)
    grew = (ctx["vars_after"]["h2_front"]["serve_cpu_ns"]
            - ctx["vars_before"]["h2_front"]["serve_cpu_ns"])
    assert grew > 0


# -- /debug/vars: the daemon's CPU by thread role ------------------------


def test_threads_cpu_carries_every_role_and_adds_up(front_daemon, windows):
    doc = windows["front"]["vars_after"]
    cpu, n = doc["threads_cpu_s"], doc["threads_n"]
    assert set(cpu) == set(THREAD_ROLES) | {"all"}
    assert set(n) == set(THREAD_ROLES)
    assert n["front_serve"] == 1 and n["front_dispatch"] == 1
    assert n["front_reactor"] == front_daemon.h2_fast.reactors >= 1
    assert n["grpc_listener"] >= 1 and n["native_events"] == 1
    assert not any("." in role for role in cpu)  # vars_delta splits on dots
    assert sum(cpu[r] for r in THREAD_ROLES) == pytest.approx(cpu["all"])
    assert all(v >= 0 for v in cpu.values())


def test_all_is_the_process_to_a_tick_a_thread(front_daemon):
    with urllib.request.urlopen(
        f"http://{front_daemon.http_address}/debug/vars", timeout=30
    ) as r:
        doc = json.loads(r.read())
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    hz = os.sysconf("SC_CLK_TCK")
    process = (int(fields[11]) + int(fields[12])) / hz
    live = len(os.listdir("/proc/self/task"))
    assert 0 <= process - doc["threads_cpu_s"]["all"] <= live / hz
