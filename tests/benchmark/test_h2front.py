"""The native front's configuration (`node100m_ledger0_h2front`) and its
two cells: what the manifest gained and kept, the launcher that hands
the clients' port to the front and refuses a program that cannot show
the configuration's scope guarantee, and a CPU rehearsal of each cell
at 20,000 rows."""

import hashlib
import json
import os

import pytest

from conftest import ROOT
from harness import CONTRACT_KEYS, run_cell
from lib import launch_daemon, launch_h2front
from lib.manifest import Manifest

CONFIG = "node100m_ledger0_h2front"
CELLS = [f"{CONFIG}.herd100", f"{CONFIG}.batch1000_zipf"]
# The entries BENCHMARK.json had when PR 31 left it (commit c490790),
# each by its name: how many cells it listed then, and `digest` of the
# entry with its list cut back to those (a later PR may append its own
# cells to an accepted list, and add entries wherever the contract says).
ACCEPTED = {
    "configs": {
        "node100m_ledger0": (None, "874bc19d4c8c"),
        "mesh4_ledger0": (None, "86286c387f2b"),
        "node100m_ledger0_filled": (None, "1980f10d83ad"),
    },
    "workloads": {
        "node100m_ledger0.batch1000_zipf": (None, "670ff7e7b3cf"),
        "node100m_ledger0.herd100": (None, "255b8a3c7a7b"),
        "mesh4_ledger0.batch1000_zipf": (None, "69b3df858e51"),
        "node100m_ledger0_filled.batch1000_zipf": (None, "b1a934720d33"),
    },
    "per_layer": {
        "generator.cpu_busy_pct": (2, "98361e033a56"),
        "listener.grpc_server_ms": (2, "ae7daeaf3057"),
        "host.engine_serve_ms": (2, "4cbb14b69a8e"),
        "pump.dispatches_per_kdecision": (2, "604805e805b0"),
        "pump.readback_ms": (1, "afdca35c2848"),
        "step.kernel_us_per_dispatch": (2, "36612d191db9"),
        "step_roofline": (2, "e5f5cdb1b1ba"),
        "device.idle_pct": (2, "b383e0ddb74d"),
        "listener.queue_wait_ms": (3, "de6e87322994"),
        "listener.decode_us": (3, "95c5c58df5c2"),
        "listener.encode_us": (3, "1b6cf27ec7ec"),
        "host.lock_wait_ms": (3, "bfd295851e49"),
        "host.lock_hold_us": (3, "30992eb3e547"),
        "host.intern_us": (3, "607214b80a97"),
        "host.pack_us": (3, "33b0cf29fd91"),
        "host.unpack_us": (3, "204088e211cc"),
        "host.sweep_ms_in_window": (3, "f147c72d3a5d"),
        "pump.h2d_us": (3, "9acc58e7e1bd"),
        "pump.launch_us": (3, "d3b353bc80e5"),
        "pump.answer_wait_ms": (2, "c69e641ffd92"),
        "pump.answer_tail_us": (3, "6c022e9fad82"),
        "device.idle_explained_pct": (3, "6be7f05c8eba"),
        "step.compiles_in_window": (3, "3162c51fd4bd"),
        "mesh.route_us": (1, "7346d34a009e"),
        "mesh.step_us_per_dispatch": (1, "3f37a06bc648"),
        "host.hotkeys_us": (3, "3ea108345dbe"),
        "generator.cpu_busy_pct.mesh4": (1, "2174e34ac5bf"),
        "listener.grpc_server_ms.mesh4": (1, "77d166967f84"),
        "host.engine_serve_ms.mesh4": (1, "b4545dac0f48"),
        "pump.dispatches_per_kdecision.mesh4": (1, "5b134458c622"),
        "device.idle_pct.mesh4": (1, "cc5eb5314697"),
        "mesh_step_roofline": (1, "8fa02954460f"),
        "evict.rows_per_kdecision": (1, "569af5866132"),
        "host.evict_clear_us": (1, "913c219bcb04"),
        "step.clear_us_per_dispatch": (1, "13ba9c8140ee"),
        "clear_roofline": (1, "cba4703f3a04"),
        "filled_step_roofline": (1, "42eaac7e0ac0"),
        "setup.load_s": (1, "3d6cdc93fc08"),
        "setup.load_krows_per_s": (1, "84d94b0d5a1e"),
        "listener.grpc_server_ms.filled": (1, "245cb6cb8521"),
        "host.engine_serve_ms.filled": (1, "e11f72a8297a"),
        "host.lock_hold_us.filled": (1, "d513ed2f7ad3"),
        "host.intern_us.filled": (1, "d1ed83189e6b"),
        "host.pack_us.filled": (1, "4fea98705e03"),
        "pump.h2d_us.filled": (1, "8c9c57b4fce7"),
        "pump.launch_us.filled": (1, "8a43c998605a"),
        "pump.dispatches_per_kdecision.filled": (1, "2aff37eafec0"),
        "step.kernel_us_per_dispatch.filled": (1, "d457ef4cafb6"),
        "device.idle_pct.filled": (1, "1d53a84c0d12"),
        "device.idle_explained_pct.filled": (1, "11fd529b8318"),
        "host.sweep_ms_in_window.filled": (1, "16b9b2edf2f1"),
        "step.compiles_in_window.filled": (1, "14f31595e9d0"),
    },
}
FRONT_METRICS = {
    "front.rpcs_per_window", "front.items_per_window", "front.declined_rpcs",
    "front.rpc_ms", "front.pack_us", "front.ring_wait_us", "front.scatter_us",
    "front.window_serve_ms", "front.ring_dropped",
}
# accepted metric -> its copy for these cells (same reader and arguments)
COPIES = {name: f"{name}.h2front" for name in (
    "generator.cpu_busy_pct", "host.lock_wait_ms", "host.lock_hold_us",
    "host.intern_us", "host.pack_us", "host.hotkeys_us", "host.unpack_us",
    "host.sweep_ms_in_window", "pump.h2d_us", "pump.launch_us",
    "pump.answer_wait_ms", "pump.answer_tail_us", "pump.readback_ms",
    "pump.dispatches_per_kdecision", "step.kernel_us_per_dispatch",
    "step.compiles_in_window", "device.idle_pct", "device.idle_explained_pct",
)}
COPIES["step_roofline"] = "h2front_step_roofline"


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


# -- the configuration and its entries -----------------------------------


def test_configuration_is_its_sibling_behind_the_front(manifest):
    front, sibling = manifest.config(CONFIG), manifest.config("node100m_ledger0")
    for key in ("env", "chips", "engine", "rows", "row_bytes", "reduced",
                "assumed", "rehearsal"):
        assert front[key] == sibling[key], key
    assert set(front["env"]) == {"GUBER_CACHE_SIZE", "GUBER_LEDGER"}  # the front at its defaults
    assert front["launcher"] == "benchmarks/lib/launch_h2front.py"
    assert os.path.exists(os.path.join(ROOT, front["launcher"]))
    assert {k: v for k, v in front["guarantees"].items() if k != "scope"} == sibling["guarantees"]
    assert "UNIMPLEMENTED" in front["guarantees"]["scope"]
    assert set(front["departs_from_defaults"]) == {"GUBER_LEDGER", "GUBER_H2_FAST_ADDRESS"}
    assert "spec.py" in front["reference"]
    for cell in CELLS:
        accepted = manifest.cell(cell.replace(CONFIG, "node100m_ledger0"))
        assert manifest.cell(cell) == dict(accepted, name=cell, config=CONFIG)


def digest(entry, listed) -> str:
    if listed is not None:
        entry = dict(entry, workloads=entry["workloads"][:listed])
    return hashlib.sha256(json.dumps(entry, sort_keys=True).encode()).hexdigest()[:12]


def test_accepted_entries_keep_their_form_and_the_new_ones_list_their_cells(manifest):
    """By name, not by place or count: every entry the manifest had
    before this configuration is as it was, and each new entry is there
    and lists the new cells that feed it.  (The new entries stand at the
    end of their lists, where the benchmark's contract wants them.)"""
    doc = manifest.doc
    for section, accepted in ACCEPTED.items():
        by_name = {e["name"]: e for e in doc[section]}
        for name, (listed, form) in accepted.items():
            assert digest(by_name[name], listed) == form, (section, name)
    assert CONFIG in [c["name"] for c in doc["configs"]]
    for cell in CELLS:
        entry = next(w for w in doc["workloads"] if w["name"] == cell)
        assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for name in FRONT_METRICS | set(COPIES.values()):
        # the herd's 220 us step has ended before its one serving
        # thread begins the read: no read spans a step there
        listed = CELLS[1:] if name == "pump.answer_tail_us.h2front" else CELLS
        assert by_name[name]["workloads"][:len(listed)] == listed, name


def test_copies_keep_the_accepted_reader_and_arguments(manifest):
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    for accepted, name in COPIES.items():
        assert {k: v for k, v in by_name[name].items() if k not in ("name", "workloads")} == {
            k: v for k, v in by_name[accepted].items() if k not in ("name", "workloads")}
        a, c = manifest.layer_metric(accepted), manifest.layer_metric(name)
        assert (c["reader"], c["args"]) == (a["reader"], a["args"])
    for name in FRONT_METRICS:
        spec = manifest.layer_metric(name)
        assert spec["reader"] in ("ratio", "vars_delta", "prom_delta")  # data over readers that exist


# -- the launcher ---------------------------------------------------------


def test_launcher_hands_the_clients_port_to_the_front(monkeypatch):
    seen = {}
    monkeypatch.setattr(launch_daemon, "main", lambda: seen.update(os.environ) or 0)
    monkeypatch.setenv("GUBER_GRPC_ADDRESS", "127.0.0.1:43210")
    monkeypatch.setenv("GUBER_H2_FAST_ADDRESS", "")  # restored after the test
    assert launch_h2front.main() == 0
    assert seen["GUBER_H2_FAST_ADDRESS"] == "127.0.0.1:43210"
    host, _, port = seen["GUBER_GRPC_ADDRESS"].rpartition(":")
    assert host == "127.0.0.1" and port not in ("43210", "0")
    assert not [k for k in seen if k.startswith("GUBER_H2_") and k != "GUBER_H2_FAST_ADDRESS"]


def test_launcher_refuses_a_program_without_the_fronts_events(monkeypatch, capsys):
    """The parent of PR 32: a front whose ring has no `rpc_total` and no
    `feeder_scatter` ends the run before anything starts."""
    from gubernator_tpu.utils import native_events

    monkeypatch.setattr(launch_daemon, "main", lambda: pytest.fail("started"))
    monkeypatch.setattr(native_events, "STAGES", {
        k: v for k, v in native_events.STAGES.items()
        if v not in launch_h2front.FRONT_EVENTS})
    monkeypatch.setenv("GUBER_GRPC_ADDRESS", "127.0.0.1:43210")
    assert launch_h2front.main() == 3
    assert "[launch_h2front] REFUSED: " in capsys.readouterr().out
    assert os.environ["GUBER_GRPC_ADDRESS"] == "127.0.0.1:43210"


# -- a rehearsal of each cell --------------------------------------------


@pytest.mark.parametrize("cell,seed,rpcs_per_window", [
    (CELLS[0], "3200000011", 3.0), (CELLS[1], "3200000027", 1.0)])
def test_cell_rehearsed_on_the_cpu(cell, seed, rpcs_per_window):
    proc, result = run_cell(
        "--workload", cell, "--seed", seed, "--seconds", "2", "--trace", "1",
        "--rehearse-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True and result["failed"] == 0, result["compared"]
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}
    assert result["compared"]["unanswered_rpcs"] == {"value": 0, "limit": 0}
    metrics = {n: m["value"] for n, m in result["metrics"].items()}
    assert FRONT_METRICS <= set(metrics)
    assert not any(n.startswith("listener.") for n in metrics)  # nothing dialed grpc-python
    assert metrics["front.declined_rpcs"] == 0 and metrics["front.ring_dropped"] == 0
    assert metrics["step.compiles_in_window.h2front"] == 0
    # the window gathers: more than one RPC a Python entry (herd), at
    # least a whole RPC (batch)
    assert metrics["front.rpcs_per_window"] >= rpcs_per_window
    assert metrics["front.items_per_window"] >= metrics["front.rpcs_per_window"]
    assert 0 < metrics["front.pack_us"] * 1e-3 < metrics["front.rpc_ms"]
    assert metrics["front.ring_wait_us"] * 1e-3 < metrics["front.rpc_ms"]
    assert metrics["front.window_serve_ms"] > 0 and metrics["front.scatter_us"] > 0
    # host-side copies read on any platform; nothing read from a device
    # trace is reported from a CPU run
    assert {"host.lock_hold_us.h2front", "pump.dispatches_per_kdecision.h2front",
            "pump.readback_ms.h2front", "generator.cpu_busy_pct.h2front"} <= set(metrics)
    assert not {"device.idle_pct.h2front", "pump.answer_tail_us.h2front",
                "h2front_step_roofline"} & set(metrics)
    assert (result["device"]["platform"], result["device"]["count"]) == ("cpu", 1)
