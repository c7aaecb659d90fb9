"""run.py end to end at a tiny size on the CPU backend, and its
refusal to run anywhere but on a chip."""

import json

from conftest import ROOT  # noqa: F401
from harness import CONTRACT_KEYS, run_cell

with open(f"{ROOT}/BENCHMARK.json") as f:
    DOC = json.load(f)


def test_one_chip_cell_traced():
    proc, result = run_cell(
        "--workload", "node100m_ledger0.herd100", "--seed", "3000000011",
        "--seconds", "3", "--trace", "1", "--rehearse-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert list(result)[:5] == CONTRACT_KEYS and list(result)[-1] == "compared"
    assert set(result) <= set(CONTRACT_KEYS) | {"breakdown", "state", "compared"}
    # the log's per-slice line: one row a 10 s slice of the window
    (line,) = [x for x in proc.stdout.splitlines() if "s slice of the window" in x]
    (row,) = json.loads(line.split(": ", 1)[1])
    assert row["from_s"] == 0.0 and row["rpcs"] > 0
    assert {"decisions_per_s", "rpc_p50_ms", "rpc_p95_ms", "pauses_ms"} <= set(row)
    assert 0 < result["state"]["rows_occupied_start"] <= result["state"]["rows_occupied_end"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    dev = result["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", 1)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    per_layer = {m["name"]: m for m in DOC["per_layer"]}
    assert result["metrics"], "a traced run reports per-layer metrics"
    for name, m in result["metrics"].items():
        assert m["unit"] == per_layer[name]["unit"]
        assert isinstance(m["value"], float)
    # host-side layers read on any platform; nothing read from a device
    # trace is reported from a CPU run
    assert "listener.grpc_server_ms" in result["metrics"]
    assert "pump.dispatches_per_kdecision" in result["metrics"]
    for name in ("device.idle_pct", "step_roofline", "step.kernel_us_per_dispatch"):
        assert name not in result["metrics"]
    assert "busy_s" not in dev
    # each number compared, beside its limit: last on stderr, last in the line
    tail = proc.stderr.strip().splitlines()[-3:]
    assert all(line.startswith("compared ") for line in tail)
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}


def test_mesh_cell_on_four_virtual_devices():
    proc, result = run_cell(
        "--workload", "mesh4_ledger0.batch1000_zipf", "--seed", "12", "--seconds", "3",
        "--trace", "0", "--rehearse-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True, result["compared"]
    assert (result["device"]["platform"], result["device"]["count"]) == ("cpu", 4)
    end_to_end = {m["name"]: m["unit"] for m in DOC["end_to_end"]}
    assert {n: m["unit"] for n, m in result["metrics"].items()} == end_to_end
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_without_a_chip_no_result_and_a_non_zero_exit():
    proc, result = run_cell(
        "--workload", "node100m_ledger0.herd100", "--seed", "1", "--seconds", "2",
        "--trace", "0",
    )
    assert proc.returncode != 0
    assert result is None
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_without_the_program_no_result_and_a_non_zero_exit(tmp_path):
    """In a directory that holds only BENCHMARK.json and the paths."""
    import shutil
    import subprocess
    import sys

    for p in DOC["paths"]:
        shutil.copytree(f"{ROOT}/{p}", tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(f"{ROOT}/BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "node100m_ledger0.herd100",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_unknown_cell_is_refused():
    proc, result = run_cell(
        "--workload", "node100m.nothing", "--seed", "1", "--seconds", "2",
        "--trace", "0", "--rehearse-cpu",
    )
    assert proc.returncode != 0 and result is None
