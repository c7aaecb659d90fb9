"""The harness driven through a whole run with the timed path broken
underneath (tests/benchmark/faulty_launcher.py), and with the control
in the daemon's place: `correct` has to come out false.  Both are
configurations: a launcher and its environment, named in a file."""

import json
import os

import pytest

from conftest import ROOT
from harness import checkout_copy, run_cell

LAUNCHER = os.path.join(ROOT, "tests", "benchmark", "faulty_launcher.py")


@pytest.mark.parametrize("fault", ["alter_answer", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    root = checkout_copy(tmp_path)
    with open(os.path.join(root, "benchmarks/configs/node100m_ledger0.json")) as f:
        config = json.load(f)
    config.update(name="broken", launcher=LAUNCHER)
    config["env"]["FAULTY_LAUNCHER_FAULT"] = fault
    with open(os.path.join(root, "benchmarks/configs/broken.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmarks/cells/broken.batch1000_zipf.json"), "w") as f:
        json.dump({"name": "broken.batch1000_zipf", "config": "broken",
                   "traffic": "batch1000_zipf", "min_checked": 1}, f)
    proc, result = run_cell(
        "--workload", "broken.batch1000_zipf", "--seed", "21",
        "--seconds", "3", "--trace", "0", "--rehearse-cpu", root=root,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is False
    assert result["compared"]["mismatched"]["value"] > 0
    assert result["compared"]["mismatched"]["limit"] == 0
    assert "compared mismatched" in proc.stderr


@pytest.mark.parametrize("cell,correct", [
    ("control_stale.batch1000_zipf", False),
    ("control_stale.herd100", False),
    ("control_none.herd100", True),
])
def test_control_in_the_daemons_place(cell, correct):
    proc, result = run_cell(
        "--workload", cell, "--seed", "22", "--seconds", "4", "--trace", "0",
        "--rehearse-cpu",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert result["correct"] is correct, result["compared"]
    assert result["device"]["platform"] == "none"
