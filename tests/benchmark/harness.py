"""Run benchmarks/run.py as the driver does, for the tests."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_cell(*args, root=ROOT, timeout=400):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return proc, result


def checkout_copy(tmp_path) -> str:
    """BENCHMARK.json and benchmarks/ copied, the program linked: a
    tree in which a test adds a configuration or a cell as files."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    return str(root)
