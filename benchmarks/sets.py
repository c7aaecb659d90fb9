#!/usr/bin/env python3
"""Runs of run.py one after another, as the driver makes them, each
kept as one row of `fixtures/spread_*.json`: what `lib/bounds.py` sets
the bounds from.

    python3 benchmarks/sets.py --out <file.jsonl> <set>:<label>:<cell>:<seed>[:<seconds>[:<trace>]] ...

Each run is a new process, the next starts when the one before has
ended, and a JSON line a run is appended to `--out`: cell, set, label,
seed, when it started, seconds, `correct` and the values of the result
line (the four end-to-end ones, or with trace 1 the per-layer ones).
The run's whole output, the per-slice line with it, goes to
`<out>.logs/<n>.log`.  This process never touches jax.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_run(name, label, cell, seed, seconds, trace, log_path) -> dict:
    started = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True,
    )
    with open(log_path, "w") as f:
        f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr)
    row = {"cell": cell, "set": name, "label": label, "seed": seed,
           "started_utc": started, "seconds": seconds}
    if trace:
        row["trace"] = trace
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
        row["correct"] = result["correct"]
        row["values"] = {k: m["value"] for k, m in result["metrics"].items()}
    else:
        row["rc"] = proc.returncode
        row["error"] = (proc.stderr or proc.stdout)[-1500:]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("runs", nargs="+",
                    help="<set>:<label>:<cell>:<seed>[:<seconds>[:<trace>]]")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        run_seconds = json.load(f)["run_seconds"]
    logs = args.out + ".logs"
    os.makedirs(logs, exist_ok=True)
    n = len(os.listdir(logs))
    for spec in args.runs:
        name, label, cell, seed, *rest = spec.split(":")
        seconds = float(rest[0]) if rest else run_seconds
        trace = int(rest[1]) if len(rest) > 1 else 0
        row = one_run(name, label, cell, int(seed), seconds, trace,
                      os.path.join(logs, f"{n:03d}.log"))
        n += 1
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
