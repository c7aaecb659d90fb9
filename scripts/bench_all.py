"""Run every BASELINE benchmark config and write BENCH_<round>_*.json.

Configs (BASELINE.md / BASELINE.json):
  1. default  — token+leaky mixed, 100k keys, single chip (headline)
  2. leaky1m  — leaky bucket, 1M keys, batch 1000
  3. global4  — GLOBAL behavior, 4-node in-process cluster
  4. zipf     — mixed algos, Zipf-skewed keys over a large space
  wire        — loopback gRPC at the serving window (p99 SLO evidence)

Each config is one bench.py subprocess, run one at a time (this parent
never touches jax, so the child is the only process on the chip), with
its knobs passed via env.  Artifacts land
in the repo root as BENCH_<round>_<name>.json where <round> comes from
BENCH_ROUND (default "r04").

Usage: python scripts/bench_all.py [name ...]   (default: all)
       python scripts/bench_all.py fast_capture
         — the under-3-minute combined tier (default+latency+herdfast
           with shortened knobs) writing BENCH_<round>_fast_capture.json
           with per-config capture durations (VERDICT r5 #1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUND = os.environ.get("BENCH_ROUND", "r05")

CONFIGS: dict[str, dict] = {
    "default": {},
    "leaky1m": {
        "BENCH_ALGO": "leaky",
        "BENCH_KEYS": "1000000",
        "BENCH_CAPACITY": str(1 << 21),
        "BENCH_BATCH": "8192",
    },
    "global4": {
        "BENCH_MODE": "global",
        "BENCH_NODES": "4",
        "BENCH_KEYS": "100000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_BATCH": "1000",
        # Steady-state measurement: the first seconds of GLOBAL load
        # are cold XLA compiles + first-window flush bursts; p99 over
        # a 5s window was dominated by them (PERF.md §15).
        "BENCH_WARM_SECONDS": "5",
        "BENCH_SECONDS": "10",
    },
    # GLOBAL's design case: HOT keys, where non-owners answer from the
    # owner-broadcast status cache (reference: architecture.md:46-74).
    # The wide-keyspace variant above defeats that cache by design.
    "global4hot": {
        "BENCH_MODE": "global",
        "BENCH_NODES": "4",
        "BENCH_KEYS": "1000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_BATCH": "1000",
        "BENCH_WARM_SECONDS": "5",
        "BENCH_SECONDS": "10",
    },
    "zipf": {
        "BENCH_ZIPF": "1.2",
        "BENCH_KEYS": "100000000",
        "BENCH_CAPACITY": str(1 << 24),  # hot working set; full 100M
        # slots is a 7.6GB HBM budget question, answered in PERF.md §8
        "BENCH_BATCH": "8192",
    },
    "wire": {
        "BENCH_MODE": "wire",
        "BENCH_BATCH": "1000",
        "BENCH_KEYS": "100000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_WIRE_PROCS": "4",
    },
    # Single-client baseline for the lock-split criterion (VERDICT r3
    # #3): multi-client p50 within ~1.5x of this proves host
    # scheduling overlaps device work.  Only meaningful where the
    # server has idle host capacity (TPU); on the one-core CPU host
    # closed-loop p50 scales with concurrency by queueing physics.
    "wire1": {
        "BENCH_MODE": "wire",
        "BENCH_BATCH": "1000",
        "BENCH_KEYS": "100000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_WIRE_PROCS": "1",
    },
    # Wire-max batch through the native h2 fast front: the front's
    # throughput shape at batch 1000 (the herd configs measure batch 1).
    "wirefast": {
        "BENCH_MODE": "wire",
        "BENCH_BATCH": "1000",
        # The native client replays ONE payload, so exactly batch-many
        # keys are exercised (the metric label says so too).
        "BENCH_KEYS": "1000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_WIRE_FAST": "1",
        # The group-commit window exists for tiny RPCs; at the
        # wire-max batch it only adds latency — run it near zero.
        "BENCH_LOCAL_BATCH_WAIT": "0.0002",
    },
    # Device decision plane fused/unfused A/B (ISSUE 10): the fused
    # single-dispatch step vs GUBER_FUSED=split, alternating pairs,
    # median of per-pair deltas; carries dispatches/batch per arm.
    "devfused": {
        "BENCH_MODE": "devfused",
        "BENCH_KEYS": "100000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_BATCH": "8192",
    },
    # Thundering herd: 32 concurrent clients, one hot key, single-item
    # RPCs (reference: benchmark_test.go thundering-herd subtest).
    "herd": {
        "BENCH_MODE": "herd",
        "BENCH_KEYS": "1",
        "BENCH_CAPACITY": str(1 << 17),
    },
    # Same herd served through the native h2 fast front
    # (net/h2_fast.py): C-side framing + group commit, one Python
    # entry per window — the grpc-python per-RPC wall removed.
    "herdfast": {
        "BENCH_MODE": "herd",
        "BENCH_KEYS": "1",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_HERD_FAST": "1",
    },
    # The herd through the fast front's NATIVE DECISION PLANE: hot-key
    # single-item RPCs answered inside the C connection threads — zero
    # GIL, zero Python frames (core/native/decision_plane.cpp).  The
    # same-session A/B is GUBER_NATIVE_LEDGER=0 over this config.
    "herdnative": {
        "BENCH_MODE": "herdnative",
        "BENCH_KEYS": "1",
        "BENCH_CAPACITY": str(1 << 17),
    },
    # Flash crowd through the hot-key replication plane (ISSUE 13 /
    # RESILIENCE §11): a time-varying zipf whose hot set rotates
    # mid-run across a 3-node cluster — promotion keeps every node
    # answering hot keys locally; the _repl0 arm below is the
    # consistent-hash-only A/B.  A finite-limit canary key checks the
    # N_replicas x lease admission bound in the same run.
    "flashcrowd": {
        "BENCH_MODE": "flashcrowd",
        "BENCH_KEYS": "1000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_SECONDS": "12",
    },
    "flashcrowd_repl0": {
        "BENCH_MODE": "flashcrowd",
        "BENCH_KEYS": "1000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_SECONDS": "12",
        "BENCH_FLASH_REPL": "0",
    },
    # Connection scale through the epoll event front (PERF.md §26):
    # 1k→10k held connections from the epoll connscale client, with
    # the thread-per-conn A/B at equal load and the feeder-ring-wait
    # starvation attribution per rung.  CPU-tier config (the front is
    # host-side; no device involvement beyond the serve plane).
    "connscale": {
        "BENCH_MODE": "connscale",
        "BENCH_KEYS": "1",
        "BENCH_CAPACITY": str(1 << 17),
    },
    # Bulk operating point: batch 32768 amortizes per-dispatch and
    # per-transfer fixed costs 4x deeper than the default-config
    # batch 8192.
    "bulk": {
        "BENCH_BATCH": "32768",
        "BENCH_KEYS": "1000000",
        "BENCH_CAPACITY": str(1 << 21),
    },
    # BASELINE config 5: count-min-sketch approximate limiter
    # (Behavior.SKETCH) over the wire — unbounded key cardinality in
    # O(1) memory, one-sided error (ops/sketch.py).
    "sketch": {
        "BENCH_MODE": "sketch",
        "BENCH_BATCH": "1000",
        "BENCH_KEYS": "10000000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_WARM_SECONDS": "3",
    },
    # Latency mode (VERDICT r4 #4): closed-loop synchronous dispatch,
    # pre-warmed engine — the p50/p99 fields are the artifact; the SLO
    # bar is p99 < 2ms (BASELINE.md).  Batch 512 is the latency
    # operating point (the bar allows <= 1000): batch-1000 sits at
    # p50 1.23 / p99 ~2.2ms, batch-512 at p50 0.92 / p99 ~1.5ms —
    # XLA:CPU execute-time variance (3-6ms dispatch spikes, scattered,
    # not GC and not periodic) sets the tail, so the margin comes from
    # a smaller per-step baseline (PERF.md §14).
    "latency": {
        "BENCH_BATCH": "512",
        "BENCH_KEYS": "100000",
        "BENCH_CAPACITY": str(1 << 17),
        "BENCH_LATENCY_BATCHES": "1000",
        "BENCH_SECONDS": "2",
    },
    # The 100M-slot HBM proof (BASELINE config 4 at full scale):
    # 19 arrays x 4B x 100M = 7.6GB of device state on one v5e chip.
    # TPU-only (the CPU fallback would also allocate 7.6GB, fine on
    # this 125GB host, but the number is meaningless there).
    "zipf100m": {
        "BENCH_ZIPF": "1.2",
        "BENCH_KEYS": "100000000",
        "BENCH_CAPACITY": "100000000",
        "BENCH_BATCH": "8192",
        "BENCH_SECONDS": "8",
    },
}


# fast_capture tier: ONE combined run capturing throughput (default),
# the latency SLO (latency) and the native front (herdfast) in under
# 3 minutes.  Each sub-config runs with shortened measure knobs; the
# combined artifact records the per-config capture duration.
FAST_CAPTURE = ["default", "latency", "herdfast"]
FAST_CAPTURE_OVERRIDES = {
    "default": {"BENCH_SECONDS": "4", "BENCH_LATENCY_BATCHES": "100"},
    "latency": {"BENCH_LATENCY_BATCHES": "400", "BENCH_SECONDS": "2"},
    "herdfast": {"BENCH_SECONDS": "4"},
}


def run_fast_capture() -> dict:
    """Run the fast tier and write BENCH_<round>_fast_capture.json
    (plus the individual per-config artifacts)."""
    import time

    t_all = time.monotonic()
    combined: dict = {"tier": "fast_capture", "configs": {}}
    for name in FAST_CAPTURE:
        overrides = dict(CONFIGS[name])
        overrides.update(FAST_CAPTURE_OVERRIDES.get(name, {}))
        t0 = time.monotonic()
        result = run(name, overrides)
        result["capture_seconds"] = round(time.monotonic() - t0, 1)
        combined["configs"][name] = result
        # Each sub-result also lands as its own artifact.
        path = os.path.join(ROOT, f"BENCH_{ROUND}_{name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        print(json.dumps(result), flush=True)
    combined["total_seconds"] = round(time.monotonic() - t_all, 1)
    path = os.path.join(ROOT, f"BENCH_{ROUND}_fast_capture.json")
    with open(path, "w") as f:
        json.dump(combined, f, indent=1)
        f.write("\n")
    return combined


def run(name: str, overrides: dict) -> dict:
    env = dict(os.environ)
    env.update(overrides)
    env.setdefault("BENCH_SECONDS", "5")
    # Own process group + group kill on timeout: bench.py starts
    # client/daemon children of its own, which a plain
    # subprocess.run timeout would leave holding the pipes open.
    import signal

    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, stdin=subprocess.DEVNULL, text=True,
        start_new_session=True,
    )
    try:
        out, err = proc.communicate(timeout=1200)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            if proc.stdout:
                proc.stdout.close()
            if proc.stderr:
                proc.stderr.close()
        return {"error": "bench timed out (group-killed)"}
    line = ""
    for ln in (out or "").strip().splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            line = ln
    if not line:
        return {
            "error": f"no JSON line (rc={proc.returncode})",
            "stderr_tail": (err or "")[-400:],
        }
    result = json.loads(line)
    result["config"] = name
    result["env"] = overrides
    if proc.returncode != 0:
        # bench.py exits non-zero on a failed run (no chip, deadline,
        # exception); keep that visible in the artifact.
        result.setdefault("error", f"bench rc={proc.returncode}")
    return result


def main() -> int:
    names = sys.argv[1:] or list(CONFIGS)
    failed = False
    if "fast_capture" in names:
        names.remove("fast_capture")
        combined = run_fast_capture()
        failed = any("error" in r for r in combined["configs"].values())
    for name in names:
        print(f"=== {name}: {CONFIGS[name]}", file=sys.stderr, flush=True)
        result = run(name, CONFIGS[name])
        path = os.path.join(ROOT, f"BENCH_{ROUND}_{name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        print(json.dumps(result), flush=True)
        failed = failed or "error" in result
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
