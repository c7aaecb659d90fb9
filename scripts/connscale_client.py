#!/usr/bin/env python
"""Connection-scale load generator subprocess.

Runs the epoll connscale client (core/h2_client.h2_connscale_run)
against ADDRESS and prints ONE JSON line with the results.  A
subprocess because fds are the scarce resource: at the 10k rung the
server and the client each hold one fd per
connection, and RLIMIT_NOFILE is per-process — colocating both halves
would cap the ramp at half the limit.

Usage: connscale_client.py ADDRESS CONNS ACTIVE SECONDS THREADS
"""

import json
import os
import resource
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    address, conns, active, seconds, threads = sys.argv[1:6]
    conns, active, threads = int(conns), int(active), int(threads)
    seconds = float(seconds)
    # Raise the fd ceiling to the hard limit; report what we got so a
    # clamped ramp is attributable in the artifact.
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        soft = hard

    from gubernator_tpu.core import h2_client

    res = h2_client.connscale(
        address, "/pb.gubernator.V1/GetRateLimits",
        bytes.fromhex(os.environ["CONNSCALE_PAYLOAD_HEX"]),
        seconds, conns, active, threads=threads,
        ramp_budget_s=float(os.environ.get("CONNSCALE_RAMP_BUDGET", 120.0)),
    )
    if res is None:
        print(json.dumps({"error": "connscale client failed to connect"}))
        return 1
    import numpy as np

    lats = res.pop("lats_s")
    out = dict(res)
    out["rate"] = res["rpcs"] / seconds
    out["p50_ms"] = (
        round(float(np.percentile(lats, 50)) * 1e3, 3) if len(lats) else None
    )
    out["p99_ms"] = (
        round(float(np.percentile(lats, 99)) * 1e3, 3) if len(lats) else None
    )
    out["nofile_limit"] = soft
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
