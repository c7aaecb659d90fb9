"""Start the daemon as `python -m gubernator_tpu.cmd.daemon` does, and
— only when BENCH_TRACE_DIR is set — bracket a short steady slice of
the window with the jax profiler from inside the process that holds
the chip (the program has no profiler hook of its own).

The slice starts when the harness creates `<dir>/go`; the thread then
writes `<dir>/done` with the wall-clock bounds of the slice and the
daemon's own /debug/vars `device` counters at both ends, read inside
the traced interval, so that the decisions stepped on the device while
the trace ran are known to the count.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _device_vars(http_addr: str) -> dict:
    with urllib.request.urlopen(
        f"http://{http_addr}/debug/vars", timeout=30
    ) as r:
        return json.loads(r.read())["device"]


def trace_slice(trace_dir: str, http_addr: str, seconds: float) -> None:
    go = os.path.join(trace_dir, "go")
    while not os.path.exists(go):
        time.sleep(0.02)
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the host's Python frames would fill the file
    opts.host_tracer_level = 2
    out = {"error": ""}
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        out["t_start"] = time.time()
        out["vars_start"] = _device_vars(http_addr)
        time.sleep(seconds)
        out["vars_stop"] = _device_vars(http_addr)
        out["t_stop"] = time.time()
        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001 — reported to the harness, which fails the run
        out["error"] = f"{type(e).__name__}: {e}"
    tmp = os.path.join(trace_dir, "done.tmp")
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, os.path.join(trace_dir, "done"))


def main() -> int:
    sys.path.insert(0, ROOT)
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    if trace_dir:
        threading.Thread(
            target=trace_slice,
            args=(trace_dir, os.environ["GUBER_HTTP_ADDRESS"],
                  float(os.environ["BENCH_TRACE_SECONDS"])),
            daemon=True, name="bench-trace",
        ).start()
    from gubernator_tpu.cmd.daemon import main as daemon_main

    return daemon_main([])


if __name__ == "__main__":
    sys.exit(main())
