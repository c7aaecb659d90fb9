"""Share of the HBM roofline that the eviction clears reached, in %.

The least time for the work — rows evicted while the trace ran (the
daemon's own `evictions_total`, read inside the traced slice: each
evicted row is cleared once) times the bytes a cleared row has to move
(lib/clear_bytes.py) — over the device time of the clear programs'
events.  Nothing where the program has no such counter or program.

args: `patterns`: glob patterns of the clear programs' module events.
"""

from lib import clear_bytes, trace_reduce


def read(args, ctx):
    meta = ctx.get("trace_meta")
    if ctx.get("trace") is None or not meta:
        return None
    start = meta["vars_start"]["counters"].get("evictions_total")
    stop = meta["vars_stop"]["counters"].get("evictions_total")
    if start is None or stop is None:
        return None
    seconds, count = trace_reduce.module_seconds(ctx["trace"], args["patterns"])
    if count == 0 or stop - start <= 0:
        return None
    return clear_bytes.clear_roofline_pct(
        stop - start, seconds, ctx["device_kind"])
