"""Share of the HBM roofline that a mesh's step programs reached, in %.

As `trace_roofline`, for a state sharded over the chips of the trace.
Every chip runs each step program on its own shard at the same time,
and the decisions stepped while the trace ran are the whole mesh's, so
the least time for the work is set against the step programs' device
time on one chip (`trace_reduce.module_seconds` reads the first) times
the number of chips: chip-seconds, not seconds.

args: `patterns`: glob patterns of the step programs' module events.
"""

from lib import roofline, trace_reduce


def read(args, ctx):
    meta = ctx.get("trace_meta")
    if ctx.get("trace") is None or not meta:
        return None
    decisions = (
        meta["vars_stop"]["counters"]["requests_total"]
        - meta["vars_start"]["counters"]["requests_total"]
    )
    seconds, count = trace_reduce.module_seconds(ctx["trace"], args["patterns"])
    if count == 0 or decisions <= 0:
        return None
    chips = len(trace_reduce.device_planes(ctx["trace"]))
    return roofline.roofline_pct(decisions, seconds * chips, ctx["device_kind"])
