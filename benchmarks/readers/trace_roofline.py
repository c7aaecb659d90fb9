"""Share of the HBM roofline that the step programs reached, in %.

The least time for the work — decisions stepped on the device while
the trace ran (the daemon's own `requests_total`, read inside the
traced slice) times the bytes one decision has to move — over the
device time of the step programs' events.

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
    return roofline.roofline_pct(decisions, seconds, ctx["device_kind"])
