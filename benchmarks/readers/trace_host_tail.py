"""From a step program's end on the device to a host thread holding
its bytes, median over the traced slice, in µs.

For each matching whole-program event on the first device: the
earliest end of a blocking host read that starts before the program's
end and ends at or after it, minus the program's end.  A caller queued
behind other callers' steps spans several programs' ends; the read
that ends soonest after one is that step's own.  Programs that no
read spans are left out.

args: `patterns`: glob patterns of the step programs' module events;
`host_events`: glob patterns of the host events that are blocking
reads of a device array.
"""

import statistics

from lib import trace_reduce


def read(args, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    reads = [
        (start, start + dur)
        for plane in trace_reduce.host_planes(trace)
        for line in plane["lines"]
        for name, start, dur in line["events"]
        if trace_reduce.matches(name, args["host_events"])
    ]
    tails = []
    for _name, start, dur in trace_reduce.module_events(trace, args["patterns"]):
        end = start + dur
        spanning = [h1 for h0, h1 in reads if h0 < end <= h1]
        if spanning:
            tails.append(min(spanning) - end)
    if not tails:
        return None
    return statistics.median(tails) / 1e3
