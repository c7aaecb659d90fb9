"""Share of the device's idle time that the program's own work
explains, in %.

Idle time is every gap between device operations on the busiest
chip.  Explained is the part of it under the union, over all host
threads, of the program's `work` annotations (utils/metrics.stage
writes one `jax.profiler.TraceAnnotation` per leaf work stage; waits
carry none).  Nothing where the trace holds no such annotation or the
device has no gap.

args: `stages`: the annotations' names, exact.
"""

from lib import trace_reduce


def read(args, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return None
    plane = max(
        planes, key=lambda p: sum(e[2] for e in trace_reduce.op_events(p))
    )
    busy = trace_reduce.merge_intervals(trace_reduce.op_events(plane))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    names = set(args["stages"])
    work = trace_reduce.merge_intervals(
        event
        for p in trace_reduce.host_planes(trace)
        for line in p["lines"]
        for event in line["events"] if event[0] in names
    )
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if not work or idle <= 0:
        return None
    covered, i = 0, 0
    for g0, g1 in gaps:  # both lists are sorted and disjoint
        while i < len(work) and work[i][1] <= g0:
            i += 1
        j = i
        while j < len(work) and work[j][0] < g1:
            covered += min(work[j][1], g1) - max(work[j][0], g0)
            j += 1
    return 100.0 * covered / idle
