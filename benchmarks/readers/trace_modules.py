"""Device time of the whole-program events whose names match.

args: `patterns`: glob patterns over the module events' names;
`stat`: "us_per_event" or "seconds".
"""

from lib import trace_reduce


def read(args, ctx):
    if ctx.get("trace") is None:
        return None
    seconds, count = trace_reduce.module_seconds(ctx["trace"], args["patterns"])
    if count == 0:
        return None
    if args.get("stat", "us_per_event") == "seconds":
        return seconds
    return 1e6 * seconds / count
