"""Idle share of the busiest chip over the traced slice, in %."""

from lib import trace_reduce


def read(args, ctx):
    if ctx.get("trace") is None:
        return None
    return trace_reduce.idle_pct(ctx["trace"])
