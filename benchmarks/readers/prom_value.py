"""Prometheus samples as they stand at the window's end: what the
daemon did before the window (its restore at start) is in no change
over the window.

args: `samples`: [{"name": ..., "labels": {...}}, ...] summed; `scale`.
Nothing where no sample is there.
"""


def read(args, ctx):
    after = ctx["prom_after"]
    values = [
        after[key] for key in (
            (s["name"], tuple(sorted(s.get("labels", {}).items())))
            for s in args["samples"]
        ) if key in after
    ]
    if not values:
        return None
    return float(args.get("scale", 1.0)) * sum(values)
