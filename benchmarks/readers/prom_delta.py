"""Change of Prometheus samples over the window.

args: `samples`: [{"name": ..., "labels": {...}}, ...] summed;
`stat`: "sum" (the change itself) or "mean" (change of `<name>_sum`
over change of `<name>_count`); `scale`: multiplies the result.
"""


def _delta(ctx, name, labels):
    key = (name, tuple(sorted(labels.items())))
    before, after = ctx["prom_before"], ctx["prom_after"]
    if key not in after:
        return None
    return after[key] - before.get(key, 0.0)


def read(args, ctx):
    scale = float(args.get("scale", 1.0))
    total = count = 0.0
    found = False
    for s in args["samples"]:
        labels = s.get("labels", {})
        if args.get("stat", "sum") == "mean":
            d_sum = _delta(ctx, s["name"] + "_sum", labels)
            d_count = _delta(ctx, s["name"] + "_count", labels)
            if d_sum is None or d_count is None:
                continue
            total, count, found = total + d_sum, count + d_count, True
        else:
            d = _delta(ctx, s["name"], labels)
            if d is None:
                continue
            total, found = total + d, True
    if not found:
        return None
    if args.get("stat", "sum") == "mean":
        return scale * total / count if count > 0 else None
    return scale * total
