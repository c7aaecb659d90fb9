"""One reading over another.

args: `num`, `den`: each {"reader": ..., "args": {...}}; `den` may be a
list, whose readings are multiplied; `scale`.  Nothing where either
side finds nothing, or the denominator is 0.
"""

from lib.manifest import read_metric


def read(args, ctx):
    num = read_metric(args["num"], ctx)
    dens = args["den"] if isinstance(args["den"], list) else [args["den"]]
    den = 1.0
    for d in dens:
        value = read_metric(d, ctx)
        if value is None:
            return None
        den *= value
    if num is None or den == 0:
        return None
    return float(args.get("scale", 1.0)) * num / den
