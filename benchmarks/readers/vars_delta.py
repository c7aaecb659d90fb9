"""Change of one number of /debug/vars over the window.

args: `path`: dotted path into the document; `scale`.
"""


def dig(doc, path):
    for part in path.split("."):
        if not isinstance(doc, dict) or part not in doc:
            return None
        doc = doc[part]
    return doc


def read(args, ctx):
    before = dig(ctx["vars_before"], args["path"])
    after = dig(ctx["vars_after"], args["path"])
    if before is None or after is None:
        return None
    return float(args.get("scale", 1.0)) * (after - before)
