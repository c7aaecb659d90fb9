"""A number the harness itself counted in the window (`ctx["run"]`):
decisions, rpcs, window_s, client_cpu_s, client_processes,
daemon_cpu_s, compiles_in_window, rpc_p99_ms.

args: `key`; `scale`.
"""


def read(args, ctx):
    value = ctx["run"].get(args["key"])
    if value is None:
        return None
    return float(args.get("scale", 1.0)) * value
