"""Start the daemon of `node100m_ledger0_h2front`: the node of
`node100m_ledger0`, reached through its native front.

The harness hands every launcher one client port, in
`GUBER_GRPC_ADDRESS`: the port its client processes and
`wait_first_answer` dial.  This launcher gives that port to the front
(`GUBER_H2_FAST_ADDRESS`: the C HTTP/2 listener, its group-commit
window and the columnar feeder, every setting at its default) and moves
the grpc-python listener to a free port that nobody dials.  Only the
front listens where the clients dial, so nothing falls back to
grpc-python in silence: a front that did not build or bind ends
`Daemon.start`, and with it the run.

Before anything compiles, the program has to be one whose front can
show the configuration's scope guarantee (no RPC of a window declined)
and tile an RPC's time: the `rpc_total` and `feeder_scatter` events and
the `/debug/vars` `h2_front` counters came together (PR 32).  A program
without them ends here, in seconds, with one line in its log.

Then `launch_daemon.main()`: the daemon as `python -m
gubernator_tpu.cmd.daemon` starts it, inside the accepted profiler
bracket.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from lib import launch_daemon  # noqa: E402
from lib.daemon_child import free_port  # noqa: E402

FRONT_EVENTS = ("rpc_total", "feeder_scatter")


def refused(why) -> int:
    print(f"[launch_h2front] REFUSED: {why}", flush=True)
    return 3


def main() -> int:
    sys.path.insert(0, ROOT)
    from gubernator_tpu.utils.native_events import STAGES

    missing = [s for s in FRONT_EVENTS if s not in STAGES.values()]
    if missing:
        return refused(
            f"this program's front publishes no {missing} events, so neither "
            f"its declined RPCs nor an RPC's time on it can be read")
    client_addr = os.environ["GUBER_GRPC_ADDRESS"]
    host = client_addr.rpartition(":")[0]
    os.environ["GUBER_H2_FAST_ADDRESS"] = client_addr
    os.environ["GUBER_GRPC_ADDRESS"] = f"{host}:{free_port()}"
    print(f"[launch_h2front] front on {client_addr} (the clients' port), "
          f"grpc-python on {os.environ['GUBER_GRPC_ADDRESS']}", flush=True)
    return launch_daemon.main()


if __name__ == "__main__":
    sys.exit(main())
