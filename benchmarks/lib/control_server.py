"""The control: the reference put in the daemon's place, with one of
the configuration's guarantees broken, to show that the comparison
which decides `correct` fails when it should.

    BENCH_CONTROL_FAULT=stale   a key asked again within 50 ms gets its
                                last answer back and the hit is not
                                applied: a stale, approximate answer
                                where the configuration says exact and
                                per-key atomic (it over-admits).
    BENCH_CONTROL_FAULT=none    the reference as it is; must pass.

Never a measurement: it holds no chip and answers from a Python dict.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from concurrent import futures

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import spec, wire  # noqa: E402

STALE_MS = 50


class Reference:
    def __init__(self, fault: str):
        self.fault = fault
        self.lock = threading.Lock()
        self.states: dict = {}
        self.last: dict = {}  # key -> (clock ms, answer row)

    def serve(self, raw: bytes, _context=None) -> bytes:
        rows = []
        with self.lock:
            now = time.time_ns() // 1_000_000
            for r in wire.decode_request(raw):
                key = r.name + "_" + r.unique_key
                seen = self.last.get(key)
                if (self.fault == "stale" and seen is not None
                        and now - seen[0] < STALE_MS):
                    rows.append(seen[1])
                    continue
                state, out = spec.apply_spec(
                    self.states.get(key),
                    spec.SpecInput(
                        hits=r.hits, limit=r.limit, duration=r.duration,
                        burst=r.burst, algorithm=r.algorithm,
                        behavior=r.behavior,
                    ),
                    now,
                )
                if state is None:
                    self.states.pop(key, None)
                else:
                    self.states[key] = state
                row = (out.status, out.limit, out.remaining, out.reset_time)
                self.last[key] = (now, row)
                rows.append(row)
        return wire.encode_response(rows)


def main() -> int:
    import grpc

    ref = Reference(os.environ.get("BENCH_CONTROL_FAULT", "none"))
    handler = grpc.method_handlers_generic_handler(
        "pb.gubernator.V1",
        {"GetRateLimits": grpc.unary_unary_rpc_method_handler(ref.serve)},
    )
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=128),
        options=[("grpc.max_receive_message_length", 64 << 20)],
    )
    server.add_generic_rpc_handlers((handler,))
    server.add_insecure_port(os.environ["GUBER_GRPC_ADDRESS"])
    server.start()
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    server.stop(0).wait()
    return 0


if __name__ == "__main__":
    sys.exit(main())
