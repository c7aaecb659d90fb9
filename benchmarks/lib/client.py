"""One client process of the generator: a few closed-loop callers, each
a thread with a connection of its own and one RPC outstanding.

Started by run.py as `python client.py <spec.json>`; driven by JSON
lines on stdin (`warm`, `window`, `answers`, `exit`), answers with one
JSON line each on stdout.  Payloads are encoded before the first
command is answered; the timed call sends bytes and keeps bytes
(identity serialisers), and responses are decoded only by `answers`,
after the window, which hands the rows on sampled keys to the parent's
judge in a file.  The process never imports the program or jax.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import judge, traffic, wire  # noqa: E402

CHANNEL_OPTIONS = [
    ("grpc.use_local_subchannel_pool", 1),  # a connection per caller
    ("grpc.max_send_message_length", 64 << 20),
    ("grpc.max_receive_message_length", 64 << 20),
]
RPC_TIMEOUT_S = 60.0


class Caller:
    def __init__(self, index: int, pool: traffic.Pool, target: str):
        import grpc

        self.index, self.pool = index, pool
        self.channel = grpc.insecure_channel(target, options=CHANNEL_OPTIONS)
        self.call = self.channel.unary_unary(wire.METHOD)
        self.next = 0
        self.wraps = 0
        self.records = []
        self.window_latencies = []
        self.window_sent_at = []
        self.window_items = 0

    def send_one(self):
        """One RPC: returns (record, seconds it took)."""
        import grpc

        if self.next >= len(self.pool.payloads):
            self.next, self.wraps = 0, self.wraps + 1
        i = self.next
        self.next += 1
        t_send = time.time()
        t0 = time.perf_counter()
        try:
            raw, err = self.call(self.pool.payloads[i], timeout=RPC_TIMEOUT_S), ""
        except grpc.RpcError as e:
            raw, err = None, f"{e.code()}: {e.details()}"
        took = time.perf_counter() - t0
        # The judge needs wall-clock bounds that really enclose the call:
        # a thread that loses the interpreter between two readings only
        # widens them.
        rec = judge.Record(i, t_send * 1e3, time.time() * 1e3, raw, err)
        self.records.append(rec)
        return rec, took

    def warm(self, n: int) -> None:
        for _ in range(n):
            self.send_one()

    def window(self, t_start: float, t_end: float) -> None:
        while time.time() < t_start:
            time.sleep(0.0005)
        while True:
            rec, took = self.send_one()
            if time.time() > t_end:
                return  # finished after the close: judged, not counted
            if rec.raw is not None:
                self.window_latencies.append(took)
                self.window_sent_at.append(rec.t_send_ms / 1e3)
                self.window_items += len(self.pool.ids[rec.pool_index])


def run_threads(callers, fn) -> None:
    threads = [threading.Thread(target=fn, args=(c,)) for c in callers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def main() -> int:
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    mix, seed = cfg["mix"], cfg["seed"]
    table = traffic.LimitTable(mix)
    callers = [
        Caller(
            c, traffic.build_pool(mix, seed, c, cfg["pool_rpcs"], table),
            cfg["target"],
        )
        for c in cfg["callers"]
    ]

    def reply(obj) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    reply({"ready": True, "pool_rpcs": cfg["pool_rpcs"]})
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "warm":
            run_threads(callers, lambda c: c.warm(cmd["rpcs"]))
            reply({"warmed": sum(len(c.records) for c in callers),
                   "errors": [r.error for c in callers for r in c.records
                              if r.error][:3]})
        elif cmd["cmd"] == "window":
            cpu0 = time.process_time()
            run_threads(
                callers, lambda c: c.window(cmd["t_start"], cmd["t_end"])
            )
            cpu = time.process_time() - cpu0
            lat = np.asarray(
                [x for c in callers for x in c.window_latencies], dtype=np.float64
            )
            sent = [t for c in callers for t in c.window_sent_at]
            np.save(cfg["latency_file"], np.stack([lat, np.asarray(sent)]))
            slowest = sorted(
                ((x, t) for c in callers
                 for x, t in zip(c.window_latencies, c.window_sent_at)),
                reverse=True,
            )[:5]
            reply({
                "slowest": [[t, x] for x, t in slowest],
                "rpcs": int(lat.size),
                "items": sum(c.window_items for c in callers),
                "cpu_s": cpu,
                "pool_wraps": sum(c.wraps for c in callers),
            })
        elif cmd["cmd"] == "answers":
            handed = judge.collect(
                wire.decode_response,
                {c.index: c.pool for c in callers},
                {c.index: c.records for c in callers},
                int(mix["items_per_rpc"]), seed,
                float(mix["judge"]["key_share"]),
                judge.hot_ids(int(mix["keys"]["ids"])),
            )
            counts = handed.pop("counts")
            np.savez(cmd["file"], **handed)
            reply({"counts": counts})
        elif cmd["cmd"] == "exit":
            break
    for c in callers:
        c.channel.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
