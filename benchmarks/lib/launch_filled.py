"""Start the daemon of `node100m_ledger0_filled`: a node restarted
from its snapshot, whose table is full before the first request.

The program's binary (`cmd.daemon.main`) takes a Loader only from code,
as upstream's does (`DaemonConfig.Loader`), so this launcher does what
that binary does and hands `spawn_daemon` a Loader: the seeded snapshot
(lib/snapshot.py), as many rows as the table has (`GUBER_CACHE_SIZE`),
streamed in columns.  The restore is the program's own —
`Daemon.start` → `engine.load(loader)` — never a write into the state
arrays.  The Loader has columns only: an engine that asks for `load()`,
the per-item walk, gets an error that says so, at once, instead of 1e8
Python iterations.

As soon as the daemon serves, and before it is measured, the node has
to show that it is what the configuration says, or this process ends
non-zero with one line in its log and the harness' run fails:
  (a) every row of the table is occupied, and as many rows went
      through the restore;
  (b) a seeded sample of restored keys (lib/snapshot.py `sample`: from
      the newest-loaded end, which the run's evictions never reach),
      asked with `hits = 0` through the gRPC listener, answers exactly
      — status, limit, remaining, reset_time — as the benchmark's own
      reference (lib/lru_reference.py over lib/spec.py) loaded with the
      same rows.

With BENCH_TRACE_DIR set, the profiler brackets a slice of the window
as `launch_daemon.py` does it.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import sys
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from lib import launch_daemon, spec, wire  # noqa: E402
from lib.lru_reference import LRUReference  # noqa: E402
from lib.snapshot import Snapshot  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmarks", "configs", "node100m_ledger0_filled.json")
AHEAD = 3  # chunks made on other threads while one is being restored
ITEMS_PER_RPC = 1000


class Refused(Exception):
    """The node is not the configuration's: it must not be measured."""


class SnapshotLoader:
    """The program's Loader protocol (gubernator_tpu/store.py) over a
    Snapshot: columns only."""

    def __init__(self, snapshot: Snapshot, rows: int):
        self.snapshot, self.rows = snapshot, rows

    def load(self):
        raise Refused(
            "the engine asked this Loader for load(), the per-item walk: it "
            "lacks the columnar restore (engine.load over "
            "Loader.load_columns), and 1e8 rows are not restored one "
            "CacheItem at a time"
        )

    def load_columns(self):
        from gubernator_tpu.store import ItemColumns

        make = self.snapshot.columns
        with ThreadPoolExecutor(AHEAD, thread_name_prefix="bench-snapshot") as pool:
            pending = deque()
            for r in self.snapshot.chunks(self.rows):
                pending.append(pool.submit(make, r))
                if len(pending) > AHEAD:
                    yield ItemColumns(**pending.popleft().result())
            while pending:
                yield ItemColumns(**pending.popleft().result())

    def save(self, items) -> None:
        """Nothing of a run is kept: the next one restores the seed's
        snapshot again.  (`items` is a generator; it is not walked.)"""


class RestoreCheck:
    """(a) and (b) of the module's docstring.  Everything that needs
    no daemon — the sample, the reference loaded with its rows, the
    encoded status queries — is made before the daemon starts, so that
    the node is asked and judged in the first second it serves."""

    def __init__(self, snapshot: Snapshot, rows: int):
        self.rows = rows
        sample = snapshot.sample()
        self.ref = LRUReference(rows)
        self.ref.load(snapshot.states(sample), snapshot.dated_ms)
        cols = snapshot.columns(sample)
        self.keys = [snapshot.key(r) for r in sample.tolist()]
        self.inputs = [
            spec.SpecInput(hits=0, limit=li, duration=du, burst=bu, algorithm=al)
            for li, du, bu, al in zip(
                cols["limit"].tolist(), cols["duration"].tolist(),
                cols["burst"].tolist(), cols["algo"].tolist())
        ]
        prefix = wire.name_prefix(snapshot.name)
        self.payloads = [
            wire.encode_request([
                (prefix, snapshot.unique_key(r),
                 wire.item_suffix(0, q.limit, q.duration, q.algorithm, 0, q.burst))
                for r, q in zip(sample[lo:lo + ITEMS_PER_RPC].tolist(),
                                self.inputs[lo:lo + ITEMS_PER_RPC])
            ])
            for lo in range(0, len(sample), ITEMS_PER_RPC)
        ]

    def run(self, device: dict, grpc_addr: str) -> None:
        """`device`: that block of the daemon's /debug/vars.  Raises
        Refused."""
        import grpc

        occupied = device.get("rows_occupied")
        loaded = device["counters"].get("rows_loaded_total")
        if occupied != self.rows or loaded != self.rows:
            raise Refused(
                f"table not full: {occupied} of {self.rows} rows occupied, "
                f"{loaded} rows restored")
        asked = []
        with grpc.insecure_channel(grpc_addr) as channel:
            call = channel.unary_unary(wire.METHOD)
            for payload in self.payloads:
                t_send = time.time_ns() // 1_000_000
                raw = call(payload, timeout=120.0)
                asked.append((t_send, time.time_ns() // 1_000_000, raw))
        wrong = []
        for n, (t_send, t_recv, raw) in enumerate(asked):
            lo = n * ITEMS_PER_RPC
            inputs = self.inputs[lo:lo + ITEMS_PER_RPC]
            answers = wire.decode_response(raw)
            if len(answers) != len(inputs):
                raise Refused(
                    f"{len(answers)} answers to {len(inputs)} status queries")
            # The node's clock for this RPC, from its first leaky answer
            # (reset_time = now + (limit - remaining) * rate); a token
            # bucket's status answer does not depend on it.
            now = t_recv
            for q, a in zip(inputs, answers):
                if q.algorithm == spec.Algorithm.LEAKY_BUCKET:
                    rate = int(q.duration / q.limit)
                    now = a.reset_time - (q.limit - a.remaining) * rate
                    break
            if not t_send - 1 <= now <= t_recv + 2:
                raise Refused(
                    f"a restored leaky bucket's reset_time puts the node's "
                    f"clock at {now}, outside the RPC's {t_send}..{t_recv}")
            for key, q, a in zip(self.keys[lo:], inputs, answers):
                want = self.ref.get_rate_limit(key, q, now)
                want = [want.status, want.limit, want.remaining, want.reset_time]
                got = [a.status, a.limit, a.remaining, a.reset_time]
                if a.error or got != want:
                    wrong.append(
                        {"key": key, "error": a.error, "got": got, "want": want})
        if wrong:
            raise Refused(
                f"{len(wrong)} of {len(self.keys)} restored keys do not answer "
                f"from their restored state; first: {json.dumps(wrong[:3])}")
        print(f"[launch_filled] restored: {self.rows} rows occupied and "
              f"loaded, {len(self.keys)} sampled keys answer as the reference",
              flush=True)


def refused(why) -> int:
    print(f"[launch_filled] REFUSED: {why}", flush=True)
    return 3


def main() -> int:
    sys.path.insert(0, ROOT)
    try:
        import gubernator_tpu.store

        gubernator_tpu.store.ItemColumns
    except (ImportError, AttributeError) as e:
        # before anything compiles: a program without the columnar
        # restore ends here, in seconds
        return refused(
            f"this program's Loader protocol has no columns "
            f"(gubernator_tpu.store.ItemColumns: {e}); 1e8 rows are not "
            f"restored one CacheItem at a time")
    trace_dir = os.environ.get("BENCH_TRACE_DIR", "")
    if trace_dir:
        threading.Thread(
            target=launch_daemon.trace_slice,
            args=(trace_dir, os.environ["GUBER_HTTP_ADDRESS"],
                  float(os.environ["BENCH_TRACE_SECONDS"])),
            daemon=True, name="bench-trace",
        ).start()
    with open(CONFIG) as f:
        block = json.load(f)["snapshot"]
    rows = int(os.environ["GUBER_CACHE_SIZE"])
    snapshot = Snapshot(block, rows, time.time_ns() // 1_000_000)
    check = RestoreCheck(snapshot, rows)

    # -- as gubernator_tpu.cmd.daemon.main, with a Loader --------------
    from gubernator_tpu.config import setup_daemon_config
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.utils.logging_setup import configure_logging
    from gubernator_tpu.utils.tracing import init_tracing, shutdown_tracing

    configure_logging(debug=False)
    init_tracing()
    conf = setup_daemon_config(None)
    try:
        daemon = spawn_daemon(conf, loader=SnapshotLoader(snapshot, rows))
    except Refused as e:
        return refused(e)
    logging.getLogger("gubernator_tpu").info(
        "gubernator_tpu listening: grpc=%s http=%s, %d rows restored",
        daemon.grpc_address, daemon.http_address, rows,
    )
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    rc = 0
    try:
        check.run(
            launch_daemon._device_vars(daemon.http_address),
            daemon.grpc_address,
        )
        stop.wait()
    except Refused as e:
        rc = refused(e)
    daemon.close()
    shutdown_tracing()
    return rc


if __name__ == "__main__":
    sys.exit(main())
