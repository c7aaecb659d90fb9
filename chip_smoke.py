#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts and
answers correctly on the attached TPU.

    python3 chip_smoke.py            # one chip or four; exits 0 only on a TPU

What it does, through the entry points a user would call:

1. Starts `python -m gubernator_tpu.cmd.daemon` (the README quick
   start) with `GUBER_CACHE_SIZE=100000000` — BASELINE config 4's
   100 M bucket rows, 4.8 GB of device state — every other default
   left alone, and `JAX_PLATFORMS=tpu` so jax itself refuses to start
   without a chip.
2. Drives its gRPC listener (1,000-item `GetRateLimits` RPCs) and its
   HTTP gateway (`POST /v1/GetRateLimits`) from this process with
   ≥ 1,000,000 distinct keys, token and leaky mixed, finite limits,
   each key hit more than once; then one batch each of duplicate hot
   keys, RESET_REMAINING, DURATION_IS_GREGORIAN, GLOBAL, SKETCH and
   short-TTL keys.  Every token-bucket answer is compared with
   `gubernator_tpu/models/spec.py` (status and remaining equal), and
   the daemon's `/debug/vars` `device` block must say platform `tpu`
   and show device dispatches growing with the traffic.
3. Stops that daemon and starts a second one against the same compile
   cache: the warm start must compile nothing.  The second one also
   opens the native front (`GUBER_H2_FAST_ADDRESS`): its start line
   and `/debug/vars` `h2_front` must say what it serves with, and 200
   single-item RPCs through it must answer as the spec — status, limit
   and remaining equal, one `reset_time` a bucket, inside the interval
   of the RPC that made the bucket — with none refused.
4. With the chip released, runs a `DecisionEngine` on it in a child
   (`--parity-child`) under a frozen clock: a seeded mixed stream
   through `get_rate_limits` and `apply_columnar`, bit-equal to the
   spec on every field, plus an explicit expiry sweep.

One process uses the chip at a time: this parent imports the package
(which imports jax) but never initializes a backend, starts one child
at a time and waits for it to exit before the next.  Any phase that
fails raises, so the exit code is non-zero and no result line is
printed.  A passing run ends with two JSON lines on stdout: the full
summary (also written to `chiprun_out/chip_smoke/summary.json`), then,
last, the result line with exactly these keys and the device as jax
reported it to the daemon:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.

`--rehearse-cpu` is the only way to run it on the CPU: a tiny size,
`"platform": "cpu"` in the result, for debugging the script itself.
`--rows` / `--keys` cut the size by name; every cut is printed and
listed under `"cuts"`.
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

FULL_ROWS = 100_000_000  # BASELINE config 4; 48 B/row = 4.8 GB
FULL_KEYS = 1_000_000
BATCH = 1000  # upstream's hard cap on items per RPC
ROW_BYTES = 48  # BucketState, 12 int32/uint32 columns
DEADLINE_S = 1150.0  # the contract allows 1200 s, compilation included
NATIVE_LIBS = ("intern_table", "wire_codec", "h2_server", "h2_client")
HOUR_MS = 3_600_000

TOKEN, LEAKY = 0, 1
UNDER, OVER = 0, 1
GLOBAL, GREGORIAN, RESET_REMAINING, SKETCH = 2, 4, 8, 32
GREGORIAN_YEARS = 5

# The `# guberlint: shapes` sites by jitted function name, for the
# checklist the summary prints (which program families this run
# compiled on the chip, by /debug/vars `device.compiles.programs`).
SHAPE_SITES = {
    "ops/bucket_kernel.py": (
        "_clear_occupied_impl",
        "_fused_step_core", "_multi_fused_core", "_uniform_step_core",
        "_multi_uniform_core", "_collapsed_step_core",
        "_load_slots_impl", "gather_page_words", "_load_page_words_impl",
    ),
    "ops/expiry.py": (
        "sweep_window_scan", "sweep_window_commit", "sweep_expired",
    ),
    "ops/sketch.py": ("_rotate", "sketch_step"),
    "core/pump.py": ("stack_rounds",),
    "core/readback.py": ("stack_outputs",),
    "parallel/sharded_engine.py": (
        "local_packed_fused", "local_collapsed_fused", "local_clear",
        "local_merge", "sharded_sweep_scan", "flat_packed_fused",
        "flat_collapsed_fused",
    ),
}


class SmokeFailure(Exception):
    """A check that did not hold.  Never caught: the run ends non-zero."""


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def result_line(summary: dict) -> str:
    """The last stdout line: `ok` and `device` and nothing else — the
    checker that reads it takes no other key."""
    dev = summary["device"]
    return json.dumps({
        "ok": summary["ok"],
        "device": {
            "platform": str(dev["platform"]),
            "kind": str(dev["kind"]),
            "count": int(dev["count"]),
        },
    })


_T0 = time.monotonic()
_children: list = []  # live child processes, for the deadline thread


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    proc.wait()


def _deadline_thread() -> None:
    time.sleep(DEADLINE_S)
    print(
        f"[chip_smoke] FAILED: exceeded its {DEADLINE_S:.0f}s deadline",
        flush=True,
    )
    for p in list(_children):
        _kill(p)
    os._exit(3)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def child_env(rehearse: bool) -> dict:
    """The ambient environment minus every GUBER_* setting (defaults
    are what is under test), with jax told which platform to insist
    on."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("GUBER_")}
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    if rehearse:
        # The CPU backend turns the step pump off by default; the
        # rehearsal exists to debug the path the chip takes.
        env["GUBER_PUMP"] = "1"
        env["GUBER_PUMP_SCAN"] = "1"
    return env


def named_platform_found() -> str:
    """What jax resolves to when left to choose, asked in a throwaway
    child AFTER a TPU child has already failed — only to name the
    platform in the failure message."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); "
         "print(d[0].platform, d[0].device_kind, len(d))"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    lines = out.stdout.strip().splitlines()
    return lines[-1] if lines else f"nothing (rc={out.returncode})"


# ----------------------------------------------------------------------
# Native libraries


def load_natives() -> dict:
    """Build (hash-keyed, from the committed .cpp files) and dlopen the
    four native libraries; any that is missing fails the run."""
    from gubernator_tpu.core.native_build import ensure_built

    loaded = {}
    for stem in NATIVE_LIBS:
        so = ensure_built(stem)
        check(so is not None, f"native library {stem} did not build")
        ctypes.CDLL(str(so))
        loaded[stem] = True
    return loaded


# ----------------------------------------------------------------------
# The daemon child and its two listeners


class DaemonChild:
    def __init__(self, tag: str, env: dict, rows: int, front: bool = False):
        self.tag = tag
        self.grpc_addr = f"127.0.0.1:{free_port()}"
        self.http_addr = f"127.0.0.1:{free_port()}"
        self.env = dict(
            env,
            GUBER_CACHE_SIZE=str(rows),
            GUBER_GRPC_ADDRESS=self.grpc_addr,
            GUBER_HTTP_ADDRESS=self.http_addr,
        )
        self.front_addr = ""
        if front:  # the native front beside the two listeners
            self.front_addr = f"127.0.0.1:{free_port()}"
            self.env["GUBER_H2_FAST_ADDRESS"] = self.front_addr
        self.log_path = os.path.join(OUT_DIR, f"daemon_{tag}.log")
        self.proc = None
        self.t_spawn = 0.0

    def start(self, timeout: float) -> float:
        """Spawn `python -m gubernator_tpu.cmd.daemon`; returns seconds
        from spawn to its first answered GetRateLimits."""
        import grpc

        from gubernator_tpu.net.pb import gubernator_pb2 as pb

        self.t_spawn = time.monotonic()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "gubernator_tpu.cmd.daemon"],
                cwd=ROOT, env=self.env, stdout=log, stderr=subprocess.STDOUT,
                stdin=subprocess.DEVNULL, start_new_session=True,
            )
        _children.append(self.proc)
        req = pb.GetRateLimitsReq(
            requests=[pb.RateLimitReq(
                name="smoke_first", unique_key="answer", hits=1, limit=10,
                duration=HOUR_MS,
            )]
        )
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"daemon ({self.tag}) died with rc={rc} before "
                    f"answering:\n{self.log_tail()}"
                )
            check(
                time.monotonic() - self.t_spawn < timeout,
                f"daemon ({self.tag}) listener never came up in "
                f"{timeout:.0f}s:\n{self.log_tail()}",
            )
            try:
                with grpc.insecure_channel(self.grpc_addr) as ch:
                    resp = rpc_call(ch)(req, timeout=5.0)
            except grpc.RpcError:
                time.sleep(0.5)
                continue
            check(
                len(resp.responses) == 1 and not resp.responses[0].error
                and resp.responses[0].remaining == 9,
                f"first answer wrong: {resp}",
            )
            return time.monotonic() - self.t_spawn

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return "(no log)"

    def debug_vars(self) -> dict:
        with urllib.request.urlopen(
            f"http://{self.http_addr}/debug/vars", timeout=60
        ) as r:
            return json.loads(r.read())

    def metrics_text(self) -> str:
        with urllib.request.urlopen(
            f"http://{self.http_addr}/metrics", timeout=60
        ) as r:
            return r.read().decode()

    def stop(self) -> None:
        """SIGTERM, wait for a clean exit (the chip is released only
        when the process is gone)."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                _kill(self.proc)
                raise SmokeFailure(
                    f"daemon ({self.tag}) ignored SIGTERM for 120s"
                )
        rc = self.proc.returncode
        _children.remove(self.proc)
        self.proc = None
        check(rc == 0, f"daemon ({self.tag}) exited rc={rc}:\n{self.log_tail()}")

    def kill(self) -> None:
        if self.proc is not None:
            _kill(self.proc)
            _children.remove(self.proc)
            self.proc = None


def rpc_call(channel):
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    return channel.unary_unary(
        "/pb.gubernator.V1/GetRateLimits",
        request_serializer=pb.GetRateLimitsReq.SerializeToString,
        response_deserializer=pb.GetRateLimitsResp.FromString,
    )


def http_call(http_addr: str, items: list) -> list:
    """POST /v1/GetRateLimits; returns [(status, remaining, error)]."""
    body = json.dumps({"requests": [
        {
            "name": it["name"], "unique_key": it["unique_key"],
            "hits": str(it["hits"]), "limit": str(it["limit"]),
            "duration": str(it["duration"]), "algorithm": it["algorithm"],
            "behavior": it["behavior"], "burst": str(it["burst"]),
        }
        for it in items
    ]}).encode()
    req = urllib.request.Request(
        f"http://{http_addr}/v1/GetRateLimits", data=body,
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        out = json.loads(r.read())
    return [
        (
            OVER if m.get("status") in ("OVER_LIMIT", 1) else UNDER,
            int(m.get("remaining", 0)),
            m.get("error", ""),
        )
        for m in out["responses"]
    ]


def grpc_call(call, items: list) -> list:
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    resp = call(
        pb.GetRateLimitsReq(
            requests=[pb.RateLimitReq(**it) for it in items]
        ),
        timeout=120.0,
    )
    return [(m.status, m.remaining, m.error) for m in resp.responses]


# ----------------------------------------------------------------------
# The plain reference


class SpecShadow:
    """key → models/spec.py SlotState, applied in arrival order."""

    def __init__(self):
        from gubernator_tpu import gregorian
        from gubernator_tpu.models import spec

        self.buckets: dict = {}
        self._greg, self._spec = gregorian, spec

    def apply(self, it: dict, now_ms: int):
        greg_dur = greg_exp = 0
        if it["behavior"] & GREGORIAN:
            now_dt = self._greg.dt_from_ms(now_ms)
            greg_dur = self._greg.gregorian_duration(now_dt, it["duration"])
            greg_exp = self._greg.gregorian_expiration(now_dt, it["duration"])
        key = it["name"] + "_" + it["unique_key"]
        state, out = self._spec.apply_spec(
            self.buckets.get(key),
            self._spec.SpecInput(
                hits=it["hits"], limit=it["limit"], duration=it["duration"],
                burst=it["burst"], algorithm=it["algorithm"],
                behavior=it["behavior"], greg_duration=greg_dur,
                greg_expire=greg_exp,
            ),
            now_ms,
        )
        if state is None:
            self.buckets.pop(key, None)
        else:
            self.buckets[key] = state
        return out


class Tally:
    """What one driver thread counted."""

    def __init__(self):
        self.decisions = self.over = self.errors = 0
        self.token_compared = self.leaky_checked = 0
        self.grpc_rpcs = self.http_posts = 0
        self.mismatches: list = []

    def merge(self, other: "Tally") -> None:
        for k, v in vars(other).items():
            if isinstance(v, list):
                getattr(self, k).extend(v)
            else:
                setattr(self, k, getattr(self, k) + v)


def judge(items, answers, shadow: SpecShadow, tally: Tally, now_ms: int):
    """Token rows: status and remaining equal to the spec.  Leaky rows
    leak by the daemon's own clock, so they are held to the invariants
    a run can show (no error, a status, 0 ≤ remaining ≤ burst)."""
    check(len(answers) == len(items), "answer count != request count")
    for it, (status, remaining, error) in zip(items, answers):
        tally.decisions += 1
        if error:
            tally.errors += 1
            tally.mismatches.append((it, "error", error))
            continue
        tally.over += status == OVER
        if it["algorithm"] == TOKEN:
            want = shadow.apply(it, now_ms)
            tally.token_compared += 1
            if (int(want.status), want.remaining) != (status, remaining):
                tally.mismatches.append(
                    (it, (status, remaining),
                     (int(want.status), want.remaining))
                )
        else:
            tally.leaky_checked += 1
            cap = it["burst"] or it["limit"]
            if status not in (UNDER, OVER) or not 0 <= remaining <= cap:
                tally.mismatches.append((it, (status, remaining), "leaky"))


def item(name, key, *, hits, limit, algorithm=TOKEN, behavior=0,
         duration=HOUR_MS, burst=0) -> dict:
    return {
        "name": name, "unique_key": key, "hits": hits, "limit": limit,
        "duration": duration, "algorithm": algorithm, "behavior": behavior,
        "burst": burst,
    }


# ----------------------------------------------------------------------
# Phase: the wide traffic


def wide_block(b: int, hits: int) -> list:
    """Block b = keys [b*1000, b*1000+1000).  Every fourth block is one
    limit config across the batch (the uniform narrow format); the rest
    mix token and leaky lane by lane (the general packed format)."""
    if b % 4 == 3:
        return [
            item("smoke_uni", f"k{b * BATCH + i}", hits=hits, limit=2)
            for i in range(BATCH)
        ]
    return [
        item(
            "smoke_mix", f"k{b * BATCH + i}", hits=hits, limit=2,
            algorithm=(b * BATCH + i) & 1,
            burst=3 if (b * BATCH + i) & 1 else 0,
        )
        for i in range(BATCH)
    ]


def drive_wide(daemon: DaemonChild, n_keys: int, n_threads: int) -> Tally:
    """Three passes over every key, per-key order kept by giving each
    block of keys to one thread: hits=1 (admitted), hits=2 (more than
    is left: OVER_LIMIT without consuming), then hits=1 again on a
    tenth of the blocks.  One block in twenty goes through the HTTP
    gateway, the rest through gRPC."""
    import grpc

    n_blocks = (n_keys + BATCH - 1) // BATCH
    tallies = [Tally() for _ in range(n_threads)]
    errors: list = []

    def worker(tid: int) -> None:
        try:
            shadow, tally = SpecShadow(), tallies[tid]
            with grpc.insecure_channel(daemon.grpc_addr) as ch:
                call = rpc_call(ch)
                for hits, stride in ((1, 1), (2, 1), (1, 10)):
                    for b in range(tid, n_blocks, n_threads):
                        if b % stride:
                            continue
                        items = wide_block(b, hits)
                        now_ms = int(time.time() * 1000)
                        if b % 20 == 7:
                            answers = http_call(daemon.http_addr, items)
                            tally.http_posts += 1
                        else:
                            answers = grpc_call(call, items)
                            tally.grpc_rpcs += 1
                        judge(items, answers, shadow, tally, now_ms)
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    total = Tally()
    for t in tallies:
        total.merge(t)
    return total


# ----------------------------------------------------------------------
# Phase: one batch of each special family


def drive_specials(daemon: DaemonChild) -> dict:
    import grpc

    out: dict = {}
    shadow = SpecShadow()
    with grpc.insecure_channel(daemon.grpc_addr) as ch:
        call = rpc_call(ch)

        def run(name: str, items: list) -> Tally:
            tally = Tally()
            now_ms = int(time.time() * 1000)
            judge(items, grpc_call(call, items), shadow, tally, now_ms)
            check(
                not tally.mismatches,
                f"{name}: {len(tally.mismatches)} rows differ from "
                f"models/spec.py, first: {tally.mismatches[:3]}",
            )
            out[name] = {"rows": tally.decisions, "over": tally.over}
            return tally

        # Duplicate hot key: 1000 hits on one bucket of 600 in ONE
        # batch (the collapsed program), then again (the ledger may
        # hold the key by now; the answers must not change).
        dup = [item("smoke_dup", "hot", hits=1, limit=600)] * BATCH
        t = run("duplicates", dup)
        check(t.over == 400, f"duplicates: {t.over} OVER_LIMIT, want 400")
        t = run("duplicates_again", dup)
        check(t.over == BATCH, "duplicates_again: every row must be OVER")

        # RESET_REMAINING on live buckets.
        base = [
            item("smoke_rst", f"r{i}", hits=3, limit=5) for i in range(BATCH)
        ]
        run("reset_seed", base)
        run("reset_remaining", [
            dict(it, hits=1, behavior=RESET_REMAINING) for it in base
        ])
        run("reset_after", [dict(it, hits=1) for it in base])

        # DURATION_IS_GREGORIAN (duration is the interval enum).
        greg = [
            item("smoke_greg", f"g{i}", hits=1 + (i & 1), limit=2,
                 behavior=GREGORIAN, duration=GREGORIAN_YEARS,
                 algorithm=i & 1, burst=0)
            for i in range(BATCH)
        ]
        run("gregorian", greg)
        run("gregorian_again", greg)

        # GLOBAL on a single node: the owner path.
        glob = [
            item("smoke_glob", f"o{i}", hits=2, limit=3, behavior=GLOBAL)
            for i in range(BATCH)
        ]
        run("global", glob)
        run("global_again", glob)

        # Short TTL: the bucket expires between the two batches and the
        # second starts a fresh one (the kernel's liveness check).
        ttl = [
            item("smoke_ttl", f"t{i}", hits=1, limit=1, duration=1)
            for i in range(BATCH)
        ]
        run("ttl", ttl)
        time.sleep(0.05)
        t = run("ttl_expired", ttl)
        check(t.over == 0, "ttl_expired: an expired bucket answered OVER")

        # SKETCH: the device-resident count-min sketch.  Not in the
        # spec; its guarantee is one-sided (never under-counts).
        sk = [
            item("smoke_sketch", f"s{i}", hits=1, limit=5, behavior=SKETCH,
                 duration=60_000)
            for i in range(BATCH)
        ]
        first = grpc_call(call, sk)
        check(
            all(not e and s == UNDER and 0 <= r <= 4 for s, r, e in first),
            f"sketch: first batch not UNDER with remaining ≤ 4: {first[:3]}",
        )
        second = grpc_call(call, [dict(it, hits=6) for it in sk])
        check(
            all(not e and s == OVER for s, _r, e in second),
            f"sketch: 6 more hits against limit 5 not OVER: {second[:3]}",
        )
        out["sketch"] = {"rows": 2 * BATCH, "over": BATCH}
    return out


# ----------------------------------------------------------------------
# Phase: the native front (GUBER_H2_FAST_ADDRESS)


def drive_front(daemon: DaemonChild, n_keys: int = 40, hits_a_key: int = 5) -> dict:
    """Single-item RPCs through the native front, one caller: every
    answer as models/spec.py gives it, a bucket's reset_time the same
    in all its answers and inside the interval of the RPC that made it
    (token buckets of limit 3, so the last two hits of a key answer
    OVER_LIMIT).  The start line and /debug/vars must say what serves."""
    import grpc

    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    check("native h2 front serving: " in daemon.log_tail(1 << 20),
          "the start log does not say what the front serves with")
    before = daemon.debug_vars()["h2_front"]
    settings = before["settings"]
    say(f"front: {settings}")
    check(settings["address"] == daemon.front_addr and settings["feeder"]
          and settings["event_ring"], f"front settings: {settings}")
    shadow, wrong, resets = SpecShadow(), [], {}
    with grpc.insecure_channel(daemon.front_addr) as ch:
        call = rpc_call(ch)
        for n in range(hits_a_key):
            for k in range(n_keys):
                it = item("smoke_front", f"f{k}", hits=1, limit=3)
                t_send = int(time.time() * 1000)
                (m,) = call(pb.GetRateLimitsReq(
                    requests=[pb.RateLimitReq(**it)]), timeout=30.0).responses
                t_recv = int(time.time() * 1000)
                want = shadow.apply(it, t_send)
                lo, hi = resets.setdefault(
                    k, (t_send + HOUR_MS - 1, t_recv + HOUR_MS + 2))
                if m.error or (m.status, m.limit, m.remaining) != (
                        int(want.status), want.limit, want.remaining) or not (
                        lo <= m.reset_time <= hi):
                    wrong.append((it["unique_key"], n, str(m).replace("\n", " ")))
                resets[k] = (m.reset_time, m.reset_time)
    rpcs = n_keys * hits_a_key
    check(not wrong, f"front: {len(wrong)} of {rpcs} answers differ from "
                     f"models/spec.py, first: {wrong[:3]}")
    after = daemon.debug_vars()["h2_front"]
    moved = {k: after[k] - before[k] for k in
             ("rpcs", "errors", "declined_rpcs", "windows", "feeder_rpcs",
              "plane_rpcs", "ring_dropped")}
    check(moved["rpcs"] == rpcs and moved["errors"] == 0,
          f"front counters for {rpcs} RPCs: {moved}")
    return {"rpcs": rpcs, "over": 2 * n_keys, "settings": settings,
            "counters": moved}


# ----------------------------------------------------------------------
# Checks on what the daemon says it serves on


def check_device(dev: dict, want_platform: str, rows: int) -> None:
    check(
        dev["platform"] == want_platform,
        f"daemon serves on platform {dev['platform']!r} "
        f"({dev['device_kind']}), not {want_platform!r}",
    )
    check(not dev["cpu_unrequested"], "backend fell to the CPU unasked")
    n = dev["device_count"]
    want_engine = "DecisionEngine" if n == 1 else "ShardedDecisionEngine"
    check(
        dev["engine"] == want_engine,
        f"{n} device(s) but engine {dev['engine']}",
    )
    check(
        dev["rows"] == rows // n * n,
        f"rows resident {dev['rows']} != {rows // n * n}",
    )
    check(
        dev["native"]["intern_table"] and dev["native"]["wire_codec"],
        f"daemon lost a native tier: {dev['native']}",
    )
    if want_platform == "tpu":
        for name, v in dev["probes"].items():
            check(v["ok"], f"{name} probe said no: {v['reason']}")
        if n == 1:
            check(dev["pump"] and dev["pump_scan"], "pump/scan off on TPU")
        # The state is resident where it should be: every device holds
        # its share and none holds a second capacity-sized copy.
        share = rows // n * ROW_BYTES
        for m in dev["memory"]:
            check(
                m["bytes_in_use"] is not None
                and share <= m["bytes_in_use"] < 1.5 * share + (1 << 30),
                f"device {m['id']} bytes_in_use {m['bytes_in_use']} vs "
                f"state share {share}",
            )


def shape_checklist(programs: dict) -> dict:
    return {
        where: {name: programs.get(f"jit({name})", 0) for name in names}
        for where, names in SHAPE_SITES.items()
    }


# ----------------------------------------------------------------------
# Phase: frozen-clock parity, in a child that owns the chip


def parity_child(args) -> int:
    """`--parity-child`: a DecisionEngine on the default device under a
    frozen clock; every response field bit-equal to models/spec.py."""
    import jax
    import numpy as np

    from gubernator_tpu import Algorithm, RateLimitReq
    from gubernator_tpu.clock import Clock
    from gubernator_tpu.core import device_info
    from gubernator_tpu.core.engine import DecisionEngine
    from gubernator_tpu.utils import jit_guard

    jit_guard.install()
    rng = random.Random(args.seed)
    clock = Clock().freeze()
    engine = DecisionEngine(
        capacity=args.rows, clock=clock, device=jax.devices()[0]
    )
    shadow = SpecShadow()
    rows = 0
    mismatches: list = []

    def compare(it, got, want, how):
        nonlocal rows
        rows += 1
        if got != (int(want.status), want.limit, want.remaining,
                   want.reset_time):
            mismatches.append({
                "via": how, "req": it, "got": got,
                "want": (int(want.status), want.limit, want.remaining,
                         want.reset_time),
            })

    def to_req(it) -> RateLimitReq:
        return RateLimitReq(
            name=it["name"], unique_key=it["unique_key"], hits=it["hits"],
            limit=it["limit"], duration=it["duration"],
            algorithm=Algorithm(it["algorithm"]), behavior=it["behavior"],
            burst=it["burst"],
        )

    def random_item(keys) -> dict:
        behavior = RESET_REMAINING if rng.random() < 0.15 else 0
        duration = rng.choice([1, 5, 100, 1000, 9000, 30000, 3_600_000])
        if rng.random() < 0.2:
            behavior |= GREGORIAN
            duration = rng.choice([0, 1, 2, 3, 4, 5])
        return item(
            "parity", rng.choice(keys),
            hits=rng.choice([-3, -1, 0, 1, 1, 1, 2, 5, 10, 100]),
            limit=rng.choice([0, 1, 2, 3, 5, 7, 10, 60, 100, 1000]),
            duration=duration, algorithm=rng.choice([TOKEN, LEAKY]),
            behavior=behavior, burst=rng.choice([0, 0, 0, 5, 20]),
        )

    # 1. get_rate_limits: small mixed batches over few keys, so that
    # duplicates in a batch, algorithm switches, limit/duration changes,
    # RESET_REMAINING, Gregorian and TTL expiry all meet live state.
    keys = [f"k{i}" for i in range(24)]
    for _ in range(args.parity_steps):
        batch = [random_item(keys) for _ in range(rng.randint(1, 12))]
        now = clock.now_ms()
        got = engine.get_rate_limits([to_req(it) for it in batch])
        for it, g in zip(batch, got):
            check(g.error == "", f"get_rate_limits error: {g.error} {it}")
            compare(
                it, (int(g.status), g.limit, g.remaining, g.reset_time),
                shadow.apply(it, now), "get_rate_limits",
            )
        clock.advance(ms=rng.choice([0, 0, 1, 3, 7, 100, 1000, 40000]))

    # 1b. A leaky remaining just below an integer: three leaks of 4/3
    # token sum to 4 - 3·2^-32-ish, which is 3 tokens.  A float64 held
    # as a pair of float32 converts that to 4 unless it is truncated in
    # float first (ops/bucket_kernel.py trunc_i64).
    def one(it):
        (g,) = engine.get_rate_limits([to_req(it)])
        compare(
            it, (int(g.status), g.limit, g.remaining, g.reset_time),
            shadow.apply(it, clock.now_ms()), "near_integer",
        )
        return g.remaining

    drip = item("parity", "near_int", hits=100, limit=100, duration=300,
                algorithm=LEAKY)
    one(drip)
    for _ in range(3):
        clock.advance(ms=4)
        left = one(dict(drip, hits=0))
    check(left == 3, f"near-integer leaky remaining {left}, want 3")

    # 2. apply_columnar: wire-width batches, pipelined four deep so the
    # step pump groups them into its scanned programs; general mixed
    # batches (with duplicates: the collapsed program), and one-config
    # batches (the uniform narrow format).
    def columnar(items):
        return engine.apply_columnar(
            [(it["name"] + "_" + it["unique_key"]).encode() for it in items],
            np.asarray([it["algorithm"] for it in items], dtype=np.int32),
            np.asarray([it["behavior"] for it in items], dtype=np.int32),
            np.asarray([it["hits"] for it in items], dtype=np.int64),
            np.asarray([it["limit"] for it in items], dtype=np.int64),
            np.asarray([it["duration"] for it in items], dtype=np.int64),
            np.asarray([it["burst"] for it in items], dtype=np.int64),
            want_async=True,
        )

    wide = [f"w{i}" for i in range(6000)]
    for rnd in range(args.parity_rounds):
        group = []
        for j in range(4):
            if (rnd + j) % 3 == 0:  # one limit config across the batch
                cfg = random_item(wide)
                cfg["behavior"] = 0
                cfg["duration"] = rng.choice([1000, 30000, 3_600_000])
                batch = [
                    dict(cfg, unique_key=k)
                    for k in rng.sample(wide, 1000)
                ]
            elif (rnd + j) % 3 == 1:  # distinct keys, mixed configs
                batch = [
                    dict(random_item(wide), unique_key=k)
                    for k in rng.sample(wide, 1000)
                ]
            else:  # duplicates of a few hot keys, same config per key
                hot = {k: random_item(wide) for k in rng.sample(wide, 8)}
                batch = [
                    dict(hot[k], unique_key=k, behavior=hot[k]["behavior"]
                         & ~RESET_REMAINING)
                    for k in rng.choices(list(hot), k=700)
                ]
            group.append(batch)
        now = clock.now_ms()
        pendings = [columnar(b) for b in group]
        for batch, pending in zip(group, pendings):
            status, limit, remaining, reset = pending.get()
            for i, it in enumerate(batch):
                compare(
                    it,
                    (int(status[i]), int(limit[i]), int(remaining[i]),
                     int(reset[i])),
                    shadow.apply(it, now), "apply_columnar",
                )
        clock.advance(ms=rng.choice([0, 1, 50, 2000, 45000]))

    # 3. The expiry sweep: everything written so far lapses, and the
    # device sweep must hand back exactly the rows the spec still holds
    # (a RESET_REMAINING on a token bucket already removed its row).
    held = len(shadow.buckets)
    clock.advance(ms=400 * 24 * HOUR_MS)
    freed = engine.sweep()
    check(freed == held, f"sweep freed {freed} of {held} expired rows")

    info = device_info.describe(engine)
    result = {
        "rows_compared": rows,
        "mismatches": len(mismatches),
        "first_mismatches": mismatches[:5],
        "swept": freed,
        "device": {
            k: info[k] for k in (
                "platform", "device_kind", "device_count",
                "pump", "pump_scan",
            )
        },
        "pump_fused_rounds": info["counters"]["pump_fused_rounds"],
        "compiles": info["compiles"]["persistent_cache"],
    }
    print(json.dumps(result), flush=True)
    return 1 if mismatches else 0


def run_parity(env: dict, args, rows: int, rehearse: bool) -> dict:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--parity-child",
        "--seed", str(args.seed), "--rows", str(rows),
        "--parity-steps", str(60 if rehearse else 400),
        "--parity-rounds", str(3 if rehearse else 12),
    ]
    log_path = os.path.join(OUT_DIR, "parity.log")
    with open(log_path, "w") as err:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=err,
            text=True, start_new_session=True,
        )
        _children.append(proc)
        out, _ = proc.communicate()
        _children.remove(proc)
    lines = [l for l in out.strip().splitlines() if l.startswith("{")]
    if not lines:
        with open(log_path, errors="replace") as f:
            raise SmokeFailure(
                f"parity child rc={proc.returncode}, no result:\n"
                f"{f.read()[-4000:]}"
            )
    result = json.loads(lines[-1])
    check(
        proc.returncode == 0 and result["mismatches"] == 0,
        f"parity: {result['mismatches']} of {result['rows_compared']} rows "
        f"differ from models/spec.py: {result['first_mismatches']}",
    )
    return result


# ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size on the CPU backend, to debug this script")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--rows", type=int, default=0,
                    help=f"bucket rows resident (default {FULL_ROWS})")
    ap.add_argument("--keys", type=int, default=0,
                    help=f"distinct keys driven (default {FULL_KEYS})")
    ap.add_argument("--parity-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--parity-steps", type=int, default=400,
                    help=argparse.SUPPRESS)
    ap.add_argument("--parity-rounds", type=int, default=12,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    if args.parity_child:
        return parity_child(args)

    rehearse = args.rehearse_cpu
    want_platform = "cpu" if rehearse else "tpu"
    # Rehearsal: small enough that XLA:CPU's fused step still passes
    # its in-place probe, so the pump and its scans are on as on a chip.
    rows = args.rows or (20_000 if rehearse else FULL_ROWS)
    n_keys = args.keys or (8_000 if rehearse else FULL_KEYS)
    cuts = []
    if rows != FULL_ROWS:
        cuts.append(f"rows {FULL_ROWS} -> {rows}")
    if n_keys != FULL_KEYS:
        cuts.append(f"keys {FULL_KEYS} -> {n_keys}")
    for c in cuts:
        say(f"CUT: {c}")

    threading.Thread(target=_deadline_thread, daemon=True).start()
    os.makedirs(OUT_DIR, exist_ok=True)

    import gubernator_tpu  # noqa: F401 — imports jax, sets its config
    from jax._src import xla_bridge

    natives = load_natives()
    say(f"native libraries built and loaded: {sorted(natives)}")
    env = child_env(rehearse)
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )
    say(f"compile cache: {cache_dir}")

    # -- first daemon: start, serve, be checked --------------------------
    cold = DaemonChild("first", env, rows)
    try:
        try:
            cold_secs = cold.start(timeout=900)
        except SmokeFailure:
            if not rehearse and cold.proc.poll() is not None:
                say(f"jax, left to choose, finds: {named_platform_found()}")
            raise
        dev0 = cold.debug_vars()["device"]
        say(
            f"first start: {cold_secs:.1f}s to first answer on "
            f"{dev0['platform']} {dev0['device_kind']} x{dev0['device_count']}"
            f", engine {dev0['engine']}, "
            f"pump {dev0['pump']}, scan {dev0['pump_scan']}"
        )
        for name, v in dev0["probes"].items():
            say(f"probe {name}: ok={v['ok']} — {v['reason']}")
        check_device(dev0, want_platform, rows)

        n_threads = 8
        t_wide = time.monotonic()
        wide = drive_wide(cold, n_keys, n_threads)
        say(
            f"wide traffic: {wide.decisions} decisions over {n_keys} keys "
            f"in {time.monotonic() - t_wide:.1f}s ({wide.grpc_rpcs} gRPC, "
            f"{wide.http_posts} HTTP), {wide.over} OVER_LIMIT, "
            f"{wide.token_compared} token rows compared"
        )
        check(
            not wide.mismatches,
            f"wide traffic: {len(wide.mismatches)} rows differ from "
            f"models/spec.py or failed, first: {wide.mismatches[:3]}",
        )
        specials = drive_specials(cold)
        say(f"special batches: {specials}")

        after = cold.debug_vars()
        dev1 = after["device"]
        c0, c1 = dev0["counters"], dev1["counters"]
        ledger_answered = (after.get("ledger") or {}).get("answered", 0)
        served = wide.decisions + sum(s["rows"] for s in specials.values())
        # Device work in step with the traffic: every distinct-key
        # batch the ledger could not answer is at least one round and
        # one dispatch.
        rpcs = wide.grpc_rpcs + wide.http_posts
        d_rounds = c1["rounds_total"] - c0["rounds_total"]
        d_disp = c1["dispatches_total"] - c0["dispatches_total"]
        d_reqs = c1["requests_total"] - c0["requests_total"]
        say(
            f"engine: +{d_reqs} rows, +{d_rounds} rounds, +{d_disp} "
            f"dispatches for {rpcs} wide RPCs; ledger answered "
            f"{ledger_answered} of {served} ({ledger_answered / served:.4%})"
        )
        check(
            d_reqs >= 0.9 * wide.decisions,
            f"engine saw {d_reqs} rows of {wide.decisions} decisions",
        )
        check(
            d_rounds >= 0.9 * rpcs and d_disp >= 0.9 * rpcs / 16,
            f"device work did not grow with traffic: {d_rounds} rounds, "
            f"{d_disp} dispatches for {rpcs} RPCs",
        )
        swallowed = [
            l for l in cold.metrics_text().splitlines()
            if l.startswith("gubernator_swallowed_exceptions")
        ]
        check(not swallowed, f"daemon swallowed exceptions: {swallowed}")
        cold.stop()
    finally:
        cold.kill()

    # -- second daemon, same cache: nothing may recompile ----------------
    warm = DaemonChild("second", env, rows, front=True)
    try:
        warm_secs = warm.start(timeout=600)
        devw = warm.debug_vars()["device"]
        cache_cold = dev0["compiles"]["persistent_cache"]
        cache_warm = devw["compiles"]["persistent_cache"]
        say(
            f"first start : {cold_secs:.1f}s, "
            f"{dev0['compiles']['backend_compiles']} compile requests, "
            f"cache {cache_cold}"
        )
        say(
            f"second start: {warm_secs:.1f}s, "
            f"{devw['compiles']['backend_compiles']} compile requests, "
            f"cache {cache_warm}"
        )
        check_device(devw, want_platform, rows)
        if not rehearse:  # the CPU backend keeps no persistent cache
            check(
                cache_warm["misses"] == 0 and cache_warm["hits"] > 0,
                f"warm start recompiled: {cache_warm}",
            )
        front = drive_front(warm)
        say(f"native front: {front['rpcs']} single-item RPCs as the spec, "
            f"counters {front['counters']}")
        warm.stop()
    finally:
        warm.kill()

    # -- frozen-clock parity on the chip the daemons released ------------
    parity = run_parity(env, args, rows, rehearse)
    say(
        f"parity: {parity['rows_compared']} rows bit-equal to the spec on "
        f"{parity['device']['platform']}, {parity['pump_fused_rounds']} "
        f"rounds through the scanned pump programs, swept {parity['swept']}"
    )
    check(
        parity["device"]["platform"] == want_platform,
        f"parity ran on {parity['device']['platform']}",
    )
    check(parity["pump_fused_rounds"] > 0, "no round took the scanned pump")

    check(
        not xla_bridge.backends_are_initialized(),
        "the parent initialized a jax backend",
    )
    summary = {
        "ok": True,
        "device": {
            "platform": dev0["platform"],
            "kind": dev0["device_kind"],
            "count": dev0["device_count"],
        },
        "rehearsal": rehearse,
        "cuts": cuts,
        "engine": dev0["engine"],
        "rows_resident": dev0["rows"],
        "bytes_in_use_per_device": [
            m["bytes_in_use"] for m in dev1["memory"]
        ],
        "pump": dev0["pump"],
        "pump_scan": dev0["pump_scan"],
        "probes": dev0["probes"],
        "native_libraries": natives,
        "daemon_native": dev0["native"],
        "decisions_served": served,
        "distinct_keys": n_keys,
        "errors": wide.errors,
        "over_limit_share": round(wide.over / wide.decisions, 4),
        "token_rows_compared": wide.token_compared,
        "ledger_answered": ledger_answered,
        "engine_rounds": d_rounds,
        "engine_dispatches": d_disp,
        "pump_fused_rounds_daemon": dev1["counters"]["pump_fused_rounds"],
        "specials": specials,
        "start": {
            "first": {
                "seconds_to_first_answer": round(cold_secs, 1),
                "compile_requests": dev0["compiles"]["backend_compiles"],
                "persistent_cache": cache_cold,
            },
            "second": {
                "seconds_to_first_answer": round(warm_secs, 1),
                "compile_requests": devw["compiles"]["backend_compiles"],
                "persistent_cache": cache_warm,
            },
        },
        "native_front": front,
        "programs_compiled": shape_checklist(dev1["compiles"]["programs"]),
        "parity_rows": parity["rows_compared"],
        "parity_swept": parity["swept"],
        "seconds": round(time.monotonic() - _T0, 1),
        "claim": None,
    }
    text = json.dumps(summary)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        f.write(text + "\n")
    print(text, flush=True)
    print(result_line(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
