#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, from the client's side of the
daemon's gRPC listener.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one new process tree.  This parent never initialises a jax
backend: it builds the native libraries, starts one daemon child
through `lib/launch_daemon.py` (which calls the program's own
`gubernator_tpu.cmd.daemon.main`) with the configuration's environment
and nothing else from GUBER_*, and the cell's client processes, which
encode their payloads from `--seed` while the daemon compiles and
loads.  It waits for the first answered GetRateLimits, warms the mix
for a fixed number of RPCs, checks that /debug/vars names the platform,
engine, rows and chips of the configuration, measures for `--seconds`,
stops the daemon, holds the answers the clients kept to the reference
(lib/judge.py), reduces, and prints one JSON line.  `setup_s` is spawn →
end of warm-up.  The run's log cuts the window into 10 s slices
(each slice's rate, p50, p95 and the machine pauses in it), so that a
run says how it wandered inside one start.  Anywhere but on a TPU it
exits non-zero and prints no result; `--rehearse-cpu` is the only CPU
path, at a tiny size, and the result then says `"platform": "cpu"`.

What is started in the daemon's place is the configuration's to say
(`launcher`, `holds_chip` in its file): the `control_*` configurations
put the benchmark's reference there (lib/control_server.py), holding no
chip, to show that `correct` fails when it should; their result says
`"platform": "none"` and is never a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import judge, trace_reduce, traffic, wire  # noqa: E402
from lib.daemon_child import DaemonChild, HarnessFailure, base_env  # noqa: E402
from lib.manifest import Manifest, read_metric  # noqa: E402

NATIVE_LIBS = ("intern_table", "wire_codec", "h2_server", "h2_client")
DEFAULT_LAUNCHER = "benchmarks/lib/launch_daemon.py"
FIRST_ANSWER_TIMEOUT_S = 1100.0  # a first run compiles ~700 s at 100 M rows
REHEARSAL_IDS = 4000


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


class Client:
    """One client process and its line protocol."""

    def __init__(self, index: int, spec: dict, run_dir: str):
        self.index = index
        self.latency_file = os.path.join(run_dir, f"latency_{index}.npy")
        self.answers_file = os.path.join(run_dir, f"answers_{index}.npz")
        spec = dict(spec, latency_file=self.latency_file)
        spec_path = os.path.join(run_dir, f"client_{index}.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        self.err_path = os.path.join(run_dir, f"client_{index}.err")
        with open(self.err_path, "w") as err:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "lib", "client.py"),
                 spec_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
                text=True, cwd=ROOT, env=base_env(),
            )

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            with open(self.err_path, errors="replace") as f:
                tail = f.read()[-2000:]
            raise HarnessFailure(
                f"client {self.index} ended (rc={self.proc.poll()}):\n{tail}"
            )
        return json.loads(line)

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send(cmd="exit")
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


def build_natives() -> None:
    """Build (hash-keyed, from the committed .cpp files) the native
    libraries the daemon loads; importing the package imports jax but
    initialises no backend."""
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    from gubernator_tpu.core.native_build import ensure_built

    for stem in NATIVE_LIBS:
        if ensure_built(stem) is None:
            raise HarnessFailure(f"native library {stem} did not build")


def daemon_env(config: dict, rehearse: bool, trace_dir: str,
               trace_seconds: float) -> dict:
    env = base_env()
    env.update(config["env"])
    if not config.get("holds_chip", True):
        return env
    env["JAX_PLATFORMS"] = "cpu" if rehearse else "tpu"
    if rehearse:
        env.update(config["rehearsal"]["env"])
        # as many virtual CPU devices as the cell has chips
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={int(config['chips'])}"
        )
    if trace_dir:
        env["BENCH_TRACE_DIR"] = trace_dir
        env["BENCH_TRACE_SECONDS"] = str(trace_seconds)
    return env


def check_device(dev: dict, config: dict, rehearse: bool) -> None:
    """The daemon serves on what the configuration says, or the run
    ends: no fallback is measured under the cell's name."""
    want_platform = "cpu" if rehearse else "tpu"
    rows = int(config["rehearsal"]["rows"] if rehearse else config["rows"])
    chips = int(config["chips"])

    def need(cond: bool, what: str) -> None:
        if not cond:
            raise HarnessFailure(what)

    need(dev["platform"] == want_platform,
         f"daemon serves on {dev['platform']!r} ({dev['device_kind']}), "
         f"not {want_platform!r}")
    need(not dev["cpu_unrequested"], "backend fell to the CPU unasked")
    need(dev["device_count"] == chips,
         f"{dev['device_count']} device(s) where the cell asks for {chips}")
    need(dev["engine"] == config["engine"],
         f"engine {dev['engine']}, configuration says {config['engine']}")
    need(dev["rows"] == rows // chips * chips,
         f"rows resident {dev['rows']} != {rows // chips * chips}")
    need(dev["native"]["intern_table"] and dev["native"]["wire_codec"],
         f"daemon lost a native tier: {dev['native']}")


# What a configuration that holds no chip (the control: the reference
# in the daemon's place) reports in the device's place.
NO_DEVICE = {
    "platform": "none", "device_kind": "reference-control",
    "device_count": 0, "memory": [], "compiles": {},
}


def snapshot(daemon: DaemonChild, holds_chip: bool) -> dict:
    """What the daemon says about itself, to be read as a change over
    the window: /debug/vars, /metrics, its CPU seconds."""
    if not holds_chip:
        return {"vars": {"device": NO_DEVICE}, "prom": {}, "cpu_s": None}
    return {
        "vars": daemon.debug_vars(), "prom": daemon.metrics(),
        "cpu_s": daemon.cpu_seconds(),
    }


def quantile(sorted_values: np.ndarray, q: float) -> float:
    """The q-quantile by the nearest rank above: the tail of all RPCs."""
    i = min(len(sorted_values) - 1, int(np.ceil(q * len(sorted_values))) - 1)
    return float(sorted_values[max(i, 0)])


def ladder(latencies: np.ndarray) -> dict:
    ordered = np.sort(latencies)
    return {
        f"p{q:g}": round(1e3 * quantile(ordered, q / 100), 3)
        for q in (50, 90, 95, 98, 99, 99.5, 100)
    }


SLICE_S = 10.0  # the per-slice line's grain


class Ticker(threading.Thread):
    """Sleeps 5 ms at a time through the window and keeps the gaps it
    overslept by more than 50 ms: a pause that this idle parent sees
    too is the machine's, not the daemon's or the clients'."""

    def __init__(self, t_end: float):
        super().__init__(daemon=True, name="bench-ticker")
        self.t_end, self.gaps = t_end, []

    def run(self) -> None:
        last = time.time()
        while last < self.t_end:
            time.sleep(0.005)
            now = time.time()
            if now - last > 0.05:
                self.gaps.append((last, now - last))
            last = now


def slices_of(sent_at, took, t_start, seconds, items_per_rpc, gaps) -> list:
    """The window cut into SLICE_S slices by completion time: each
    slice's completed decisions/s, p50 and p95, and the pauses over
    50 ms (`gaps`: start, length) that the idle parent's ticker saw
    begin in it.  One long run gives a dozen readings of how a single
    start wanders."""
    done = sent_at + took - t_start
    out = []
    for k in range(int(np.ceil(seconds / SLICE_S))):
        lo, hi = k * SLICE_S, min(seconds, (k + 1) * SLICE_S)
        lat = np.sort(took[(done >= lo) & (done < hi)])
        row = {"from_s": lo, "rpcs": int(lat.size),
               "decisions_per_s": lat.size * items_per_rpc / (hi - lo)}
        if lat.size:
            row["rpc_p50_ms"] = 1e3 * quantile(lat, 0.50)
            row["rpc_p95_ms"] = 1e3 * quantile(lat, 0.95)
        row["pauses_ms"] = [
            round(1e3 * g) for t, g in gaps if lo <= t - t_start < hi
        ]
        out.append(row)
    return out


def run(args) -> int:
    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    config = manifest.config(cell["config"])
    mix = manifest.mix(cell["traffic"])
    rehearse = args.rehearse_cpu
    # a configuration that holds no chip is the control: the reference
    # served by its own launcher, never a measurement
    holds_chip = bool(config.get("holds_chip", True))
    if rehearse:
        mix = dict(mix, keys=dict(mix["keys"], ids=REHEARSAL_IDS))
    seconds = float(args.seconds)
    n_callers, n_procs = int(mix["callers"]), int(mix["client_processes"])
    warm_rpcs = int(mix["warmup_rpcs_per_caller"])
    pool_rpcs = warm_rpcs + int(
        np.ceil(float(mix["pool_rpcs_per_caller_per_s"]) * seconds)
    ) + 2

    run_dir = os.path.join(ROOT, ".bench_run", f"{args.workload}-{os.getpid()}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    trace_dir = os.path.join(run_dir, "trace") if args.trace else ""
    if trace_dir:
        os.makedirs(trace_dir)
    trace_seconds = min(float(cell.get("trace_seconds", 3.0)), seconds / 3.0)

    t_setup = time.monotonic()
    daemon, clients = None, []
    try:
        if holds_chip:
            build_natives()
        daemon = DaemonChild(
            os.path.join(ROOT, config.get("launcher", DEFAULT_LAUNCHER)),
            ROOT, daemon_env(config, rehearse, trace_dir, trace_seconds),
            os.path.join(run_dir, "daemon.log"),
        )
        daemon.spawn()
        for p in range(n_procs):
            clients.append(Client(p, {
                "mix": mix, "seed": args.seed, "target": daemon.grpc_addr,
                "callers": list(range(p, n_callers, n_procs)),
                "pool_rpcs": pool_rpcs,
            }, run_dir))
        first = wire.encode_request([(
            wire.name_prefix("bench_first"), b"answer",
            wire.item_suffix(1, 10, 3_600_000, 0, 0, 0),
        )])
        daemon.wait_first_answer(first, FIRST_ANSWER_TIMEOUT_S)
        say(f"first answer {time.monotonic() - t_setup:.1f}s after spawn, "
            f"{time.time() - daemon.t_listen:.2f}s after the port opened")
        for c in clients:
            c.recv()  # ready: pools encoded
        if holds_chip:
            check_device(daemon.debug_vars()["device"], config, rehearse)
        for c in clients:
            c.send(cmd="warm", rpcs=warm_rpcs)
        for c in clients:
            warmed = c.recv()
            if warmed["errors"]:
                raise HarnessFailure(f"warm-up RPCs failed: {warmed['errors']}")
        setup_s = time.monotonic() - t_setup
        say(f"warm-up done: setup_s {setup_s:.1f}")

        # -- the measured window -------------------------------------
        before = snapshot(daemon, holds_chip)
        t_start = time.time() + 0.3
        t_end = t_start + seconds
        for c in clients:
            c.send(cmd="window", t_start=t_start, t_end=t_end)
        ticker = Ticker(t_end)
        ticker.start()
        if trace_dir:
            time.sleep(max(0.0, t_start + 0.3 * seconds - time.time()))
            open(os.path.join(trace_dir, "go"), "w").close()
        windows = [c.recv() for c in clients]
        closed = time.time()
        t_listen = daemon.t_listen
        after = snapshot(daemon, holds_chip)
        trace_meta = None
        if trace_dir and holds_chip:
            done = os.path.join(trace_dir, "done")
            while not os.path.exists(done):
                if time.time() > closed + 90:
                    raise HarnessFailure("the trace slice never finished")
                time.sleep(0.1)
            with open(done) as f:
                trace_meta = json.load(f)
            if trace_meta["error"]:
                raise HarnessFailure(f"profiler: {trace_meta['error']}")
        rc = daemon.stop()
        daemon = None
        if rc != 0:
            raise HarnessFailure(f"daemon exited rc={rc}")

        # -- the answers, against the reference ------------------------
        t_judge = time.monotonic()
        for c in clients:
            c.send(cmd="answers", file=c.answers_file)
        counts = [c.recv()["counts"] for c in clients]
        parts = []
        for c, n in zip(clients, counts):
            with np.load(c.answers_file) as z:
                parts.append(dict({k: z[k] for k in z.files}, counts=n))
        cols = judge.merge_columns(parts)
        judged = dict(
            cols["counts"],
            **judge.judge_answers(traffic.LimitTable(mix), cols),
        )
        judge_s = time.monotonic() - t_judge
        timed = np.concatenate(
            [np.load(c.latency_file) for c in clients], axis=1
        )
        latencies = np.sort(timed[0])
    finally:
        if daemon is not None:
            daemon.kill()
        for c in clients:
            c.close()

    # -- reduce ----------------------------------------------------------
    rpcs = int(latencies.size)
    items = sum(w["items"] for w in windows)
    if rpcs == 0:
        raise HarnessFailure("no RPC completed inside the window")
    dev_after = after["vars"]["device"]
    compiles_in_window = (
        dev_after["compiles"].get("backend_compiles", 0)
        - before["vars"]["device"]["compiles"].get("backend_compiles", 0)
    )
    peaks = [m["peak_bytes_in_use"] or 0 for m in dev_after["memory"]]
    device = {
        "platform": dev_after["platform"], "kind": dev_after["device_kind"],
        "count": dev_after["device_count"],
        "memory_peak_bytes": max(peaks, default=0),
    }
    end_to_end = {
        "decisions_per_s": items / seconds,
        "rpc_p50_ms": 1e3 * quantile(latencies, 0.50),
        "rpc_p95_ms": 1e3 * quantile(latencies, 0.95),
        "setup_s": setup_s,
    }
    state = {
        "rows": config["rehearsal"]["rows"] if rehearse and holds_chip
        else config.get("rows"),
        "rows_occupied_start": before["prom"].get(("gubernator_cache_size", ())),
        "rows_occupied_end": after["prom"].get(("gubernator_cache_size", ())),
    }
    say(
        f"window: {rpcs} RPCs, {items} decisions in {seconds:.0f}s; "
        f"OVER_LIMIT share of all answers {judged['over'] / max(1, judged['answered_items']):.4f}; "
        f"compiles inside the window {compiles_in_window}; pool wraps "
        f"{sum(w['pool_wraps'] for w in windows)}; client CPU "
        f"{sum(w['cpu_s'] for w in windows):.1f}s over {n_procs} processes; "
        f"judge {judge_s:.1f}s"
    )
    slowest = sorted(
        (s for w in windows for s in w["slowest"]), key=lambda s: -s[1]
    )[:8]
    say(f"latency ms over {rpcs} RPCs: {json.dumps(ladder(timed[0]))}; window "
        f"opened {t_start - t_listen:.2f}s after the port; slowest RPCs [sent s "
        f"after the port, ms]: "
        f"{[[round(t - t_listen, 2), round(1e3 * x, 1)] for t, x in slowest]}")
    say(f"by {SLICE_S:g} s slice of the window (by completion time): "
        + json.dumps(slices_of(timed[1], timed[0], t_start, seconds,
                               int(mix["items_per_rpc"]), ticker.gaps)))
    say(f"judged: {judged['keys']} keys ({judged['shared_keys']} with answers "
        f"to more than one caller, {judged['reordered_keys']} placed in another "
        f"order than their clocks'), {judged['checked']} answers; rows occupied "
        f"{state['rows_occupied_start']} -> {state['rows_occupied_end']} of "
        f"{state['rows']}")
    say(f"end to end: {json.dumps(end_to_end)}")

    result = {
        "correct": False,
        "attempted": judged["answered_items"] + judged["failed_items"],
        "failed": judged["failed_items"],
        "metrics": {},
        "device": device,
    }
    if args.trace:
        trace = None
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is not None:
            trace = trace_reduce.load_xplane(xplane)
        ctx = {
            "prom_before": before["prom"], "prom_after": after["prom"],
            "vars_before": before["vars"], "vars_after": after["vars"],
            "trace": trace, "trace_meta": trace_meta,
            "device_kind": device["kind"],
            "run": {
                "window_s": seconds, "decisions": items, "rpcs": rpcs,
                "client_cpu_s": sum(w["cpu_s"] for w in windows),
                "client_processes": n_procs,
                "daemon_cpu_s": (
                    None if after["cpu_s"] is None
                    else after["cpu_s"] - before["cpu_s"]
                ),
                "compiles_in_window": compiles_in_window,
            },
        }
        chosen = manifest.metrics_of("per_layer", args.workload)
        values = {
            m["name"]: read_metric(manifest.layer_metric(m["name"]), ctx)
            for m in chosen
        }
        busy = trace_reduce.device_busy(trace) if trace else None
        if busy is not None:
            device["busy_s"] = busy["busy_s"]
            device["window_s"] = busy["window_s"]
            result["breakdown"] = trace_reduce.breakdown(trace)
            say(f"trace: busy {busy['per_chip_busy_s']} of {busy['window_s']:.3f}s "
                f"traced; slice asked {trace_seconds:.1f}s")
    else:
        chosen = manifest.metrics_of("end_to_end", args.workload)
        values = {m["name"]: end_to_end.get(m["name"]) for m in chosen}
    units = {m["name"]: m["unit"] for m in chosen}
    result["metrics"] = {
        name: {"value": value, "unit": units[name]}
        for name, value in values.items() if value is not None
    }
    shutil.rmtree(run_dir, ignore_errors=True)
    # How much of the state the traffic had filled (the driver ignores
    # the key): rows interned at the window's two ends.
    result["state"] = state

    # The least number of answers a run has to have checked, for the
    # window it was given; a rehearsal checks what it gets.
    min_checked = 1 if rehearse else int(
        cell["min_checked"] * seconds / manifest.doc["run_seconds"]
    )
    verdict = judge.verdict(judged, min_checked)
    result["correct"] = verdict["correct"]
    result["compared"] = verdict["compared"]
    if judged["first_mismatches"]:
        say(f"first mismatches: {json.dumps(judged['first_mismatches'])}")
    sys.stdout.flush()
    for name, c in verdict["compared"].items():
        print(f"compared {name}: {json.dumps(c)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size on the CPU backend, to debug the harness")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except HarnessFailure as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
