"""Headline benchmark: end-to-end rate-limit decisions/sec on one chip.

Drives the full local decision path — key interning, round scheduling,
batch assembly, the jitted bucket kernel on the TPU, response
materialization — exactly what a daemon does per 500µs window.

Baseline: the reference sustains > 2,000 requests/sec on a production
node (reference: README.md:97-100; SURVEY.md §6).  `vs_baseline` is the
multiple over that figure.

Device contract: the run measures on whatever accelerator jax finds,
in this process — there is no subprocess probe and no second process
that needs the chip.  Without an accelerator, and without an explicit
`BENCH_FORCE_CPU=1`, it exits non-zero; it never carries on on the CPU
under a device's name and never replays a committed artifact.  A run
that fails (exception, hard deadline, a result carrying "error") still
prints its one JSON line and exits non-zero.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": "decisions/sec",
   "vs_baseline": N, "p50_ms": N, "p99_ms": N, "platform": "..."}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Optional

BASELINE_DECISIONS_PER_SEC = 2000.0  # reference README.md:97-100

BATCH = int(os.environ.get("BENCH_BATCH", 8192))
N_KEYS = int(os.environ.get("BENCH_KEYS", 100_000))
CAPACITY = int(os.environ.get("BENCH_CAPACITY", 1 << 17))
WARMUP_BATCHES = 3
MEASURE_SECONDS = float(os.environ.get("BENCH_SECONDS", 5.0))
# Depth matches the readback combiner's MAX_GROUP: outstanding batches
# share one stacked d2h transfer, so the pipeline should keep a full
# group in flight (core/readback.py).
PIPELINE_DEPTH = int(os.environ.get("BENCH_PIPELINE", 16))
LATENCY_BATCHES = int(os.environ.get("BENCH_LATENCY_BATCHES", 200))
# "engine" (headline: columnar engine path) | "wire" (loopback gRPC
# through a real daemon — VERDICT r1 item 2's served-path evidence) |
# "global" (GLOBAL behavior over an in-process cluster — BASELINE
# config 3).
MODE = os.environ.get("BENCH_MODE", "engine")
# Algorithm mix for engine mode: mixed | token | leaky (config 2).
ALGO = os.environ.get("BENCH_ALGO", "mixed")
# Zipf skew exponent for engine-mode key sampling; 0 = round-robin
# (config 4's skewed 100M-key load uses e.g. BENCH_ZIPF=1.2).
# numpy's sampler requires alpha > 1.
ZIPF = float(os.environ.get("BENCH_ZIPF", 0))
if ZIPF and ZIPF <= 1.0:
    raise SystemExit("BENCH_ZIPF must be > 1 (numpy zipf sampler) or 0")
# Whole-run deadline: a run that stops making progress emits the JSON
# line and exits non-zero instead of hanging.  Floored by the
# configured workload so a long healthy run is never misreported.
HARD_TIMEOUT = max(
    float(os.environ.get("BENCH_HARD_TIMEOUT", 540.0)),
    3.0 * MEASURE_SECONDS + 0.1 * LATENCY_BATCHES + 120.0,
)

_emit_lock = threading.Lock()
_emitted = False


def _emit_once(result: dict) -> None:
    """Print the contract's single JSON line exactly once, racing the
    watchdog safely."""
    global _emitted
    with _emit_lock:
        if _emitted:
            return
        _emitted = True
        print(json.dumps(result), flush=True)


def _pick_platform() -> str:
    """The platform this run measures on.  `BENCH_FORCE_CPU=1` asks
    for the host backend by name; otherwise jax picks, in this process
    (a backend that cannot start fails with its own error), and a run
    that finds no accelerator exits non-zero."""
    if os.environ.get("BENCH_FORCE_CPU", "0") != "0":
        from gubernator_tpu.platform_guard import force_cpu_platform

        force_cpu_platform()
        return "cpu"
    import jax

    platform = jax.devices()[0].platform
    if platform == "cpu":
        raise SystemExit(
            "bench.py: jax found no accelerator (platform 'cpu'); set "
            "BENCH_FORCE_CPU=1 to run on the host backend by name"
        )
    return platform


def main() -> int:
    platform = _pick_platform()

    def _watchdog() -> None:
        time.sleep(HARD_TIMEOUT)
        _emit_once(
            {
                "metric": "rate-limit decisions/sec, single chip, end-to-end",
                "value": 0,
                "unit": "decisions/sec",
                "vs_baseline": 0,
                "platform": platform,
                "error": f"bench exceeded hard deadline ({HARD_TIMEOUT:.0f}s)",
            }
        )
        os._exit(1)

    threading.Thread(target=_watchdog, daemon=True).start()

    try:
        import numpy as np

        if MODE == "sketch":
            result = _run_wire(np, platform, sketch=True)
        elif MODE == "wire":
            result = _run_wire(np, platform)
        elif MODE == "global":
            result = _run_global(np, platform)
        elif MODE == "herd":
            result = _run_herd(np, platform)
        elif MODE == "deadpeer":
            result = _run_deadpeer(np, platform)
        elif MODE == "reshard":
            result = _run_reshard(np, platform)
        elif MODE == "herdnative":
            # 32 concurrent SINGLE-ITEM RPCs against the h2 fast front:
            # the native decision plane's per-RPC floor as its own
            # artifact (herdfast is the same front at the window path;
            # GUBER_NATIVE_LEDGER=0 gives the same-session A/B pair).
            result = _run_herd(np, platform, force_fast=True)
        elif MODE == "devfused":
            # Same-session fused/unfused device-path A/B: the fused
            # single-dispatch decision step (GUBER_FUSED default) vs
            # the unfused compute+scatter chain (GUBER_FUSED=split),
            # alternating pairs with the median-of-pair-deltas
            # treatment from herdtrace.  On CPU this run IS the CPU
            # line the TPU recapture is compared against (PERF.md §24).
            result = _run_devfused(np, platform)
        elif MODE == "feeder":
            # Columnar feeder plane (PERF.md §25): the C wire→columns
            # pack line (rows/s) vs the Python columnar decode line,
            # plus a same-session GUBER_NATIVE_FEEDER=0 A/B of the
            # herd front with the window_wait / feeder_ring_wait
            # stage attribution embedded (the §23 tail surface).
            result = _run_feeder(np, platform)
        elif MODE == "connscale":
            # Connection-scale ramp (PERF.md §26, ROADMAP item 2):
            # 1k→10k idle-plus-active connections through the epoll
            # reactor front from the epoll connscale client (one
            # subprocess — fds are per-process), with a same-session
            # thread-per-conn A/B at equal load via
            # GUBER_H2_EVENT_FRONT=0 and the feeder-ring-wait p99
            # starvation attribution per rung.
            result = _run_connscale(np, platform)
        elif MODE == "flashcrowd":
            # Hot-key replication A/B (ROADMAP item 3): a time-varying
            # zipf where the hot set ROTATES mid-run — with replication
            # on, promotion keeps every node answering hot keys locally
            # so the herd-style p99 stays flat across rotations; the
            # BENCH_FLASH_REPL=0 arm shows the owner's per-key serve
            # ceiling.  A finite-limit canary key measures admission
            # against the N_replicas x lease bound in the same run.
            result = _run_flashcrowd(np, platform)
        elif MODE == "crossregion":
            # Multi-region federation A/B (ROADMAP item 4): a 2×2
            # region×peer cluster under injected inter-region latency
            # — same-session healthy control, then a full inter-region
            # partition phase (0 errors: every answer is region-local,
            # flagged degraded_region; a finite-limit canary measures
            # drift against the N_regions × limit bound), then heal →
            # requeued deltas converge (drops == 0, convergence time
            # recorded).  RESILIENCE.md §12 / PERF.md §28.
            result = _run_crossregion(np, platform)
        elif MODE == "fleetobs":
            # Fleet observability A/B (ISSUE 15): the cluster rollup
            # + SLO watchdog live at a bench-visible tick on a 2×2
            # region×peer cluster vs every watchdog paused (the
            # GUBER_OBS=0 steady state), alternating pairs with the
            # median-of-pair-deltas treatment — pins the plane's
            # serving overhead < 2% and captures the live burn-rate /
            # admission-headroom columns for the trend.
            result = _run_fleetobs(np, platform)
        elif MODE == "zipfpaged":
            # Paged-state A/B (ROADMAP item 1, PERF.md §30): zipf over
            # a key space ≥10x the resident page budget through the
            # page-table plane (fault rate + spill p99 from the
            # plane's counters), a same-session GUBER_PAGED=0 dense
            # control at equal resident load (the ≤10% hot-path bar),
            # and the dense arm's capacity wall recorded under the
            # full key space.
            result = _run_zipfpaged(np, platform)
        elif MODE == "herdtrace":
            # Same-session tracing A/B: the herdfast workload once with
            # tracing disabled and once with the in-memory recorder +
            # tail flight recorder live — pins the tracing-off cost
            # (< 2% throughput delta is the ISSUE 9 acceptance bar)
            # and captures the tail attribution PERF.md §23 cites.
            result = _run_herdtrace(np, platform)
        else:
            result = _run_engine(np, platform)
        _emit_once(result)
        return 1 if result.get("error") else 0
    except Exception as e:  # noqa: BLE001 — contract: one JSON line, always
        result = {
            "metric": "rate-limit decisions/sec, single chip, end-to-end",
            "value": 0,
            "unit": "decisions/sec",
            "vs_baseline": 0,
            "platform": platform,
            "error": f"{type(e).__name__}: {e}"[:500],
        }
        _emit_once(result)
        return 1


def _key_indices(np, n_batches: int):
    """Per-batch key indices: round-robin over N_KEYS, or Zipf-skewed
    when BENCH_ZIPF=<alpha> is set (BASELINE config 4's skewed load)."""
    if ZIPF > 0:
        rng = np.random.default_rng(0)
        return [
            (rng.zipf(ZIPF, BATCH) - 1) % N_KEYS for _ in range(n_batches)
        ]
    return [
        (np.arange(BATCH, dtype=np.int64) + b * BATCH) % N_KEYS
        for b in range(n_batches)
    ]


def _algo_column(np, key_idx):
    """Algorithm per KEY (it is a property of the limit's name in real
    traffic — reference: request-carried config keyed by name), so
    duplicate occurrences of a key agree and hot-key segments stay
    collapsible."""
    from gubernator_tpu import Algorithm

    n = len(key_idx)
    if ALGO == "token":
        return np.full(n, int(Algorithm.TOKEN_BUCKET), dtype=np.int32)
    if ALGO == "leaky":
        return np.full(n, int(Algorithm.LEAKY_BUCKET), dtype=np.int32)
    return (np.asarray(key_idx) % 2).astype(np.int32)


def _run_engine(np, platform: str) -> dict:
    """Engine-level columnar throughput + latency (the headline mode).

    BENCH_KEYS/BENCH_CAPACITY/BENCH_ALGO/BENCH_ZIPF parameterize it
    into BASELINE configs 2 (leaky @ 1M keys) and 4 (mixed Zipf @ 100M
    keys)."""
    from gubernator_tpu.core.engine import DecisionEngine

    engine = DecisionEngine(capacity=CAPACITY, max_kernel_width=max(8192, BATCH))

    # Pre-build columnar batches (client-side cost, not engine cost) —
    # the engine's native request format (DecisionEngine.apply_columnar);
    # the dataclass/gRPC tier sits above this.
    n_batches = max(1, min((N_KEYS + BATCH - 1) // BATCH, 256))
    # Round-robin mode can only touch n_batches*BATCH distinct keys
    # (client-side key materialization is capped); report the honest
    # working-set size.  Zipf mode samples the full N_KEYS range.
    distinct = N_KEYS if ZIPF else min(N_KEYS, n_batches * BATCH)
    batches = []
    for idx in _key_indices(np, n_batches):
        keys = [b"bench_k%d" % i for i in idx.tolist()]
        batches.append(
            dict(
                keys=keys,
                algo=_algo_column(np, idx),
                behavior=np.zeros(BATCH, dtype=np.int32),
                hits=np.ones(BATCH, dtype=np.int64),
                limit=np.full(BATCH, 1_000_000, dtype=np.int64),
                duration=np.full(BATCH, 3_600_000, dtype=np.int64),
                burst=np.full(BATCH, 1_000_000, dtype=np.int64),
            )
        )

    for i in range(WARMUP_BATCHES):
        engine.apply_columnar(**batches[i % len(batches)])
    # Warm the readback-combiner stack programs AND the step pump's
    # scan families for this batch width so the pipelined throughput
    # loop never pays an XLA compile mid-measurement
    # (core/readback.py, core/pump.py).
    import jax.numpy as jnp

    from gubernator_tpu.core.engine import _pad_size
    from gubernator_tpu.ops.bucket_kernel import PACKED_OUT_ROWS

    engine.readback.warmup_stacks(
        (PACKED_OUT_ROWS, _pad_size(BATCH)), jnp.int32
    )
    if engine._pump is not None:
        engine._pump.warmup(_pad_size(BATCH))

    # Latency: synchronous dispatch→readback per batch (what one
    # 500µs serving window pays end to end).  Target: p99 < 2ms
    # (BASELINE.md).
    lat = np.empty(LATENCY_BATCHES, dtype=np.float64)
    for i in range(LATENCY_BATCHES):
        t0 = time.perf_counter()
        engine.apply_columnar(**batches[i % len(batches)])
        lat[i] = time.perf_counter() - t0
    p50_ms = float(np.percentile(lat, 50) * 1e3)
    p99_ms = float(np.percentile(lat, 99) * 1e3)

    # Throughput: pipelined — keep a few batches in flight so
    # device→host readback of batch i overlaps dispatch of batch
    # i+1 (PendingColumnar).
    from collections import deque

    pending = deque()
    n_done = 0
    start = time.perf_counter()
    i = 0
    while True:
        pending.append(
            engine.apply_columnar(**batches[i % len(batches)], want_async=True)
        )
        i += 1
        if len(pending) > PIPELINE_DEPTH:
            pending.popleft().get()
            n_done += BATCH
        elapsed = time.perf_counter() - start
        if elapsed >= MEASURE_SECONDS:
            break
    while pending:
        pending.popleft().get()
        n_done += BATCH
    elapsed = time.perf_counter() - start

    rate = n_done / elapsed
    return {
        "metric": "rate-limit decisions/sec, single chip, end-to-end "
        f"(batch={BATCH}, {distinct} hot keys"
        + (f", zipf={ZIPF} over {N_KEYS}" if ZIPF else "")
        + f", capacity={CAPACITY}, algo={ALGO})",
        "value": round(rate, 1),
        "unit": "decisions/sec",
        "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
        "p50_ms": round(p50_ms, 3),
        "p99_ms": round(p99_ms, 3),
        "platform": platform,
    }


def _run_zipfpaged(np, platform: str) -> dict:
    """Paged-state A/B (PERF.md §30, ROADMAP item 1): zipf traffic
    over a key space ≥10× the resident page budget through the
    GUBER_PAGED plane, with a same-session GUBER_PAGED=0 dense
    control.

    Phases (each MEASURE_SECONDS):
      1. paged fill — populate the whole key space once (sequential:
         ascending slots pack pages contiguously, so the fill pays
         ~1 fault per page, not per key);
      2. paged zipf — the headline number: decisions/s with the tail
         faulting cold pages in and out, fault-rate and spill-p99
         recorded from the plane's own counters (never silent);
      3. hot A/B — a resident-sized working set through BOTH arms at
         equal resident load (the ≤10% acceptance bar);
      4. dense churn — the dense arm faced with the full key space:
         it cannot hold it (device array fixed at boot), so the
         intern table evicts and every evicted bucket's state is
         FORGOTTEN — the capacity wall this plane removes, recorded.
    """
    batch = min(BATCH, int(os.environ.get("BENCH_PAGED_BATCH", 1024)))
    page_size = int(os.environ.get("BENCH_PAGED_PAGE", 64))
    frames = batch  # a full batch of unique keys never segments
    resident_rows = frames * page_size
    ratio = max(10, int(os.environ.get("BENCH_PAGED_RATIO", 10)))
    n_keys = resident_rows * ratio
    alpha = ZIPF if ZIPF > 0 else 1.2
    from gubernator_tpu.core.engine import DecisionEngine

    saved = {
        k: os.environ.get(k)
        for k in ("GUBER_PAGED", "GUBER_PAGE_SIZE", "GUBER_PAGED_RESIDENT")
    }

    def _engine(paged: bool) -> DecisionEngine:
        if paged:
            os.environ["GUBER_PAGED"] = "1"
            os.environ["GUBER_PAGE_SIZE"] = str(page_size)
            os.environ["GUBER_PAGED_RESIDENT"] = str(frames)
        else:
            os.environ["GUBER_PAGED"] = "0"
        try:
            return DecisionEngine(
                capacity=n_keys if paged else resident_rows,
                max_kernel_width=max(8192, batch),
            )
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def _cols():
        return dict(
            behavior=np.zeros(batch, dtype=np.int32),
            hits=np.ones(batch, dtype=np.int64),
            limit=np.full(batch, 1_000_000, dtype=np.int64),
            duration=np.full(batch, 3_600_000, dtype=np.int64),
            burst=np.full(batch, 1_000_000, dtype=np.int64),
        )

    def _batches(idx_list):
        return [
            dict(
                keys=[b"pg_k%d" % i for i in idx.tolist()],
                algo=(np.asarray(idx) % 2).astype(np.int32),
                **_cols(),
            )
            for idx in idx_list
        ]

    rng = np.random.default_rng(0)
    zipf_batches = _batches(
        (rng.zipf(alpha, batch) - 1) % n_keys for _ in range(64)
    )
    hot_keys = resident_rows // 2  # well inside the frames, both arms
    hot_batches = _batches(
        (np.arange(batch, dtype=np.int64) + b * batch) % hot_keys
        for b in range(hot_keys // batch)
    )

    def _measure(engine, batches, seconds) -> tuple[float, int]:
        from collections import deque

        pending = deque()
        n_done = 0
        start = time.perf_counter()
        i = 0
        while True:
            pending.append(
                engine.apply_columnar(
                    **batches[i % len(batches)], want_async=True
                )
            )
            i += 1
            if len(pending) > PIPELINE_DEPTH:
                pending.popleft().get()
                n_done += batch
            if time.perf_counter() - start >= seconds:
                break
        while pending:
            pending.popleft().get()
            n_done += batch
        return n_done / (time.perf_counter() - start), n_done

    errors = 0
    paged = _engine(paged=True)
    assert paged.paging is not None and paged.capacity == resident_rows

    # Phase 1: fill the whole key space once, sequentially.
    t_fill = time.perf_counter()
    for lo in range(0, n_keys, batch):
        idx = np.arange(lo, min(lo + batch, n_keys), dtype=np.int64)
        b = _batches([idx % n_keys])[0]
        for col in b:
            if col != "keys":
                b[col] = b[col][: len(idx)]
        paged.apply_columnar(**b)
    fill_s = time.perf_counter() - t_fill
    fill_faults = paged.paging.faults

    # Phase 2: zipf over the full key space (latency sync, then
    # pipelined throughput).  Warm the duplicate-collapse program
    # family first — zipf batches repeat hot keys, a shape the
    # sequential fill never compiled.
    for i in range(WARMUP_BATCHES):
        paged.apply_columnar(**zipf_batches[i % len(zipf_batches)])
    lat_n = min(LATENCY_BATCHES, 50)
    lat = np.empty(lat_n, dtype=np.float64)
    for i in range(lat_n):
        t0 = time.perf_counter()
        paged.apply_columnar(**zipf_batches[i % len(zipf_batches)])
        lat[i] = time.perf_counter() - t0
    d0 = paged.paging.faults
    n0 = paged.requests_total
    zipf_rate, zipf_done = _measure(paged, zipf_batches, MEASURE_SECONDS)
    zipf_faults = paged.paging.faults - d0
    assert paged.requests_total - n0 == zipf_done

    # Phase 3a: paged hot path (first pass faults the working set in,
    # then measure resident-only).
    for b in hot_batches:
        paged.apply_columnar(**b)
    f_hot0 = paged.paging.faults
    hot_paged_rate, _ = _measure(paged, hot_batches, MEASURE_SECONDS)
    hot_phase_faults = paged.paging.faults - f_hot0

    plane = paged.paging
    paged_stats = {
        "page_size": page_size,
        "frames": frames,
        "resident_rows": resident_rows,
        "logical_keys": n_keys,
        "keyspace_ratio": ratio,
        "resident_ratio": round(resident_rows / n_keys, 4),
        "fill_seconds": round(fill_s, 2),
        "fill_faults": fill_faults,
        "zipf_faults": zipf_faults,
        "fault_rate": round(zipf_faults / max(zipf_done, 1), 6),
        "faults": plane.faults,
        "spills": plane.spills,
        "refills": plane.refills,
        "spill_p99_ms": round(plane.spill_duration.p99() * 1e3, 3),
        "refill_p99_ms": round(plane.refill_wait.p99() * 1e3, 3),
        "fault_p99_ms": round(plane.fault_duration.p99() * 1e3, 3),
        "hot_phase_faults": hot_phase_faults,
    }

    # Phase 3b + 4: the dense arm — equal resident footprint.
    dense = _engine(paged=False)
    assert dense.paging is None and dense.capacity == resident_rows
    for b in hot_batches:
        dense.apply_columnar(**b)
    hot_dense_rate, _ = _measure(dense, hot_batches, MEASURE_SECONDS)
    churn_rate, _ = _measure(dense, zipf_batches, MEASURE_SECONDS)

    hot_delta_pct = round(
        100.0 * (hot_paged_rate - hot_dense_rate) / hot_dense_rate, 2
    )
    return {
        "metric": "rate-limit decisions/sec, paged device state, zipf "
        f"alpha={alpha} over {n_keys} keys ({ratio}x the "
        f"{resident_rows} resident rows; batch={batch})",
        "value": round(zipf_rate, 1),
        "unit": "decisions/sec",
        "vs_baseline": round(zipf_rate / BASELINE_DECISIONS_PER_SEC, 2),
        "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 3),
        "p99_ms": round(float(np.percentile(lat, 99) * 1e3), 3),
        "platform": platform,
        "errors": errors,
        "paged": paged_stats,
        "hot": {
            "working_set": hot_keys,
            "paged_value": round(hot_paged_rate, 1),
            "dense_value": round(hot_dense_rate, 1),
            "delta_pct": hot_delta_pct,
        },
        "dense": {
            "keyspace_bound": resident_rows,
            "churn_value": round(churn_rate, 1),
            "note": "dense arm's device array is fixed at boot: under "
            f"the full {n_keys}-key space the intern table evicts and "
            "every evicted bucket is forgotten (state loss), the "
            "capacity wall the paged plane removes",
        },
    }


def _run_wire(np, platform: str, *, sketch: bool = False) -> dict:
    """Loopback-gRPC serving throughput: real daemon, real wire.

    Measures the SERVED path — pb decode → columnar fast path →
    engine → pb encode (gubernator_tpu/net/server.py) — which after
    VERDICT r1 item 2 is the same engine program as `_run_engine`.
    Client-side encode cost is excluded (payloads pre-serialized);
    responses are received but not parsed.

    sketch=True: BASELINE config 5 — every request carries
    Behavior.SKETCH, so decisions come from the count-min-sketch
    approximate limiter (O(1) memory at unbounded key cardinality)
    instead of the bucket engine.
    """
    import grpc

    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior

    wire_batch = min(BATCH, 1000)  # MAX_BATCH_SIZE on the wire
    n_threads = int(os.environ.get("BENCH_WIRE_THREADS", 8))
    behavior = int(Behavior.SKETCH) if sketch else 0
    # BENCH_WIRE_FAST=1: serve through the native h2 fast front with
    # native clients — measures the front at the wire-max batch (the
    # herd configs measure it at batch 1).  The front does not serve
    # the sketch route, so the combination is an explicit error rather
    # than a silently-grpc-measured artifact.
    fast = os.environ.get("BENCH_WIRE_FAST", "0") != "0"
    if fast and sketch:
        return {
            "metric": "rate-limit decisions/sec, native h2 fast front",
            "value": 0,
            "unit": "decisions/sec",
            "vs_baseline": 0,
            "platform": platform,
            "error": "BENCH_WIRE_FAST does not support the sketch mode "
            "(the fast front serves plain columnar decisions only)",
        }
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=CAPACITY,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        ledger=_ledger_enabled(),
        native_ledger=_native_ledger_enabled(),
        h2_fast_address="127.0.0.1:0" if fast else "",
        h2_fast_window=float(
            os.environ.get("BENCH_LOCAL_BATCH_WAIT", "0.002")
        ),
    )
    daemon = spawn_daemon(conf)
    try:
        if fast and not sketch:
            from gubernator_tpu.core import h2_client
            from gubernator_tpu.net.grpc_service import V1_SERVICE as _V1

            payloads = _build_payloads(pb, wire_batch, behavior=behavior)
            res = h2_client.bench_unary(
                daemon.h2_fast_address, f"/{_V1}/GetRateLimits",
                payloads[0], MEASURE_SECONDS, n_threads,
            )
            if res is None or res[0] == 0 or res[1] != 0:
                # NEVER fall through to the grpc path: the artifact
                # would be measured over a different stack while
                # labeled "fast front".
                return {
                    "metric": "rate-limit decisions/sec, single node, "
                    "native h2 fast front",
                    "value": 0,
                    "unit": "decisions/sec",
                    "vs_baseline": 0,
                    "platform": platform,
                    "error": (
                        "native h2 client unavailable or errored: "
                        f"res={None if res is None else (res[0], res[1])}"
                    ),
                }
            rpcs, errors, lats, _frame, connected = res
            rate = rpcs * wire_batch / MEASURE_SECONDS
            return {
                "ledger": _ledger_stats_inproc(daemon),
                **_observability_stats(daemon),
                "metric": "rate-limit decisions/sec, single node, "
                f"native h2 fast front (batch={wire_batch}, "
                f"{connected} native clients, {wire_batch} hot keys)",
                "value": round(rate, 1),
                "unit": "decisions/sec",
                "vs_baseline": round(
                    rate / BASELINE_DECISIONS_PER_SEC, 2
                ),
                "p50_ms": round(
                    float(np.percentile(lats, 50)) * 1e3, 3
                ) if len(lats) else None,
                "p99_ms": round(
                    float(np.percentile(lats, 99)) * 1e3, 3
                ) if len(lats) else None,
                "platform": platform,
            }
        n_procs = int(os.environ.get("BENCH_WIRE_PROCS", "0"))
        if n_procs:
            rate, p50_ms, p99_ms = _drive_grpc_procs(
                np, [daemon.grpc_address], n_procs, wire_batch,
                behavior=behavior,
            )
            n_threads = n_procs  # for the metric label
        else:
            payloads = _build_payloads(pb, wire_batch, behavior=behavior)
            rate, p50_ms, p99_ms = _drive_grpc(
                np, [daemon.grpc_address], payloads, n_threads, wire_batch
            )
        label = (
            "rate-limit decisions/sec, count-min-sketch approximate "
            "limiter over loopback gRPC "
            if sketch
            else "rate-limit decisions/sec, single node, loopback gRPC "
        )
        return {
            "ledger": _ledger_stats_inproc(daemon),
            **_observability_stats(daemon),
            "metric": label
            + f"(batch={wire_batch}, {n_threads} client threads, {N_KEYS} hot keys)",
            "value": round(rate, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": p50_ms,
            "p99_ms": p99_ms,
            "platform": platform,
        }
    finally:
        daemon.close()


def _build_payloads(pb, wire_batch: int, behavior: int) -> list:
    """Pre-serialized GetRateLimitsReq payloads cycling the key space."""
    payloads = []
    for b in range(max(1, min(N_KEYS // wire_batch, 64))):
        msg = pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="bench",
                    unique_key="%dk" % ((b * wire_batch + i) % N_KEYS),
                    hits=1,
                    limit=1_000_000,
                    duration=3_600_000,
                    algorithm=i % 2,
                    behavior=behavior,
                    burst=1_000_000,
                )
                for i in range(wire_batch)
            ]
        )
        payloads.append(msg.SerializeToString())
    return payloads


def _client_proc_main() -> int:
    """Subprocess closed-loop gRPC client (BENCH_WIRE_PROCS mode).

    argv: --wire-client <addr> <seconds> <batch> <n_keys> <behavior>
    Emits one JSON line {count, lats: [...] (downsampled s)} on stdout.
    Lives in bench.py so the child needs no extra file and inherits the
    import path."""
    import grpc  # noqa: F401 (ensures import error surfaces in child)
    import numpy as np

    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    addr, seconds, batch, n_keys, behavior = sys.argv[2:7]
    seconds, batch, n_keys, behavior = (
        float(seconds), int(batch), int(n_keys), int(behavior),
    )
    globals()["N_KEYS"] = n_keys
    payloads = _build_payloads(pb, batch, behavior=behavior)
    import grpc as g

    from gubernator_tpu.net.grpc_service import V1_SERVICE

    ch = g.insecure_channel(addr)
    call = ch.unary_unary(
        f"/{V1_SERVICE}/GetRateLimits",
        request_serializer=lambda raw: raw,
        response_deserializer=lambda raw: raw,
    )
    call(payloads[0])  # warm / connect
    lats = []
    count = 0
    start = time.perf_counter()
    deadline = start + seconds
    i = 0
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        call(payloads[i % len(payloads)])
        lats.append(time.perf_counter() - t0)
        count += batch
        i += 1
    elapsed = time.perf_counter() - start
    ch.close()
    if len(lats) > 10_000:  # bound the pipe payload
        lats = list(np.random.default_rng(0).choice(lats, 10_000, replace=False))
    print(
        json.dumps({"count": count, "elapsed": elapsed, "lats": lats}),
        flush=True,
    )
    return 0


def _drive_grpc_procs(
    np, addrs: list, n_procs: int, items_per_rpc: int, behavior: int = 0,
    seconds: float | None = None,
):
    """Closed-loop load from SUBPROCESS clients: the server's GIL is
    not shared with the load generator, so the measurement reflects
    server capacity, not client/server GIL thrash.  Returns
    (items/sec, p50_ms, p99_ms)."""
    seconds = MEASURE_SECONDS if seconds is None else seconds
    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__), "--wire-client",
                addrs[t % len(addrs)], str(seconds),
                str(items_per_rpc), str(N_KEYS), str(behavior),
            ],
            stdout=subprocess.PIPE,
            text=True,
        )
        for t in range(n_procs)
    ]
    rate = 0.0
    lats: list = []
    for p in procs:
        out, _ = p.communicate(timeout=3 * seconds + 180)
        line = [l for l in out.strip().splitlines() if l.startswith("{")][-1]
        d = json.loads(line)
        # Each child measures its own closed-loop window; the summed
        # per-child rates estimate concurrent capacity without charging
        # interpreter startup to the denominator.
        rate += d["count"] / max(d["elapsed"], 1e-6)
        lats.extend(d["lats"])
    arr = np.asarray(lats)
    p50 = round(float(np.percentile(arr, 50)) * 1e3, 3) if arr.size else None
    p99 = round(float(np.percentile(arr, 99)) * 1e3, 3) if arr.size else None
    return rate, p50, p99


def _drive_grpc(np, addrs: list, payloads: list, n_threads: int, items_per_rpc: int):
    """Closed-loop gRPC load: n_threads workers round-robin over
    `addrs`, replaying pre-serialized payloads.  BENCH_WARM_SECONDS of
    load runs unrecorded first so the measurement reflects steady
    state, not cold XLA compiles and first-window flush monsters.
    Returns (items/sec, p50_ms, p99_ms)."""
    import grpc

    from gubernator_tpu.net.grpc_service import V1_SERVICE

    warm_seconds = float(os.environ.get("BENCH_WARM_SECONDS", 0.0))
    barrier = threading.Barrier(n_threads + 1)
    measuring = threading.Event()
    if not warm_seconds:
        measuring.set()
    stop = threading.Event()
    counts = [0] * n_threads
    lats: list = [None] * n_threads

    def worker(tid: int) -> None:
        mylat = []
        try:
            ch = grpc.insecure_channel(addrs[tid % len(addrs)])
            call = ch.unary_unary(
                f"/{V1_SERVICE}/GetRateLimits",
                request_serializer=lambda raw: raw,
                response_deserializer=lambda raw: raw,
            )
            call(payloads[tid % len(payloads)])  # warmup / connect
        finally:
            # A failed warmup must not strand main() on the barrier
            # (the deadline thread would misreport it as a hang).
            barrier.wait()
        i = tid
        while not stop.is_set():
            t0 = time.perf_counter()
            call(payloads[i % len(payloads)])
            if measuring.is_set():
                mylat.append(time.perf_counter() - t0)
                counts[tid] += items_per_rpc
            i += n_threads
        lats[tid] = mylat
        ch.close()

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    if warm_seconds:
        time.sleep(warm_seconds)
        measuring.set()
    start = time.perf_counter()
    time.sleep(MEASURE_SECONDS)
    stop.set()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    all_lat = np.asarray([x for ml in lats if ml for x in ml])
    rate = sum(counts) / elapsed
    p50 = round(float(np.percentile(all_lat, 50)) * 1e3, 3) if all_lat.size else None
    p99 = round(float(np.percentile(all_lat, 99)) * 1e3, 3) if all_lat.size else None
    return rate, p50, p99


def _herd_result_valid(pb, res) -> bool:
    """Gate on the native loop's validity hooks: a trailers-only error
    reply also carries END_STREAM, so the raw rpc count alone cannot
    distinguish served decisions from a wall of UNIMPLEMENTED/
    UNAVAILABLE.  Require real throughput, a sane error rate, and that
    the captured first response decodes as a well-formed
    GetRateLimitsResp."""
    import struct

    rpcs, errors, _lats, frame, connected = res
    if rpcs <= 0 or errors > rpcs * 0.01 or connected <= 0:
        return False
    if len(frame) < 5 or frame[0] != 0:
        return False
    try:
        (ln,) = struct.unpack(">I", frame[1:5])
        resp = pb.GetRateLimitsResp.FromString(frame[5 : 5 + ln])
    except Exception:  # noqa: BLE001 — any decode failure invalidates
        return False
    return len(resp.responses) == 1 and not resp.responses[0].error


def _run_herd(np, platform: str, *, force_fast: bool = False) -> dict:
    """Thundering herd: many concurrent single-item requests for the
    SAME hot key (reference: benchmark_test.go BenchmarkServer's
    thundering-herd subtest) — measures per-request wire overhead plus
    the hot-key collapse under maximal contention.

    Load comes from the native h2 client loop (core/h2_client.py) when
    it builds: C threads cost ~nothing, so the number measures SERVER
    capacity — the role the reference's Go clients play in its own
    benchmark (README.md:97-104).  On this one-core host a grpc-python
    closed loop burns ~250µs/RPC of *client* Python on the server's
    core.  BENCH_HERD_NATIVE=0 forces the Python-client loop.

    force_fast (the herdnative config): always serve through the h2
    fast front, where the native decision plane answers hot-key RPCs
    inside the C connection threads (GUBER_NATIVE_LEDGER=0 for the
    same-session A/B: identical front, window path only)."""
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    import grpc

    n_threads = int(os.environ.get("BENCH_HERD_THREADS", 32))
    # BENCH_HERD_FAST=1: serve through the native h2 fast front
    # (net/h2_fast.py) — zero per-RPC Python; the C side owns framing
    # and the group-commit window.
    fast = force_fast or os.environ.get("BENCH_HERD_FAST", "0") != "0"
    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=CAPACITY,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        ledger=_ledger_enabled(),
        native_ledger=_native_ledger_enabled(),
        # The herd is what the group-commit window exists for: the
        # concurrent single-item RPCs share one engine dispatch per
        # window (net/wire_window.py).  2ms groups ~arrival_rate×2ms
        # requests per engine dispatch; the measured knee is at
        # ~2-4ms on this host (PERF.md §13).
        local_batch_wait=float(
            os.environ.get("BENCH_LOCAL_BATCH_WAIT", "0.002")
        ),
        h2_fast_address="127.0.0.1:0" if fast else "",
        h2_fast_window=float(
            os.environ.get("BENCH_LOCAL_BATCH_WAIT", "0.002")
        ),
    )
    daemon = spawn_daemon(conf)
    try:
        # One payload for BOTH load paths — native and fallback must
        # measure the identical request.
        payload = pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="herd", unique_key="hot", hits=1,
                    limit=10**12, duration=3_600_000,
                )
            ]
        ).SerializeToString()
        if os.environ.get("BENCH_HERD_NATIVE", "1") != "0":
            from gubernator_tpu.core import h2_client

            res = h2_client.bench_unary(
                daemon.h2_fast_address if fast else daemon.grpc_address,
                f"/{V1_SERVICE}/GetRateLimits",
                payload,
                MEASURE_SECONDS,
                n_threads,
            )
            if res is not None and _herd_result_valid(pb, res):
                rpcs, errors, lats, _frame, connected = res
                rate = rpcs / MEASURE_SECONDS
                front_stats = (
                    daemon.h2_fast.stats()
                    if fast and getattr(daemon, "h2_fast", None)
                    else None
                )
                if fast:
                    front = "native h2 fast front"
                    if front_stats and front_stats.get("native_rpcs"):
                        front = (
                            "native h2 fast front + decision plane "
                            f"({front_stats['lanes']} lanes)"
                        )
                else:
                    front = "grpc listener"
                return {
                    "ledger": _ledger_stats_inproc(daemon),
                    "front": front_stats,
                    **_observability_stats(daemon),
                    "metric": "rate-limit decisions/sec, thundering herd "
                    f"({connected} concurrent native h2 clients via "
                    f"{front}, 1 hot key, single-item RPCs)",
                    "value": round(rate, 1),
                    "unit": "decisions/sec",
                    "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
                    "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3)
                    if len(lats) else None,
                    "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3)
                    if len(lats) else None,
                    "errors": int(errors),
                    "platform": platform,
                }
        barrier = threading.Barrier(n_threads + 1)
        stop = threading.Event()
        counts = [0] * n_threads
        lats: list = [None] * n_threads

        def worker(tid):
            mylat = []
            try:
                ch = grpc.insecure_channel(daemon.grpc_address)
                call = ch.unary_unary(
                    f"/{V1_SERVICE}/GetRateLimits",
                    request_serializer=lambda raw: raw,
                    response_deserializer=lambda raw: raw,
                )
                call(payload)
            finally:
                barrier.wait()
            while not stop.is_set():
                t0 = time.perf_counter()
                call(payload)
                mylat.append(time.perf_counter() - t0)
                counts[tid] += 1
            lats[tid] = mylat
            ch.close()

        threads = [
            threading.Thread(target=worker, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        start = time.perf_counter()
        time.sleep(MEASURE_SECONDS)
        stop.set()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start
        import numpy as _np

        all_lat = _np.asarray([x for ml in lats if ml for x in ml])
        rate = sum(counts) / elapsed
        return {
            "ledger": _ledger_stats_inproc(daemon),
            **_observability_stats(daemon),
            "metric": "rate-limit decisions/sec, thundering herd "
            f"({n_threads} concurrent clients, 1 hot key, single-item RPCs)",
            "value": round(rate, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": round(float(_np.percentile(all_lat, 50)) * 1e3, 3)
            if all_lat.size
            else None,
            "p99_ms": round(float(_np.percentile(all_lat, 99)) * 1e3, 3)
            if all_lat.size
            else None,
            "platform": platform,
        }
    finally:
        daemon.close()



def _observability_stats(daemon) -> dict:
    """The per-stage latency budget (real p50/p99 now, not means) plus
    the native event ring's stage histograms and drop counters —
    embedded in every in-process-daemon artifact so a regression in
    either is visible in the committed JSON, not just on a live
    /metrics scrape."""
    out = {"stage_budget": daemon.stage_budget()}
    ev = getattr(daemon.instance, "native_events", None)
    if ev is not None:
        out["native_events"] = ev.stats()
    return out


def _run_feeder(np, platform: str) -> dict:
    """Feeder microbench + same-session feeder on/off front A/B.

    Part 1 — the pack line, measured in isolation: rows/s of the C
    columnar feeder (wire bytes → device-ready columns: decode, FNV
    hashes, column append into the lock-free ring; sink windows, C
    producer threads — zero Python anywhere) against the Python
    columnar line (wire_codec.decode_reqs per RPC with fresh numpy
    columns — the pre-feeder per-window ingest work) on the SAME
    payloads.  The headline value is the C pack rate; the acceptance
    bar is ≥10M rows/s or ≥5× the Python line (ISSUE 11).

    Part 2 — the served path: the herd workload against the fast
    front once with the feeder on and once with GUBER_NATIVE_FEEDER=0
    (the byte window path), same session.  Each arm embeds its native
    event-ring stage histograms, so the artifact carries the
    window_wait vs feeder_ring_wait p99 attribution the §23 tail
    analysis needs.
    """
    from gubernator_tpu.core.native_plane import NativeColumnarFeeder
    from gubernator_tpu.net import wire_codec
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.service import COLUMNAR_DISQUALIFIERS

    items_per_rpc = int(os.environ.get("BENCH_FEEDER_ITEMS", 100))
    reps = int(os.environ.get("BENCH_FEEDER_REPS", 20_000))
    # Producer threads: leave one core for the recycle thread.  With
    # producers + recycler oversubscribing the vCPUs, this gVisor
    # box's futex/yield costs collapse the pipeline ~30× (measured:
    # 2 producers on 2 cores degrade 18M → 0.6M rows/s after a few
    # seconds; 1 producer is stable).  Real conn threads never spin —
    # they fall back to the byte path on ring pressure — so the
    # pathological regime is bench-only.
    threads = int(os.environ.get("BENCH_FEEDER_THREADS", 0)) or max(
        1, min(4, (os.cpu_count() or 1) - 1)
    )
    body = pb.GetRateLimitsReq(
        requests=[
            pb.RateLimitReq(
                name="feed", unique_key=f"user_{i}_key", hits=1,
                limit=10**9, duration=60_000,
                algorithm=i % 2,
            )
            for i in range(items_per_rpc)
        ]
    ).SerializeToString()

    # -- part 1: pack lines ------------------------------------------
    # Ring shape measured on this box (2 cores): more, smaller
    # windows pipeline best when producers and the recycle thread
    # share cores — n_slots=8 / flush=2048 is flat-optimal from 1 to
    # 2 producer threads (the 4/4096 default optimizes the SERVED
    # path, where Python window serve dominates the recycle).
    feeder = NativeColumnarFeeder(
        disqualify_mask=COLUMNAR_DISQUALIFIERS,
        n_slots=8, max_rows=8192, flush_rows=2048,
        window_s=0.0002, window_handler=None,
    )
    # Median of several draws: single draws on this shared 2-core box
    # swing >2x with scheduler luck (the herdtrace precedent -- all
    # draws are committed in the artifact).
    pack_draws = int(os.environ.get("BENCH_FEEDER_DRAWS", 5))
    pack_rates = []
    packed = 0
    try:
        feeder.bench_pack(body, items_per_rpc, 200, threads)  # warmup
        for _ in range(pack_draws):
            t0 = time.perf_counter()
            got = feeder.bench_pack(body, items_per_rpc, reps, threads)
            pack_dt = time.perf_counter() - t0
            packed += got
            pack_rates.append(got / pack_dt if pack_dt > 0 else 0.0)
        feeder_stats = feeder.stats()
    finally:
        feeder.close()
    pack_rate = float(np.median(pack_rates))

    # The Python columnar line: one decode_reqs per RPC (fresh numpy
    # columns each call — exactly the per-window work the dispatch
    # thread used to do, minus the ctypes body copies it ALSO paid).
    py_reps = max(200, int(reps / 20))
    wire_codec.decode_reqs(body, items_per_rpc, 0)  # warmup/build
    py_rates = []
    for _ in range(pack_draws):
        t0 = time.perf_counter()
        for _ in range(py_reps):
            dec = wire_codec.decode_reqs(body, items_per_rpc, 0)
        py_dt = time.perf_counter() - t0
        assert dec is not None and dec.n == items_per_rpc
        py_rates.append(
            py_reps * items_per_rpc / py_dt if py_dt > 0 else 0.0
        )
    py_rate = float(np.median(py_rates))

    # -- part 2: front A/B (same session) ----------------------------
    def _arm(feeder_on: bool, clients: Optional[int] = None) -> dict:
        prev = os.environ.get("GUBER_NATIVE_FEEDER")
        prev_threads = os.environ.get("BENCH_HERD_THREADS")
        os.environ["GUBER_NATIVE_FEEDER"] = "1" if feeder_on else "0"
        if clients is not None:
            os.environ["BENCH_HERD_THREADS"] = str(clients)
        try:
            out = _run_herd(np, platform, force_fast=True)
        finally:
            if prev is None:
                os.environ.pop("GUBER_NATIVE_FEEDER", None)
            else:
                os.environ["GUBER_NATIVE_FEEDER"] = prev
            if clients is not None:
                if prev_threads is None:
                    os.environ.pop("BENCH_HERD_THREADS", None)
                else:
                    os.environ["BENCH_HERD_THREADS"] = prev_threads
        stages = (out.get("native_events") or {}).get("stages") or {}
        return {
            "value": out.get("value"),
            "p50_ms": out.get("p50_ms"),
            "p99_ms": out.get("p99_ms"),
            "errors": out.get("errors"),
            "front": out.get("front"),
            "window_wait": stages.get("window_wait"),
            "window_serve": stages.get("window_serve"),
            "feeder_pack": stages.get("feeder_pack"),
            "feeder_ring_wait": stages.get("feeder_ring_wait"),
            "feeder_serve": stages.get("feeder_serve"),
        }

    # Alternating off/on pairs, medians reported (single pairs swing
    # with scheduler luck; herdtrace treatment — all draws committed).
    ab_pairs = int(os.environ.get("BENCH_FEEDER_AB_PAIRS", 3))
    arms_off = []
    arms_on = []
    for _ in range(ab_pairs):
        arms_off.append(_arm(False))
        arms_on.append(_arm(True))

    def _median_arm(arms) -> dict:
        # The median-BY-THROUGHPUT draw, reported wholesale: its own
        # p99 and stage histograms stay internally consistent (mixing
        # a median value with another draw's stage attribution would
        # let the embedded tail numbers contradict the headline they
        # sit next to).  Per-draw p99 lists ride separately below.
        ranked = sorted(arms, key=lambda a: a.get("value") or 0.0)
        return dict(ranked[len(ranked) // 2])

    arm_off = _median_arm(arms_off)
    arm_on = _median_arm(arms_on)
    # Tail-analysis arm: the same feeder front WITHOUT the bench's
    # deliberate core oversubscription (closed-loop C clients ≫
    # cores).  At 32-on-2-cores the queue-wait p99 measures scheduler
    # starvation of the one Python serve thread, identically on both
    # ingest paths; this arm shows what the ring wait is when the
    # serve thread can actually run (PERF.md §25's tail analysis).
    light_clients = int(os.environ.get("BENCH_FEEDER_LIGHT_THREADS", 0)) or max(
        2, 4 * (os.cpu_count() or 1)
    )
    arm_light = _arm(True, clients=light_clients)

    def _p99(arm: dict, stage: str):
        s = arm.get(stage)
        return s.get("p99_ms") if isinstance(s, dict) else None

    return {
        "metric": (
            "columnar feeder pack throughput (wire bytes → "
            f"device-ready columns, {threads} C threads, "
            f"{items_per_rpc}-item RPCs) + same-session front A/B"
        ),
        "value": round(pack_rate, 1),
        "unit": "rows/sec packed",
        "vs_baseline": round(pack_rate / max(py_rate, 1.0), 2),
        "feeder_rows_packed": int(packed),
        "pack_rate_draws": [round(r, 1) for r in pack_rates],
        "python_line_draws": [round(r, 1) for r in py_rates],
        "python_line_rows_per_s": round(py_rate, 1),
        "pack_speedup": round(pack_rate / max(py_rate, 1.0), 2),
        "feeder_ring": {
            k: feeder_stats[k]
            for k in (
                "feeder_windows", "feeder_ring_full", "feeder_declined",
            )
        },
        "front_ab": {
            "feeder_on": arm_on,
            "feeder_off": arm_off,
            "feeder_on_light": {"clients": light_clients, **arm_light},
            # The §23 tail comparison: the queue wait a fall-through
            # RPC pays before its window serves, per ingest path.
            "window_wait_p99_ms_off": sorted(
                _p99(a, "window_wait") or 0.0 for a in arms_off
            )[len(arms_off) // 2],
            "feeder_ring_wait_p99_ms_on": sorted(
                _p99(a, "feeder_ring_wait") or 0.0 for a in arms_on
            )[len(arms_on) // 2],
            "window_wait_p99_draws_off": [
                _p99(a, "window_wait") for a in arms_off
            ],
            "feeder_ring_wait_p99_draws_on": [
                _p99(a, "feeder_ring_wait") for a in arms_on
            ],
            "feeder_ring_wait_p99_ms_light": _p99(
                arm_light, "feeder_ring_wait"
            ),
        },
        "platform": platform,
    }


def _run_connscale(np, platform: str) -> dict:
    """Connection-scale ramp + thread-per-conn A/B (PERF.md §26).

    Each rung gets a FRESH daemon (stage histograms, conn gauges and
    fd counts then attribute to that rung alone) whose fast front runs
    the epoll reactor plane; the load comes from the epoll connscale
    client in a SUBPROCESS (fds are the scarce resource — the server
    half of every connection lives in THIS process, the client half in
    the child, so each side gets the full RLIMIT_NOFILE budget).  The
    client holds `rung` connections open and runs a closed unary loop
    on BENCH_CONNSCALE_ACTIVE of them from one epoll thread — unlike
    the 32-thread herd generator, it cannot starve the server's serve
    thread (§25), so the feeder_ring_wait p99 each rung embeds is the
    server's own behavior, not scheduler noise.

    The A/B arm re-runs the FIRST rung (default 1k — the biggest load
    the thread-per-conn plane can reasonably hold) with
    GUBER_H2_EVENT_FRONT=0: same instance shape, same client, equal
    load; `ab_equal_load` carries both rates.  The native decision
    plane is disabled in BOTH arms so every RPC traverses the serve
    plane — the ring-wait attribution is the point of the exercise.
    """
    import resource

    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
        soft = hard
    rungs = [
        int(x)
        for x in os.environ.get(
            "BENCH_CONNSCALE_RUNGS", "1000,5000,10000"
        ).split(",")
        if x.strip()
    ]
    # No silent caps: a rung beyond the per-process fd budget is
    # clamped AND recorded (the 100k rung needs a raised ulimit).
    fd_budget = soft - 2048
    clamped = [r for r in rungs if r > fd_budget]
    rungs = sorted({min(r, fd_budget) for r in rungs})
    # 16 active closed loops ≈ 4-5k dec/s through the serve plane on
    # this 2-core box — real load, while the one-thread client leaves
    # the serve thread schedulable (at 64 the CLIENT's own CPU puts
    # ~2.2 busy threads on 2 cores and the ring-wait tail measures
    # preemption again — the §25 lesson, now client-side; at 24 the
    # tail sits right AT the 10 ms §26 bar on good draws and over it
    # on noisy ones).
    active = int(os.environ.get("BENCH_CONNSCALE_ACTIVE", 16))
    cl_threads = int(os.environ.get("BENCH_CONNSCALE_CLIENT_THREADS", 1))
    # Reactor count for the event arms.  The production default
    # (ncpu−1, one core reserved for the serve plane) is right when
    # cores are plentiful; on a ≤2-core box it leaves ONE pinned
    # reactor serializing all ingress while the threaded arm spreads
    # over every core — measured −10% closed-loop.  The bench's job is
    # to compare FRONTS, not affinity policies, so on tiny boxes it
    # runs ncpu floating reactors (recorded per-row as `reactors`).
    ncpu = os.cpu_count() or 1
    reactors_env = os.environ.get(
        "BENCH_CONNSCALE_REACTORS", str(ncpu) if ncpu <= 2 else "0"
    )
    payload = pb.GetRateLimitsReq(
        requests=[
            pb.RateLimitReq(
                name="cs", unique_key="hot", hits=1, limit=10**12,
                duration=3_600_000,
            )
        ]
    ).SerializeToString()

    def _fd_count() -> int:
        try:
            return len(os.listdir("/proc/self/fd"))
        except OSError:
            return -1

    # Exact tail attribution: the collector's log2 histograms resolve
    # one OCTAVE (a true 6 ms p99 reads 11.59), useless against a
    # 10 ms bar — so the collector is parked (1h interval) and the
    # ring is drained RAW here, with real percentiles over the
    # nanosecond durations.  The ring is sized for a full measurement
    # window of records.
    _drain_buf = np.zeros(4 * 262144, dtype=np.int64)

    def _drain_raw(front):
        chunks = []
        while True:
            n = front.drain_events(_drain_buf)
            if n <= 0:
                break
            chunks.append(_drain_buf[: 4 * n].reshape(n, 4).copy())
        return (
            np.concatenate(chunks)
            if chunks
            else np.zeros((0, 4), dtype=np.int64)
        )

    def _stage_stats(rec) -> dict:
        from gubernator_tpu.utils.native_events import STAGES

        out = {}
        for kind, stage in STAGES.items():
            durs = rec[rec[:, 0] == kind][:, 2]
            if len(durs):
                out[stage] = {
                    "count": int(len(durs)),
                    "p50_ms": round(
                        float(np.percentile(durs, 50)) / 1e6, 3
                    ),
                    "p99_ms": round(
                        float(np.percentile(durs, 99)) / 1e6, 3
                    ),
                    "max_ms": round(float(durs.max()) / 1e6, 3),
                }
        return out

    def _arm(n_conns: int, event_front: bool) -> dict:
        prev_env = {
            k: os.environ.get(k)
            for k in (
                "GUBER_H2_EVENT_FRONT", "GUBER_H2_REACTORS",
                "GUBER_NATIVE_EVENTS_CAP", "GUBER_NATIVE_EVENTS_INTERVAL",
            )
        }
        os.environ["GUBER_H2_EVENT_FRONT"] = "1" if event_front else "0"
        os.environ["GUBER_H2_REACTORS"] = reactors_env
        os.environ["GUBER_NATIVE_EVENTS_CAP"] = "262144"
        os.environ["GUBER_NATIVE_EVENTS_INTERVAL"] = "3600"
        try:
            conf = DaemonConfig(
                grpc_listen_address="127.0.0.1:0",
                http_listen_address="127.0.0.1:0",
                cache_size=CAPACITY,
                peer_discovery_type="none",
                device_count=1,
                sweep_interval=0.0,
                ledger=_ledger_enabled(),
                native_ledger=False,  # every RPC hits the serve plane
                local_batch_wait=0.002,
                h2_fast_address="127.0.0.1:0",
                # 1 ms group window: the ring wait p99 measures the
                # serve plane's HEALTH (starvation shows up as queue
                # wait far beyond the window), so the deliberate wait
                # should be small against the 10 ms §26 bar.
                h2_fast_window=float(
                    os.environ.get("BENCH_CONNSCALE_WINDOW", "0.001")
                ),
            )
            daemon = spawn_daemon(conf)
        finally:
            for k, v in prev_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        try:
            # Warm the serve path (XLA compiles, first-window flush
            # monsters) BEFORE the measured client: cold-compile
            # hundreds-of-ms windows would otherwise land in the ring
            # wait tail this mode exists to attribute.
            from gubernator_tpu.core import h2_client as _h2c

            _h2c.bench_unary(
                daemon.h2_fast_address,
                "/pb.gubernator.V1/GetRateLimits", payload, 0.5, 2,
            )
            _drain_raw(daemon.h2_fast)  # warmup stays out of the tail
            proc = subprocess.Popen(
                [
                    sys.executable,
                    os.path.join(
                        os.path.dirname(os.path.abspath(__file__)),
                        "scripts", "connscale_client.py",
                    ),
                    daemon.h2_fast_address, str(n_conns), str(active),
                    str(MEASURE_SECONDS), str(cl_threads),
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
                env=dict(os.environ, CONNSCALE_PAYLOAD_HEX=payload.hex()),
            )
            peak_conns = peak_fds = 0
            while proc.poll() is None:
                cs = daemon.h2_fast.conn_stats()
                peak_conns = max(peak_conns, cs["conns_open"])
                peak_fds = max(peak_fds, _fd_count())
                time.sleep(0.25)
            out, err = proc.communicate(timeout=60)
            try:
                client = json.loads(out.strip().splitlines()[-1])
            except (ValueError, IndexError):
                client = {
                    "error": f"client rc={proc.returncode}: {err[-300:]}"
                }
            stages = _stage_stats(_drain_raw(daemon.h2_fast))
            ring = daemon.h2_fast.ring_stats()
            front = daemon.h2_fast.stats()
            ring_wait = stages.get("feeder_ring_wait") or stages.get(
                "window_wait"
            )
            return {
                "conns": n_conns,
                "event_front": bool(front.get("event_front")),
                "reactors": front.get("reactors"),
                "connected": client.get("connected"),
                "alive_at_end": client.get("alive_at_end"),
                "ramp_ms": client.get("ramp_ms"),
                "rate": round(client.get("rate") or 0.0, 1),
                "p50_ms": client.get("p50_ms"),
                "p99_ms": client.get("p99_ms"),
                "client_errors": client.get("errors"),
                "client_error": client.get("error"),
                "server_errors": front.get("errors"),
                "server_rpcs": front.get("rpcs"),
                "conns_open_peak": peak_conns,
                "server_fd_peak": peak_fds,
                "feeder_ring_wait_p99_ms": (
                    ring_wait or {}
                ).get("p99_ms"),
                "ring_dropped": ring.get("dropped"),
                "stages": stages,
            }
        finally:
            daemon.close()

    rows = [_arm(r, True) for r in rungs]
    # A/B at equal load: the smallest rung on the thread-per-conn
    # plane (a 10k-thread arm would measure the scheduler, not the
    # front — which is itself the finding, but not a useful number).
    # Alternating event/threaded pairs with the delta as the MEDIAN OF
    # PER-PAIR DELTAS — single draws on this 2-core box swing ±20%
    # with scheduler luck (the herdtrace treatment; all draws
    # committed).
    ab_conns = int(
        os.environ.get("BENCH_CONNSCALE_THREADED_CONNS", rungs[0])
    )
    ab_pairs = int(os.environ.get("BENCH_CONNSCALE_AB_PAIRS", 3))
    pair_deltas = []
    ev_arms = []
    th_arms = []
    for _ in range(ab_pairs):
        e = _arm(ab_conns, True)
        t = _arm(ab_conns, False)
        ev_arms.append(e)
        th_arms.append(t)
        if t["rate"]:
            pair_deltas.append(
                round((e["rate"] - t["rate"]) / t["rate"] * 100.0, 2)
            )

    def _median_arm(arms):
        ranked = sorted(arms, key=lambda a: a.get("rate") or 0.0)
        return dict(ranked[len(ranked) // 2])

    event_match = _median_arm(ev_arms)
    threaded = _median_arm(th_arms)
    top = rows[-1]
    ev_rate = event_match["rate"] or 0.0
    th_rate = threaded["rate"] or 0.0
    return {
        "metric": (
            "rate-limit decisions/sec under connection scale "
            f"(epoll event front, {top['conns']} held connections, "
            f"{active} active closed loops, {cl_threads}-thread epoll "
            "client)"
        ),
        "value": top["rate"],
        "unit": "decisions/sec",
        "vs_baseline": round(
            (top["rate"] or 0.0) / BASELINE_DECISIONS_PER_SEC, 2
        ),
        "p50_ms": top["p50_ms"],
        "p99_ms": top["p99_ms"],
        "conns_held": top["conns_open_peak"],
        "errors": (top["client_errors"] or 0)
        + (top["server_errors"] or 0),
        "ring_wait_p99_ms_top": top["feeder_ring_wait_p99_ms"],
        "rungs": rows,
        "rungs_clamped_by_nofile": clamped,
        "nofile_limit": soft,
        "ab_equal_load": {
            "conns": ab_conns,
            "event_rate": ev_rate,
            "threaded_rate": th_rate,
            "event_delta_pct": (
                sorted(pair_deltas)[len(pair_deltas) // 2]
                if pair_deltas
                else None
            ),
            "pair_deltas_pct": pair_deltas,
            "event_rate_draws": [a["rate"] for a in ev_arms],
            "threaded_rate_draws": [a["rate"] for a in th_arms],
            "event_arm": event_match,
            "threaded_arm": threaded,
        },
        "platform": platform,
    }


def _run_herdtrace(np, platform: str) -> dict:
    """Tracing A/B, one session: herdfast with GUBER_TRACING effectively
    off vs with the in-memory recorder + tail sampling live.  Run as
    BENCH_TRACE_PAIRS alternating off/on pairs (default 3) and compare
    the per-arm MEDIANS: single-pair deltas on this shared sandbox
    swing ±9% run-to-run (three observed draws: +0.5%, −9.2%, +9.4%),
    which would let one lucky/unlucky pair tell any story about a
    sub-1% effect.  The artifact carries both medians, every draw, the
    median delta, and the flight recorder's tail attribution (which
    stage the retained tail trees actually spent their milliseconds
    in)."""
    from gubernator_tpu.utils import tracing

    pairs = max(1, int(os.environ.get("BENCH_TRACE_PAIRS", "3")))
    tracer = tracing.InMemoryTracer(max_spans=50_000)
    off_runs, on_runs = [], []
    off_lats, on_lats = {"p50_ms": [], "p99_ms": []}, {
        "p50_ms": [], "p99_ms": [],
    }
    off = on = None
    for _ in range(pairs):
        tracing.set_tracer(None)
        off = _run_herd(np, platform, force_fast=True)
        off_runs.append(off.get("value") or 0)
        for k in off_lats:
            if off.get(k) is not None:
                off_lats[k].append(off[k])
        tracing.set_tracer(tracer)
        try:
            on = _run_herd(np, platform, force_fast=True)
        finally:
            tracing.set_tracer(None)
        on_runs.append(on.get("value") or 0)
        for k in on_lats:
            if on.get(k) is not None:
                on_lats[k].append(on[k])
    off_v = float(np.median(off_runs))
    on_v = float(np.median(on_runs))
    # The headline delta is the MEDIAN OF PER-PAIR DELTAS: the arms
    # alternate precisely so that each pair shares its minute of
    # machine drift — differencing within pairs cancels the drift
    # that dominates cross-arm comparisons on this box, and the
    # median is robust to an outlier pair.  Arm medians stay in the
    # artifact as context.
    pair_deltas = [
        round((b - a) / a * 100, 2)
        for a, b in zip(off_runs, on_runs)
        if a
    ]
    delta_pct = (
        round(float(np.median(pair_deltas)), 2) if pair_deltas else None
    )

    def _med(draws):
        return round(float(np.median(draws)), 3) if draws else None
    recorder = getattr(tracer, "_flight_recorder", None)
    flight = None
    if recorder is not None:
        dump = recorder.dump(limit=5)
        # Aggregate where the retained tail trees spent their time, by
        # span name — the per-stage attribution PERF.md §23 publishes.
        by_name: dict = {}
        for tree in dump["traces"]:
            for s in tree["spans"]:
                agg = by_name.setdefault(
                    s["name"], {"count": 0, "total_ms": 0.0}
                )
                agg["count"] += 1
                agg["total_ms"] = round(
                    agg["total_ms"] + s["duration_ms"], 3
                )
        flight = {
            "considered": dump["considered"],
            "recorded": dump["recorded"],
            "threshold_ms": dump["threshold_ms"],
            "root_p50_ms": dump["root_p50_ms"],
            "root_p99_ms": dump["root_p99_ms"],
            "tail_spans_by_name": by_name,
        }
    return {
        "metric": "rate-limit decisions/sec, thundering herd, tracing "
        f"A/B (same session, median of {pairs} alternating pairs: "
        "off vs in-memory + tail sampling)",
        "value": round(on_v, 1),
        "unit": "decisions/sec",
        "vs_baseline": round(on_v / BASELINE_DECISIONS_PER_SEC, 2),
        "tracing_off_value": round(off_v, 1),
        "tracing_delta_pct": delta_pct,
        "pair_deltas_pct": pair_deltas,
        "off_runs": off_runs,
        "on_runs": on_runs,
        # Latencies get the same median treatment as throughput — a
        # single pair's p50/p99 is a draw of the same ±9% noise the
        # medians exist to defeat; per-draw lists ride along.
        "p50_ms": _med(on_lats["p50_ms"]),
        "p99_ms": _med(on_lats["p99_ms"]),
        "p50_ms_off": _med(off_lats["p50_ms"]),
        "p99_ms_off": _med(off_lats["p99_ms"]),
        "p50_draws": {"off": off_lats["p50_ms"], "on": on_lats["p50_ms"]},
        "p99_draws": {"off": off_lats["p99_ms"], "on": on_lats["p99_ms"]},
        "spans_recorded": len(tracer.spans()),
        "flight": flight,
        "stage_budget_off": off.get("stage_budget"),
        "stage_budget": on.get("stage_budget"),
        "native_events_off": off.get("native_events"),
        "native_events": on.get("native_events"),
        "ledger": on.get("ledger"),
        "platform": platform,
    }


def _run_devfused(np, platform: str) -> dict:
    """Device-path fused/unfused A/B in one session.

    Arms alternate per pair so each pair shares its minute of machine
    drift (the herdtrace treatment — single-pair deltas swing ±9% on
    this box): arm A forces GUBER_FUSED=split (the old multi-dispatch
    gather/scatter chain: compute + scatter programs per round, no
    step pump), arm B runs the default fused single-kernel step.  The
    artifact carries both arm medians, every draw, the median of
    per-pair deltas, and each arm's measured device dispatches/batch —
    the steady-state fused number must be 1.0 (pinned by
    tests/test_fused_parity.py)."""
    from gubernator_tpu.core.engine import DecisionEngine

    pairs = max(1, int(os.environ.get("BENCH_DEVFUSED_PAIRS", "3")))
    n_batches = max(1, min((N_KEYS + BATCH - 1) // BATCH, 64))
    batches = []
    for idx in _key_indices(np, n_batches):
        batches.append(
            dict(
                keys=[b"bench_k%d" % i for i in idx.tolist()],
                algo=_algo_column(np, idx),
                behavior=np.zeros(BATCH, dtype=np.int32),
                hits=np.ones(BATCH, dtype=np.int64),
                limit=np.full(BATCH, 1_000_000, dtype=np.int64),
                duration=np.full(BATCH, 3_600_000, dtype=np.int64),
                burst=np.full(BATCH, 1_000_000, dtype=np.int64),
            )
        )

    def measure(engine) -> dict:
        from collections import deque

        for i in range(WARMUP_BATCHES):
            engine.apply_columnar(**batches[i % len(batches)])
        lat_n = min(LATENCY_BATCHES, 50)
        lat = np.empty(lat_n, dtype=np.float64)
        for i in range(lat_n):
            t0 = time.perf_counter()
            engine.apply_columnar(**batches[i % len(batches)])
            lat[i] = time.perf_counter() - t0
        d0, b0 = engine.dispatches_total, engine.batches_total
        pending = deque()
        n_done = 0
        start = time.perf_counter()
        i = 0
        while True:
            pending.append(
                engine.apply_columnar(
                    **batches[i % len(batches)], want_async=True
                )
            )
            i += 1
            if len(pending) > PIPELINE_DEPTH:
                pending.popleft().get()
                n_done += BATCH
            if time.perf_counter() - start >= MEASURE_SECONDS:
                break
        while pending:
            pending.popleft().get()
            n_done += BATCH
        elapsed = time.perf_counter() - start
        d_batches = engine.batches_total - b0
        return {
            "rate": n_done / elapsed,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "dispatches_per_batch": (
                round((engine.dispatches_total - d0) / d_batches, 4)
                if d_batches
                else 0.0
            ),
            "fused_mode": engine.fused_mode,
        }

    def build(mode: str) -> "DecisionEngine":
        saved = os.environ.get("GUBER_FUSED")
        os.environ["GUBER_FUSED"] = mode
        try:
            return DecisionEngine(
                capacity=CAPACITY, max_kernel_width=max(8192, BATCH)
            )
        finally:
            if saved is None:
                os.environ.pop("GUBER_FUSED", None)
            else:
                os.environ["GUBER_FUSED"] = saved

    unfused_runs, fused_runs = [], []
    unfused_last = fused_last = None
    for _ in range(pairs):
        unfused_last = measure(build("split"))
        unfused_runs.append(unfused_last["rate"])
        fused_last = measure(build(os.environ.get("GUBER_FUSED", "auto")))
        fused_runs.append(fused_last["rate"])
    pair_deltas = [
        round((b - a) / a * 100, 2)
        for a, b in zip(unfused_runs, fused_runs)
        if a
    ]
    delta_pct = (
        round(float(np.median(pair_deltas)), 2) if pair_deltas else None
    )
    fused_v = float(np.median(fused_runs))
    unfused_v = float(np.median(unfused_runs))
    return {
        "metric": "rate-limit decisions/sec, device decision plane "
        f"fused/unfused A/B (batch={BATCH}, median of {pairs} "
        "alternating pairs: GUBER_FUSED=split vs fused)",
        "value": round(fused_v, 1),
        "unit": "decisions/sec",
        "vs_baseline": round(fused_v / BASELINE_DECISIONS_PER_SEC, 2),
        "unfused_value": round(unfused_v, 1),
        "fused_delta_pct": delta_pct,
        "pair_deltas_pct": pair_deltas,
        "unfused_runs": [round(v, 1) for v in unfused_runs],
        "fused_runs": [round(v, 1) for v in fused_runs],
        "p50_ms": round(fused_last["p50_ms"], 3),
        "p99_ms": round(fused_last["p99_ms"], 3),
        "p50_ms_unfused": round(unfused_last["p50_ms"], 3),
        "p99_ms_unfused": round(unfused_last["p99_ms"], 3),
        "dispatches_per_batch": fused_last["dispatches_per_batch"],
        "dispatches_per_batch_unfused": unfused_last[
            "dispatches_per_batch"
        ],
        "fused_mode": fused_last["fused_mode"],
        "unfused_mode": unfused_last["fused_mode"],
        "platform": platform,
    }


def _ledger_enabled() -> bool:
    """GUBER_LEDGER must govern the in-process daemons too (the
    process-per-node modes read it via setup_daemon_config; these
    build DaemonConfig directly)."""
    return os.environ.get("GUBER_LEDGER", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )

def _native_ledger_enabled() -> bool:
    """GUBER_NATIVE_LEDGER must govern the in-process daemons too —
    these build DaemonConfig directly, and the config field is
    authoritative over the front (the A/B pairs depend on it)."""
    return os.environ.get(
        "GUBER_NATIVE_LEDGER", "1"
    ).strip().lower() not in ("0", "false", "no", "off")


def _ledger_stats_inproc(daemon) -> Optional[dict]:
    """Ledger counters + the dispatches-per-decision gauge from an
    in-process daemon (wire/herd modes) — every artifact claiming a
    ledger hit rate must carry the counters that back it."""
    inst = daemon.instance
    led = getattr(inst, "ledger", None)
    if led is None:
        return None
    out = led.stats()
    eng = inst.engine
    # Decisions = engine rows + ledger answers (Python AND native) —
    # the native plane's answers never touch the engine counters.
    decisions = (
        eng.requests_total + out["answered"]
        + out.get("native_answered", 0)
    )
    out["dispatches_per_decision"] = (
        round(eng.rounds_total / decisions, 4) if decisions else 0.0
    )
    return out


_LEDGER_SCRAPE_KEYS = (
    "gubernator_ledger_answered",
    "gubernator_ledger_native_answered",
    "gubernator_ledger_fallthrough",
    "gubernator_ledger_settles",
    "gubernator_check_counter",
    "gubernator_engine_rounds",
)


def _scrape_ledger_raw(http_addrs: list) -> dict:
    """Cumulative ledger counters summed across the nodes' /metrics."""
    import re
    import urllib.request

    out: dict = {}
    pat = re.compile(
        r"^(gubernator_ledger_answered|gubernator_ledger_native_answered|"
        r"gubernator_ledger_fallthrough|"
        r"gubernator_ledger_settles|gubernator_check_counter|"
        r"gubernator_engine_rounds)(?:_total)?\s+([0-9.e+-]+)",
        re.M,
    )
    for addr in http_addrs:
        try:
            with urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=5
            ) as r:
                text = r.read().decode()
        except OSError:
            continue
        for name, val in pat.findall(text):
            out[name] = out.get(name, 0.0) + float(val)
    return out


def _ledger_diff(before: dict, after: dict) -> dict:
    """Measured-window ledger summary from cumulative scrapes."""
    d = {
        k: int(after.get(k, 0.0) - before.get(k, 0.0))
        for k in set(before) | set(after)
    }
    answered = d.get("gubernator_ledger_answered", 0)
    native = d.get("gubernator_ledger_native_answered", 0)
    rounds = d.get("gubernator_engine_rounds", 0)
    engine_rows = d.get("gubernator_check_counter", 0)
    decisions = engine_rows + answered + native
    return {
        "answered": answered,
        "native_answered": native,
        "fallthrough": d.get("gubernator_ledger_fallthrough", 0),
        "settles": d.get("gubernator_ledger_settles", 0),
        "dispatches_per_decision": (
            round(rounds / decisions, 4) if decisions else 0.0
        ),
    }


def _scrape_stage_raw(http_addrs: list) -> dict:
    """Cumulative per-stage histograms (gubernator_stage_seconds
    bucket/count/sum) summed across the nodes' /metrics.  Summing
    per-node cumulative bucket counts IS the cross-node histogram
    merge (obs/fleet.py's semantics), so a diff of two scrapes yields
    REAL merged quantiles for the measured window — this used to fold
    gubernator_stage_duration count/sum into per-node means, the
    means-of-means lie the fleet rollup exists to retire."""
    import re
    import urllib.request

    stages: dict = {}
    pat = re.compile(
        r"gubernator_stage_seconds_(bucket|count|sum)\{([^}]*)\}\s+"
        r"([0-9.eE+-]+)"
    )
    lab = re.compile(r'(\w+)="([^"]*)"')
    for addr in http_addrs:
        try:
            with urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=5
            ) as r:
                text = r.read().decode()
        except OSError:
            continue
        for kind, labels, val in pat.findall(text):
            d = dict(lab.findall(labels))
            ent = stages.setdefault(
                d.get("stage", ""),
                {"count": 0.0, "sum": 0.0, "buckets": {}},
            )
            if kind == "bucket":
                le = d.get("le", "")
                ent["buckets"][le] = (
                    ent["buckets"].get(le, 0.0) + float(val)
                )
            else:
                ent[kind] += float(val)
    return stages


def _stage_budget_diff(before: dict, after: dict) -> dict:
    """Per-stage budget over the MEASURED window only (the histograms
    are cumulative from daemon start, and the warmup round's
    cold-compile windows must not bias the published budget): the
    bucket diffs rebuild a DurationStat per stage, so the published
    p50/p99 are real cross-node merged quantiles, with the window
    mean alongside."""
    from gubernator_tpu.utils.metrics import DurationStat

    # The exporter formats each bucket's upper bound with the same
    # "%.9g" as this table, so le strings map back to bucket indexes
    # exactly ("+Inf" duplicates the top bucket's cumulative count
    # and is dropped here).
    le_to_idx = {
        f"{DurationStat.bucket_bounds(i)[1]:.9g}": i
        for i in range(DurationStat.N_BUCKETS)
    }
    out = {}
    for stage, a in after.items():
        b = before.get(stage) or {"count": 0.0, "sum": 0.0, "buckets": {}}
        dn = a["count"] - b.get("count", 0.0)
        ds = a["sum"] - b.get("sum", 0.0)
        stat = DurationStat()
        prev = 0.0
        for le in sorted(
            (k for k in a["buckets"] if k in le_to_idx),
            key=lambda k: le_to_idx[k],
        ):
            cum = a["buckets"][le] - (b.get("buckets") or {}).get(le, 0.0)
            c = cum - prev
            prev = cum
            if c > 0:
                stat.buckets[le_to_idx[le]] += int(round(c))
        stat.count = sum(stat.buckets)
        row = {
            "count": int(dn),
            "mean_ms": round(ds / dn * 1e3, 3) if dn else 0.0,
        }
        if stat.count:
            row["p50_ms"] = round(stat.quantile(0.5) * 1e3, 3)
            row["p99_ms"] = round(stat.quantile(0.99) * 1e3, 3)
        out[stage] = row
    return out


def _run_global_procs(np, platform: str, n_nodes: int, wire_batch: int) -> dict:
    """GLOBAL over a process-per-node cluster (GUBER_STATIC_PEERS).

    The in-process harness serializes every node's Python behind ONE
    GIL — a contention mode the Go reference does not have anywhere
    (its in-process benchmark cluster still parallelizes across
    cores).  One daemon process per node is the faithful analog of a
    real deployment, and the artifact records the topology.  Client
    load also runs as subprocesses (the wire config's precedent) so
    the measurement reflects server capacity."""
    import signal
    import socket

    from gubernator_tpu.types import Behavior

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    grpc_addrs = [f"127.0.0.1:{free_port()}" for _ in range(n_nodes)]
    http_addrs = [f"127.0.0.1:{free_port()}" for _ in range(n_nodes)]
    peers = ",".join(grpc_addrs)
    procs = []
    root = os.path.dirname(os.path.abspath(__file__))
    for i in range(n_nodes):
        env = dict(os.environ)
        env.update(
            {
                "GUBER_PLATFORM": "cpu",
                "JAX_PLATFORMS": "cpu",
                "GUBER_GRPC_ADDRESS": grpc_addrs[i],
                "GUBER_HTTP_ADDRESS": http_addrs[i],
                "GUBER_PEER_DISCOVERY_TYPE": "none",
                "GUBER_STATIC_PEERS": peers,
                "GUBER_CACHE_SIZE": str(CAPACITY),
                "GUBER_SWEEP_INTERVAL": "0",
                # The harness's cluster-test knobs, matched.
                "GUBER_GLOBAL_SYNC_WAIT": os.environ.get(
                    "BENCH_GLOBAL_SYNC_WAIT", "50ms"
                ),
                "GUBER_BATCH_WAIT": "5ms",
                "GUBER_GLOBAL_TIMEOUT": "1s",
                "GUBER_BATCH_TIMEOUT": "1s",
                # Serving-daemon posture for a shared-core CPU host:
                # inline XLA dispatch (async dispatch only adds
                # cross-thread handoffs when there is no accelerator
                # RPC to overlap — each handoff costs scheduler
                # latency under 4-nodes-on-2-cores oversubscription),
                # and a worker pool sized near the core count so
                # excess RPCs queue FIFO in the executor instead of
                # convoying on the engine lock.
                "JAX_CPU_ENABLE_ASYNC_DISPATCH": os.environ.get(
                    "BENCH_CPU_ASYNC_DISPATCH", "false"
                ),
                "GUBER_GRPC_WORKERS": os.environ.get(
                    "BENCH_GRPC_WORKERS", "6"
                ),
            }
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "gubernator_tpu.cmd.daemon"],
                env=env,
                cwd=root,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
                stdin=subprocess.DEVNULL,
                start_new_session=True,
            )
        )
    try:
        import grpc

        from gubernator_tpu.net.grpc_service import V1Stub, dial
        from gubernator_tpu.net.pb import gubernator_pb2 as pb

        deadline = time.monotonic() + 240.0
        for addr in grpc_addrs:
            while True:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"node {addr} never became ready")
                ch = dial(addr)
                try:
                    V1Stub(ch).HealthCheck(pb.HealthCheckReq(), timeout=1.0)
                    break
                except grpc.RpcError:
                    time.sleep(0.25)
                finally:
                    ch.close()
        warm_seconds = float(os.environ.get("BENCH_WARM_SECONDS", 0.0))
        n_procs = int(os.environ.get("BENCH_WIRE_PROCS", "8"))
        behavior = int(Behavior.GLOBAL)
        if warm_seconds:
            # A throwaway client round pays the cold XLA compiles and
            # first-window flush storms before the measured window.
            _drive_grpc_procs(
                np, grpc_addrs, n_procs, wire_batch, behavior=behavior,
                seconds=warm_seconds,
            )
        stage_before = _scrape_stage_raw(http_addrs)
        ledger_before = _scrape_ledger_raw(http_addrs)
        rate, p50_ms, p99_ms = _drive_grpc_procs(
            np, grpc_addrs, n_procs, wire_batch, behavior=behavior
        )
        budget = _stage_budget_diff(
            stage_before, _scrape_stage_raw(http_addrs)
        )
        ledger = _ledger_diff(ledger_before, _scrape_ledger_raw(http_addrs))
        return {
            "metric": f"rate-limit decisions/sec, GLOBAL, {n_nodes}-node "
            f"cluster, one daemon process per node (batch={wire_batch}, "
            f"{n_procs} client procs, {N_KEYS} hot keys)",
            "value": round(rate, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": p50_ms,
            "p99_ms": p99_ms,
            # The node processes are pinned to the host backend above
            # (GUBER_PLATFORM=cpu): that is where the decisions ran,
            # whatever this parent process found.
            "platform": "cpu",
            "parent_platform": platform,
            "topology": "process-per-node",
            "stage_budget_ms": budget,
            # Rows carry merged p50/p99 (histogram diff across the
            # nodes' gubernator_stage_seconds), not per-node means —
            # bench_trend marks artifacts that predate this.
            "stage_budget_source": "histogram-merge",
            "ledger": ledger,
        }
    finally:
        for p in procs:
            try:
                os.killpg(p.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass


def _drive_herd(np, address: str, payloads, n_threads: int, seconds: float,
                during=None) -> dict:
    """Shared client-herd scaffold for the cluster A/B benches
    (deadpeer, reshard): `n_threads` workers fire single-item raw
    GetRateLimits RPCs at `address` for `seconds`, measuring
    per-request latency.  `during` (optional callable) runs on a
    helper thread once the herd is at full rate and is JOINED before
    the herd stops — membership/failure events must never be cut
    short mid-flight.  Returns {value, p50_ms, p99_ms, requests,
    errors}."""
    import grpc

    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    stop = threading.Event()
    barrier = threading.Barrier(n_threads + 1)
    counts = [0] * n_threads
    errors = [0] * n_threads
    lats: list = [None] * n_threads

    def worker(tid: int) -> None:
        mylat = []
        ch = grpc.insecure_channel(address)
        call = ch.unary_unary(
            f"/{V1_SERVICE}/GetRateLimits",
            request_serializer=lambda raw: raw,
            response_deserializer=lambda raw: raw,
        )
        try:
            call(payloads[0])
        finally:
            barrier.wait()
        i = tid
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                raw = call(payloads[i % len(payloads)])
                resp = pb.GetRateLimitsResp()
                resp.ParseFromString(raw)
                if any(r.error for r in resp.responses):
                    errors[tid] += 1
            except grpc.RpcError:
                errors[tid] += 1
            mylat.append(time.perf_counter() - t0)
            counts[tid] += 1
            i += n_threads
        lats[tid] = mylat
        ch.close()

    threads = [
        threading.Thread(target=worker, args=(t,), daemon=True)
        for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    barrier.wait()
    helper = None
    if during is not None:
        helper = threading.Thread(target=during, daemon=True)
        helper.start()
    start = time.perf_counter()
    time.sleep(seconds)
    if helper is not None:
        helper.join()
    stop.set()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    all_lat = np.asarray([x for ml in lats if ml for x in ml])
    return {
        "value": round(sum(counts) / elapsed, 1),
        "p50_ms": round(float(np.percentile(all_lat, 50)) * 1e3, 3)
        if all_lat.size else None,
        "p99_ms": round(float(np.percentile(all_lat, 99)) * 1e3, 3)
        if all_lat.size else None,
        "requests": int(sum(counts)),
        "errors": int(sum(errors)),
    }


def _run_flashcrowd(np, platform: str) -> dict:
    """Flash-crowd A/B (ISSUE 13 acceptance): single-item RPCs sprayed
    across all nodes under a time-varying zipf — ~80% of traffic on a
    small hot set that ROTATES every MEASURE_SECONDS/BENCH_FLASH_PHASES
    — once with hot-key replication live (promotion keeps every node
    answering hot keys from pre-debited credit leases) and once with
    BENCH_FLASH_REPL=0 (consistent-hash-only: every non-owner request
    pays the forward hop to the hot key's owner).

    The artifact splits p99 into steady vs rotation windows (the first
    second after each hot-set switch): the acceptance bar is rotation
    p99 within 2x steady p99 with replication on.  A finite-limit
    CANARY key rides every phase's hot set; its admitted count checks
    the N_replicas x lease bound live (pre-debit => admitted <= limit
    on a healthy owner)."""
    from gubernator_tpu.cluster.harness import ClusterHarness
    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    import grpc

    n_nodes = int(os.environ.get("BENCH_NODES", 3))
    n_threads = int(os.environ.get("BENCH_FLASH_THREADS", 8))
    phases = max(2, int(os.environ.get("BENCH_FLASH_PHASES", 4)))
    # ONE celebrity key per phase is the scenario (DualMap's
    # affinity-vs-load-balance hard case): with replication off, ~75%
    # of all traffic funnels through that key's single owner.
    hot_n = int(os.environ.get("BENCH_FLASH_HOT", 1))
    repl_on = os.environ.get("BENCH_FLASH_REPL", "1") != "0"
    # Sized to EXHAUST during the run (canary traffic is ~10% of a few
    # hundred req/s): admitted-vs-limit is only evidence if the bucket
    # actually runs dry.
    canary_limit = int(os.environ.get("BENCH_FLASH_CANARY_LIMIT", 150))
    lease = int(os.environ.get("BENCH_FLASH_LEASE", 200))
    phase_dur = MEASURE_SECONDS / phases
    h = ClusterHarness().start(n_nodes, cache_size=CAPACITY)
    try:
        for d in h.daemons:
            r = d.replication
            assert r is not None
            if repl_on:
                # Sized to this harness: the in-process closed-loop
                # cluster runs a few hundred req/s total, so a hot key
                # (and the ~10%-share canary) sees ~10-40/s —
                # promotion must engage well below that.
                r.promote_rate = float(
                    os.environ.get("BENCH_FLASH_PROMOTE_RATE", 8)
                )
                r.interval = 0.1
                r.cooldown = max(0.5, phase_dur * 0.5)
                r.lease = lease
                r.lease_ttl = 0.5
                d.instance.hotkeys.window_s = 0.5
            else:
                r.enabled = False
        addrs = [d.grpc_address for d in h.daemons]

        def payload(key, limit):
            return pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="flash", unique_key=key, hits=1,
                        limit=limit, duration=3_600_000,
                    )
                ]
            ).SerializeToString()

        # Keys vary a LEADING byte (FNV-1 trailing-byte collapse; see
        # hash_ring.py) so hot keys spread across owners.
        hot_payloads = [
            [payload(f"{p}{j}_fc{p}", 10**9) for j in range(hot_n)]
            for p in range(phases)
        ]
        cold_payloads = [payload(f"{i}_fcold", 10**9) for i in range(64)]
        canary_payload = payload("9cy_fcanary", canary_limit)

        stop = threading.Event()
        barrier = threading.Barrier(n_threads + 1)
        counts = [0] * n_threads
        errors = [0] * n_threads
        canary_admitted = [0] * n_threads
        lats: list = [None] * n_threads
        start_box = [0.0]
        rng_seed = 1234

        def worker(tid: int) -> None:
            rng = np.random.default_rng(rng_seed + tid)
            mylat = []
            ch = grpc.insecure_channel(addrs[tid % len(addrs)])
            call = ch.unary_unary(
                f"/{V1_SERVICE}/GetRateLimits",
                request_serializer=lambda raw: raw,
                response_deserializer=lambda raw: raw,
            )
            try:
                call(cold_payloads[0])
            finally:
                barrier.wait()
            while not stop.is_set():
                now = time.perf_counter()
                rel = now - start_box[0]
                p = min(int(rel / phase_dur), phases - 1)
                u = rng.random()
                if u < 0.1:
                    body, is_canary = canary_payload, True
                elif u < 0.85:
                    body = hot_payloads[p][int(rng.integers(hot_n))]
                    is_canary = False
                else:
                    body = cold_payloads[int(rng.integers(64))]
                    is_canary = False
                t0 = time.perf_counter()
                try:
                    raw = call(body)
                    resp = pb.GetRateLimitsResp()
                    resp.ParseFromString(raw)
                    for rr in resp.responses:
                        if rr.error:
                            errors[tid] += 1
                        elif is_canary and rr.status == 0:  # UNDER
                            canary_admitted[tid] += 1
                except grpc.RpcError:
                    errors[tid] += 1
                mylat.append((rel, time.perf_counter() - t0))
                counts[tid] += 1
            lats[tid] = mylat
            ch.close()

        threads = [
            threading.Thread(target=worker, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        # Stamp BEFORE releasing the barrier: workers read the stamp
        # right after their own wait returns, and a zero stamp would
        # give the first samples garbage phase offsets that pollute
        # the steady-p99 population.
        start_box[0] = time.perf_counter()
        barrier.wait()
        time.sleep(MEASURE_SECONDS)
        stop.set()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - start_box[0]
        all_lat = [x for ml in lats if ml for x in ml]
        rel = np.asarray([t for t, _ in all_lat])
        dur = np.asarray([d for _, d in all_lat])
        # Rotation windows: the first second after each hot-set switch
        # (phase 0's cold start is excluded from both populations).
        rot_w = min(1.0, phase_dur / 2)
        rot_mask = np.zeros(len(rel), dtype=bool)
        for p in range(1, phases):
            t0 = p * phase_dur
            rot_mask |= (rel >= t0) & (rel < t0 + rot_w)
        steady_mask = ~rot_mask & (rel >= min(1.0, phase_dur / 2))
        p99 = lambda a: (  # noqa: E731
            round(float(np.percentile(a, 99)) * 1e3, 3) if len(a) else None
        )
        repl_stats = {
            k: sum(d.replication.stats()[k] for d in h.daemons)
            for k in h.daemons[0].replication.stats()
        }
        admitted = int(sum(canary_admitted))
        n_replicas = n_nodes - 1
        steady_p99 = p99(dur[steady_mask])
        rot_p99 = p99(dur[rot_mask])
        return {
            "metric": "rate-limit decisions/sec, flash crowd (hot set "
            f"rotates every {phase_dur:.1f}s across {phases} phases, "
            f"{n_threads} client threads spraying {n_nodes} nodes, "
            f"replication {'on' if repl_on else 'off'})",
            "value": round(sum(counts) / elapsed, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(
                sum(counts) / elapsed / BASELINE_DECISIONS_PER_SEC, 2
            ),
            "p50_ms": round(float(np.percentile(dur, 50)) * 1e3, 3),
            "p99_ms": p99(dur),
            "steady_p99_ms": steady_p99,
            "rotation_p99_ms": rot_p99,
            "rotation_over_steady": (
                round(rot_p99 / steady_p99, 2)
                if steady_p99 and rot_p99 else None
            ),
            "phases": phases,
            "requests": int(sum(counts)),
            "errors": int(sum(errors)),
            "replication_on": repl_on,
            "replication": repl_stats,
            "canary": {
                "limit": canary_limit,
                "admitted": admitted,
                "over_admission": max(0, admitted - canary_limit),
                "bound": n_replicas * lease,
                "lease": lease,
                "replicas": n_replicas,
            },
            "platform": platform,
        }
    finally:
        h.stop()


def _run_crossregion(np, platform: str) -> dict:
    """Multi-region federation A/B (ISSUE 14 acceptance): a 2×2
    region×peer in-process cluster (two datacenters, two daemons
    each) with deterministic injected inter-region link latency.

    Three phases in ONE session:
      1. healthy control — client herds drive MULTI_REGION single-item
         RPCs into BOTH regions; cross-region deltas converge live.
      2. partition — every inter-region link cut (asymmetric rules,
         both directions).  The acceptance bar: ZERO errors (answers
         are region-local; convergence defers into the requeue
         backlog), answers flagged degraded_region once the region
         circuits open, and a finite-limit canary driven from both
         regions admits ≤ N_regions × limit (the §12 drift bound,
         measured live).
      3. heal — the requeued deltas deliver; the artifact records the
         convergence time and asserts drops == 0 inside the age cap.

    The artifact embeds the per-stage cross-region hop budget
    (multiregion window wait + region-push RPC quantiles from the
    stitched-trace stage timers) so PERF.md §28 can attribute the DCN
    cost."""
    import grpc

    from dataclasses import replace as dc_replace

    from gubernator_tpu.cluster.harness import (
        ClusterHarness,
        cluster_behaviors,
    )
    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior

    regions = ["", "dc-west"]
    n_per_region = int(os.environ.get("BENCH_XR_PEERS", 2))
    n_threads = int(os.environ.get("BENCH_XR_THREADS", 8))
    link_ms = float(os.environ.get("BENCH_XR_LINK_MS", 10.0))
    # Sized to EXHAUST in both regions during the partition phase
    # (~10% canary share of a few-hundred-req/s closed-loop herd):
    # admitted-vs-limit is only drift evidence if the bucket actually
    # runs dry on each side of the cut.
    canary_limit = int(os.environ.get("BENCH_XR_CANARY_LIMIT", 40))
    datacenters = [r for r in regions for _ in range(n_per_region)]
    # The requeue age cap must outlive the partition phase, or the
    # "drops == 0" acceptance would be measuring the cap, not the
    # convergence.
    behaviors = dc_replace(
        cluster_behaviors(),
        multi_region_requeue_age=max(60.0, 6.0 * MEASURE_SECONDS),
    )
    h = ClusterHarness().start(
        len(datacenters), datacenters=datacenters,
        behaviors=behaviors, cache_size=CAPACITY,
    )
    try:
        h.install_faults(seed=5)
        if link_ms > 0:
            # Deterministic DCN RTT on every inter-region link — the
            # cross-region hop pays it, decisions never do.
            h.region_link_latency(regions[0], regions[1], link_ms / 1e3)
        entry = {
            r: next(
                d
                for d, dc in zip(h.daemons, h._datacenters)
                if dc == r
            )
            for r in regions
        }
        mrb = int(Behavior.MULTI_REGION)

        def payload(key, limit, hits=1):
            return pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="xr", unique_key=key, hits=hits,
                        limit=limit, duration=3_600_000, behavior=mrb,
                    )
                ]
            ).SerializeToString()

        # Keys vary a LEADING byte (FNV-1 trailing-byte collapse; see
        # hash_ring.py) so every owner in every region gets a share.
        payloads = [payload(f"{i}_xr", 10**9) for i in range(256)]
        canary_payload = payload("9xy_xrcanary", canary_limit)

        def drive(seconds: float, canary: bool):
            """Closed-loop herd split across BOTH regions' entry
            nodes; optional ~10% canary share.  Returns {value, p50,
            p99, requests, errors, canary_admitted}."""
            addrs = [entry[regions[t % len(regions)]].grpc_address
                     for t in range(n_threads)]
            stop = threading.Event()
            barrier = threading.Barrier(n_threads + 1)
            counts = [0] * n_threads
            errors = [0] * n_threads
            admitted = [0] * n_threads
            lats: list = [None] * n_threads

            def worker(tid: int) -> None:
                rng = np.random.default_rng(100 + tid)
                mylat = []
                ch = grpc.insecure_channel(addrs[tid])
                call = ch.unary_unary(
                    f"/{V1_SERVICE}/GetRateLimits",
                    request_serializer=lambda raw: raw,
                    response_deserializer=lambda raw: raw,
                )
                try:
                    call(payloads[tid % len(payloads)])
                finally:
                    barrier.wait()
                i = tid
                while not stop.is_set():
                    is_canary = canary and rng.random() < 0.1
                    body = (
                        canary_payload
                        if is_canary
                        else payloads[i % len(payloads)]
                    )
                    t0 = time.perf_counter()
                    try:
                        raw = call(body)
                        resp = pb.GetRateLimitsResp()
                        resp.ParseFromString(raw)
                        for rr in resp.responses:
                            if rr.error:
                                errors[tid] += 1
                            elif is_canary and rr.status == 0:  # UNDER
                                admitted[tid] += 1
                    except grpc.RpcError:
                        errors[tid] += 1
                    mylat.append(time.perf_counter() - t0)
                    counts[tid] += 1
                    i += n_threads
                lats[tid] = mylat
                ch.close()

            threads = [
                threading.Thread(target=worker, args=(t,), daemon=True)
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            start = time.perf_counter()
            time.sleep(seconds)
            stop.set()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - start
            all_lat = np.asarray(
                [x for ml in lats if ml for x in ml]
            )
            pct = lambda q: (  # noqa: E731
                round(float(np.percentile(all_lat, q)) * 1e3, 3)
                if all_lat.size else None
            )
            return {
                "value": round(sum(counts) / elapsed, 1),
                "p50_ms": pct(50),
                "p99_ms": pct(99),
                "requests": int(sum(counts)),
                "errors": int(sum(errors)),
                "canary_admitted": int(sum(admitted)),
            }

        def mr_sum(field: str) -> int:
            return sum(
                d.multiregion_stats()[field] for d in h.daemons
            )

        def degraded_sum() -> int:
            return sum(
                d.instance.counters["degraded_region_answers"]
                for d in h.daemons
            )

        def settle(timeout: float = 30.0) -> float:
            """Force-deliver the retry backlog on every node; returns
            seconds until pending_retry hits 0 everywhere."""
            t0 = time.perf_counter()
            deadline = t0 + timeout
            while time.perf_counter() < deadline:
                for d in h.daemons:
                    d.instance.multi_region_mgr.retry_now()
                if all(
                    d.instance.multi_region_mgr.pending_retry() == 0
                    for d in h.daemons
                ):
                    break
                time.sleep(0.05)
            return round(time.perf_counter() - t0, 3)

        # -- phase 1: healthy control ---------------------------------
        healthy = drive(MEASURE_SECONDS, canary=False)
        settle(10.0)
        healthy["region_sends"] = mr_sum("region_sends")

        # -- phase 2: full inter-region partition ---------------------
        h.partition_regions(regions[0], regions[1])
        degraded_before = degraded_sum()
        sends_before_heal = mr_sum("region_sends")
        part = drive(MEASURE_SECONDS, canary=True)
        part["degraded_region_answers"] = degraded_sum() - degraded_before
        part["hits_requeued"] = mr_sum("hits_requeued")

        # -- phase 3: heal → converge ---------------------------------
        h.heal()
        heal_s = settle(30.0)
        admitted = part["canary_admitted"]
        dropped = mr_sum("hits_dropped")
        states = h.multiregion_states()
        hop = entry[regions[0]].instance.multi_region_mgr
        return {
            "metric": "rate-limit decisions/sec, MULTI_REGION traffic "
            f"across a {len(regions)}x{n_per_region} region x peer "
            f"cluster with the inter-region links CUT ({n_threads} "
            f"client threads split across both regions, {link_ms:g}ms "
            "injected inter-region link latency; value = partitioned "
            "phase)",
            "value": part["value"],
            "unit": "decisions/sec",
            "vs_baseline": round(
                part["value"] / BASELINE_DECISIONS_PER_SEC, 2
            ),
            "p50_ms": part["p50_ms"],
            "p99_ms": part["p99_ms"],
            "requests": part["requests"],
            "errors": part["errors"],
            "healthy": healthy,
            "partitioned": part,
            "canary": {
                "limit": canary_limit,
                "admitted": admitted,
                "over_admission": max(0, admitted - canary_limit),
                "bound": len(regions) * canary_limit,
                "within_bound": admitted <= len(regions) * canary_limit,
                "regions": len(regions),
            },
            "heal_convergence_s": heal_s,
            "hits_dropped": dropped,
            "region_sends_post_heal": mr_sum("region_sends")
            - sends_before_heal,
            "link_latency_ms": link_ms,
            "multiregion": {
                "window_wait": hop.window_wait.snapshot_ms(),
                "region_rpc": hop.region_rpc.snapshot_ms(),
                "states": states,
            },
            "platform": platform,
        }
    finally:
        h.stop()


def _run_fleetobs(np, platform: str) -> dict:
    """Fleet observability A/B (ISSUE 15 acceptance): the rollup +
    SLO watchdog's serving cost, pinned < 2% like herdtrace.

    A 2×2 region×peer in-process cluster serves a closed-loop herd of
    single-item RPCs split across all four nodes.  Every node runs
    the obs plane at a bench-visible tick (GUBER_SLO_INTERVAL, default
    0.5s here vs 5s in production) and node 0 is the designated
    rollup node (fleet scope): each of its ticks is a real 4-node
    ObsSnapshot fan-out + histogram merge + SLI evaluation.  Arms
    alternate per pair with the herdtrace median-of-pair-deltas
    treatment; the OFF arm pauses every watchdog (no ticks, no
    fan-outs — the GUBER_OBS=0 steady state; what remains is the
    serve paths' one-attribute admission-watch peek, which both arms
    pay).  A finite-limit canary key (~5% of traffic, watched on
    every node) makes the admission-bound gauge live: the artifact
    carries its cluster-summed admitted count, the derived
    N_regions × limit bound, and the headroom — which must never be
    negative on this healthy cluster.  The canary is MULTI_REGION —
    the crossregion drift canary's shape — because that is the route
    the admission watch covers by design (the dataclass serve path;
    the raw-wire columnar route would under-count, the documented
    safe direction — OBSERVABILITY.md §10)."""
    import grpc

    from gubernator_tpu.cluster.harness import ClusterHarness
    from gubernator_tpu.net.grpc_service import V1_SERVICE
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior

    pairs = max(1, int(os.environ.get("BENCH_FLEETOBS_PAIRS", "3")))
    n_threads = int(os.environ.get("BENCH_FLEETOBS_THREADS", 8))
    seconds = float(
        os.environ.get("BENCH_FLEETOBS_SECONDS", min(MEASURE_SECONDS, 4.0))
    )
    canary_limit = int(os.environ.get("BENCH_FLEETOBS_CANARY_LIMIT", 50))
    regions = ["", "dc-west"]
    datacenters = [r for r in regions for _ in range(2)]
    # The daemons read the obs knobs at start; restore after.
    obs_env = {
        "GUBER_OBS": "1",
        "GUBER_SLO_INTERVAL": os.environ.get(
            "BENCH_FLEETOBS_INTERVAL", "0.5s"
        ),
        "GUBER_SLO_FAST_WINDOWS": "1,3",
        "GUBER_SLO_SLOW_WINDOWS": "5,10",
    }
    saved = {k: os.environ.get(k) for k in obs_env}
    os.environ.update(obs_env)
    try:
        h = ClusterHarness().start(
            len(datacenters), datacenters=datacenters,
            cache_size=CAPACITY,
        )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    try:
        lead = h.daemons[0]
        lead.slo.fleet_scope = True  # the designated rollup node
        canary_key = "fo_9canary"
        for d in h.daemons:
            d.instance.admission_watch.watch(canary_key, limit=canary_limit)

        def payload(key, limit, behavior=0):
            return pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="fo", unique_key=key, hits=1,
                        limit=limit, duration=3_600_000,
                        behavior=behavior,
                    )
                ]
            ).SerializeToString()

        # Keys vary a LEADING byte (FNV-1 trailing-byte collapse; see
        # hash_ring.py) so every owner in every region gets a share.
        payloads = [payload(f"{i}_fo", 10**9) for i in range(256)]
        canary_payload = payload(
            "9canary", canary_limit, behavior=int(Behavior.MULTI_REGION)
        )
        addrs = [
            h.daemons[t % len(h.daemons)].grpc_address
            for t in range(n_threads)
        ]

        def drive(sec: float) -> dict:
            stop = threading.Event()
            barrier = threading.Barrier(n_threads + 1)
            counts = [0] * n_threads
            errors = [0] * n_threads
            lats: list = [None] * n_threads

            def worker(tid: int) -> None:
                rng = np.random.default_rng(300 + tid)
                mylat = []
                ch = grpc.insecure_channel(addrs[tid])
                call = ch.unary_unary(
                    f"/{V1_SERVICE}/GetRateLimits",
                    request_serializer=lambda raw: raw,
                    response_deserializer=lambda raw: raw,
                )
                try:
                    call(payloads[tid % len(payloads)])
                finally:
                    barrier.wait()
                i = tid
                while not stop.is_set():
                    body = (
                        canary_payload
                        if rng.random() < 0.05
                        else payloads[i % len(payloads)]
                    )
                    t0 = time.perf_counter()
                    try:
                        call(body)
                    except grpc.RpcError:
                        errors[tid] += 1
                    mylat.append(time.perf_counter() - t0)
                    counts[tid] += 1
                    i += n_threads
                lats[tid] = mylat
                ch.close()

            threads = [
                threading.Thread(target=worker, args=(t,), daemon=True)
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            barrier.wait()
            start = time.perf_counter()
            time.sleep(sec)
            stop.set()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - start
            all_lat = np.asarray([x for ml in lats if ml for x in ml])
            pct = lambda q: (  # noqa: E731
                round(float(np.percentile(all_lat, q)) * 1e3, 3)
                if all_lat.size else None
            )
            return {
                "value": round(sum(counts) / elapsed, 1),
                "p50_ms": pct(50),
                "p99_ms": pct(99),
                "errors": int(sum(errors)),
            }

        off_runs, on_runs = [], []
        off_lats = {"p50_ms": [], "p99_ms": []}
        on_lats = {"p50_ms": [], "p99_ms": []}
        errors = 0
        for _ in range(pairs):
            for d in h.daemons:
                d.slo.pause()
            off = drive(seconds)
            for d in h.daemons:
                d.slo.resume()
            on = drive(seconds)
            off_runs.append(off["value"])
            on_runs.append(on["value"])
            errors += off["errors"] + on["errors"]
            for k in off_lats:
                if off.get(k) is not None:
                    off_lats[k].append(off[k])
                if on.get(k) is not None:
                    on_lats[k].append(on[k])
        # Let the designated node tick at least once more with the
        # full traffic counted, then read the live surfaces.
        time.sleep(1.0)
        fleet = lead.fleet_stats()
        slo_view = lead.slo.evaluate(fleet, record=False)
        status = lead.slo_status()
        adm = (fleet.get("admitted") or {}).get(canary_key) or {}
        hr = (slo_view.get("headroom") or {}).get(canary_key) or {}
        burns = slo_view.get("slis") or {}
        off_v = float(np.median(off_runs))
        on_v = float(np.median(on_runs))
        pair_deltas = [
            round((b - a) / a * 100, 2)
            for a, b in zip(off_runs, on_runs)
            if a
        ]
        delta_pct = (
            round(float(np.median(pair_deltas)), 2)
            if pair_deltas else None
        )

        def _med(draws):
            return round(float(np.median(draws)), 3) if draws else None
        return {
            "metric": "rate-limit decisions/sec, fleet observability "
            f"A/B across a 2x2 region x peer cluster ({n_threads} "
            f"client threads, median of {pairs} alternating pairs: "
            "watchdog paused vs rollup node fan-out ticking every "
            f"{obs_env['GUBER_SLO_INTERVAL']}; value = obs-on arm)",
            "value": round(on_v, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(on_v / BASELINE_DECISIONS_PER_SEC, 2),
            "fleetobs_off_value": round(off_v, 1),
            "fleetobs_delta_pct": delta_pct,
            "pair_deltas_pct": pair_deltas,
            "off_runs": off_runs,
            "on_runs": on_runs,
            "p50_ms": _med(on_lats["p50_ms"]),
            "p99_ms": _med(on_lats["p99_ms"]),
            "p50_ms_off": _med(off_lats["p50_ms"]),
            "p99_ms_off": _med(off_lats["p99_ms"]),
            "errors": errors,
            "fleet": {
                "nodes": len(fleet.get("nodes") or ()),
                "regions": sorted((fleet.get("regions") or {}).keys()),
                "scrape_ok": (fleet.get("scrape") or {}).get("ok"),
                "scrape_failed": (fleet.get("scrape") or {}).get("failed"),
            },
            "slo": {
                "samples": status.get("samples"),
                "max_burn": (
                    round(max(burns.values()), 4) if burns else None
                ),
                "breaches": len(status.get("breaches") or ()),
            },
            "canary": {
                "limit": canary_limit,
                "admitted": int(adm.get("admitted", 0)),
                "bound": hr.get("bound"),
                "headroom": hr.get("headroom"),
                "within_bound": (hr.get("headroom") or 0) >= 0,
            },
            "platform": platform,
        }
    finally:
        h.stop()


def _run_deadpeer(np, platform: str) -> dict:
    """Dead-peer A/B (ISSUE 5 acceptance): the forward path's latency
    shape when an owner dies, healthy-cluster control first in the
    SAME session.

    4 in-process daemons; a grpc client herd drives single-item
    requests with keys spread across all owners through node 0 (so
    ~3/4 of items exercise the forward path).  Phase 1 measures the
    healthy cluster; phase 2 kills one non-entry daemon and measures
    again.  GUBER_DEGRADED_LOCAL governs the dead phase's semantics:
    on (default) broken circuits answer from node 0's engine (p99
    must NOT collapse into connect-timeout storms — the health
    plane's whole point); off restores reference fail-closed errors.
    The artifact embeds degraded/health counters so bench_trend.py
    can fold them."""
    from gubernator_tpu.cluster.harness import ClusterHarness, cluster_behaviors
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    from dataclasses import replace as dc_replace

    n_nodes = int(os.environ.get("BENCH_NODES", 4))
    n_threads = int(os.environ.get("BENCH_DEADPEER_THREADS", 8))
    degraded = os.environ.get("GUBER_DEGRADED_LOCAL", "1").strip().lower() not in (
        "0", "false", "no", "off"
    )
    behaviors = dc_replace(cluster_behaviors(), degraded_local=degraded)
    h = ClusterHarness().start(
        n_nodes, behaviors=behaviors, cache_size=CAPACITY
    )
    try:
        entry = h.daemons[0]
        # Payloads: distinct keys, round-robin — every owner gets a
        # share, so killing one daemon breaks ~1/n of the traffic.
        # Keys vary a LEADING byte: FNV-1 does not avalanche
        # trailing-byte differences (see harness._verify_membership),
        # so "dp_{i}"-style names would collapse into one ring gap
        # and skew per-owner shares wildly between runs.
        payloads = [
            pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="deadpeer", unique_key=f"{i}_dp", hits=1,
                        limit=10**9, duration=3_600_000,
                    )
                ]
            ).SerializeToString()
            for i in range(256)
        ]

        def measure(seconds: float):
            return _drive_herd(
                np, entry.grpc_address, payloads, n_threads, seconds
            )

        healthy = measure(MEASURE_SECONDS)
        victim = n_nodes - 1  # never the entry node
        h.kill(victim)
        dead = measure(MEASURE_SECONDS)
        inst = entry.instance
        dead["degraded_answers"] = inst.counters["degraded_answers"]
        dead["backoff_retries"] = inst.counters["backoff_retries"]
        dead["async_retries"] = inst.counters["async_retries"]
        dead["peer_health"] = entry.peer_health()
        return {
            "metric": "rate-limit decisions/sec, forward path with 1 of "
            f"{n_nodes} owners dead ({n_threads} client threads, "
            f"single-item RPCs via node 0, degraded_local={'on' if degraded else 'off'})",
            "value": dead["value"],
            "unit": "decisions/sec",
            "vs_baseline": round(dead["value"] / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": dead["p50_ms"],
            "p99_ms": dead["p99_ms"],
            "degraded_local": degraded,
            "healthy": healthy,
            "dead": dead,
            "platform": platform,
        }
    finally:
        h.stop()


def _run_reshard(np, platform: str) -> dict:
    """Elastic-membership A/B (ISSUE 7 acceptance): throughput/latency
    while the cluster RESHARDS under load — a 5th node joins mid-run,
    then an original owner drains out — vs a same-shape
    static-membership control (BENCH_RESHARD_STATIC=1, committed as
    the *_static artifact).

    4 in-process daemons; a client herd drives single-item requests
    with keys spread across all owners through node 0.  In reshard
    mode an event thread fires `add_peer` at ~25% of the window and
    `drain_peer` (a non-entry original) at ~60%; the artifact embeds
    the drain stats, handoff row counters, epochs, and dual-window
    seconds so scripts/bench_trend.py can fold them."""
    from gubernator_tpu.cluster.harness import ClusterHarness
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    n_nodes = int(os.environ.get("BENCH_NODES", 4))
    n_threads = int(os.environ.get("BENCH_RESHARD_THREADS", 8))
    static = os.environ.get("BENCH_RESHARD_STATIC", "0") != "0"
    h = ClusterHarness().start(n_nodes, cache_size=CAPACITY)
    try:
        entry = h.daemons[0]
        # Keys vary a LEADING byte (FNV-1 trailing-byte collapse; see
        # harness._verify_membership) so every owner gets a share and
        # the reshard actually moves live traffic.
        payloads = [
            pb.GetRateLimitsReq(
                requests=[
                    pb.RateLimitReq(
                        name="reshard", unique_key=f"{i}_rs", hits=1,
                        limit=10**9, duration=3_600_000,
                    )
                ]
            ).SerializeToString()
            for i in range(256)
        ]

        events: dict = {}

        def reshard_events() -> None:
            # Join at ~25% of the window, drain an original owner at
            # ~60% — both land while the herd is at full rate.
            time.sleep(MEASURE_SECONDS * 0.25)
            t0 = time.perf_counter()
            h.add_peer()
            h.wait_membership_settled(30)
            events["join_settle_s"] = round(time.perf_counter() - t0, 3)
            time.sleep(MEASURE_SECONDS * 0.35)
            t0 = time.perf_counter()
            victim = h.daemons[1]
            events["drain"] = h.drain_peer(1)
            h.wait_membership_settled(30)
            events["drain_settle_s"] = round(time.perf_counter() - t0, 3)
            # drain_peer popped the victim from h.daemons — snapshot
            # its counters here or the summed totals silently drop
            # the entire drain volume (and any drain forfeits).
            events["drained_node"] = dict(victim.instance.handoff_counters)

        result = _drive_herd(
            np, entry.grpc_address, payloads, n_threads,
            MEASURE_SECONDS, during=None if static else reshard_events,
        )
        value = result["value"]
        drained = events.get("drained_node", {})
        membership = {
            "epochs": h.membership_epochs(),
            "dual_seconds": round(
                max(d.membership.dual_seconds() for d in h.daemons), 4
            ),
            "handoff": {
                k: sum(
                    d.instance.handoff_counters[k] for d in h.daemons
                )
                + drained.get(k, 0)
                for k in ("shipped", "forfeited", "received")
            },
            **{k: v for k, v in events.items() if k != "drained_node"},
        }
        return {
            "metric": "rate-limit decisions/sec, "
            + (
                f"static {n_nodes}-node control"
                if static
                else f"{n_nodes}-node cluster resharding mid-run "
                "(join a 5th, drain an original owner)"
            )
            + f" ({n_threads} client threads, single-item RPCs via node 0)",
            "value": value,
            "unit": "decisions/sec",
            "vs_baseline": round(value / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": result["p50_ms"],
            "p99_ms": result["p99_ms"],
            "requests": result["requests"],
            "errors": result["errors"],
            "reshard": not static,
            "membership": membership,
            "platform": platform,
        }
    finally:
        h.stop()


def _run_global(np, platform: str) -> dict:
    """BASELINE config 3: GLOBAL behavior over a local cluster.

    Every request carries Behavior.GLOBAL; clients spray all nodes, so
    non-owners answer from the owner-broadcast status cache while hits
    aggregate asynchronously to owners (reference: global.go;
    benchmark_test.go:29-148's GLOBAL subtest).

    On the CPU host the cluster runs one daemon PROCESS per node
    (BENCH_GLOBAL_PROCS=0 restores the in-process harness): in-process
    nodes share one GIL, a serialization the Go reference never pays,
    and the artifact should measure the serving stack, not CPython's
    scheduler.  On an accelerator host the in-process harness stands
    (N processes cannot share one device)."""
    from gubernator_tpu.cluster.harness import ClusterHarness
    from gubernator_tpu.net.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior

    n_nodes = int(os.environ.get("BENCH_NODES", 4))
    n_threads = int(os.environ.get("BENCH_WIRE_THREADS", 8))
    wire_batch = min(BATCH, 1000)
    procs_default = "1" if platform == "cpu" else "0"
    if os.environ.get("BENCH_GLOBAL_PROCS", procs_default) != "0":
        return _run_global_procs(np, platform, n_nodes, wire_batch)
    h = ClusterHarness().start(n_nodes, cache_size=CAPACITY)
    try:
        addrs = [h.peer_at(i).grpc_address for i in range(n_nodes)]
        n_procs = int(os.environ.get("BENCH_WIRE_PROCS", "0"))
        if n_procs:
            rate, p50_ms, p99_ms = _drive_grpc_procs(
                np, addrs, n_procs, wire_batch, behavior=int(Behavior.GLOBAL)
            )
            n_threads = n_procs
        else:
            payloads = _build_payloads(pb, wire_batch, behavior=int(Behavior.GLOBAL))
            rate, p50_ms, p99_ms = _drive_grpc(np, addrs, payloads, n_threads, wire_batch)
        return {
            "metric": f"rate-limit decisions/sec, GLOBAL, {n_nodes}-node "
            f"in-process cluster (batch={wire_batch}, {n_threads} client "
            f"threads, {N_KEYS} hot keys)",
            "value": round(rate, 1),
            "unit": "decisions/sec",
            "vs_baseline": round(rate / BASELINE_DECISIONS_PER_SEC, 2),
            "p50_ms": p50_ms,
            "p99_ms": p99_ms,
            "platform": platform,
        }
    finally:
        h.stop()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--wire-client":
        sys.exit(_client_proc_main())
    sys.exit(main())
