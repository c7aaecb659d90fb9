"""V1Instance — the request router over the TPU decision engine.

reference: gubernator.go:46-854.  The reference walks each request item
through a goroutine maze (per-item peer pick → worker channel hop →
per-key algorithm call).  Here the router is *batch-first*, matching
how the TPU engine wants its work:

  1. validate every item (error-in-response, never error-in-RPC);
  2. one vectorized owner lookup for the whole batch (hash ring);
  3. partition: LOCAL (we own) / GLOBAL non-owner / FORWARD per peer;
  4. LOCAL items go to the engine as ONE batch (one device step per
     duplicate-key round) — the reference's worker fan-out collapses
     into the vmapped kernel;
  5. GLOBAL non-owners answer from the host status cache (owner
     broadcasts land there) and queue async hits;
  6. FORWARD items ride the per-peer batching client with the
     reference's 5-retry ownership-migration loop.

Responses keep request order exactly (reference: gubernator.go:524-531).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu.cluster.global_manager import GlobalManager
from gubernator_tpu.cluster.hash_ring import (
    RegionPicker,
    ReplicatedConsistentHash,
)
from gubernator_tpu.cluster.health import backoff_delay
from gubernator_tpu.cluster.multiregion import MultiRegionManager
from gubernator_tpu.cluster.peer_client import PeerClient, PeerError
from gubernator_tpu.config import BehaviorConfig, Config
from gubernator_tpu.utils.metrics import CPU_MIN_ITEMS, DurationStat, stage
from gubernator_tpu.types import (
    MAX_BATCH_SIZE,
    Algorithm,
    Behavior,
    HealthCheckResp,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
    UpdatePeerGlobal,
    has_behavior,
)

log = logging.getLogger("gubernator_tpu.service")

# Hot-loop int constants (IntFlag ops are ~1.5µs each in CPython; see
# core/engine.py note).
_GLOBAL_I = int(Behavior.GLOBAL)
_MULTI_REGION_I = int(Behavior.MULTI_REGION)
_SKETCH_I = int(Behavior.SKETCH)
_TOKEN_I = int(Algorithm.TOKEN_BUCKET)
# Rows carrying these can never be answered from replicated leased
# credit: the ledger's precondition breakers, plus MULTI_REGION (a
# replica answer would skip the owner's region-hit queueing) and
# SKETCH (node-local approximate limiter — ownership doesn't apply).
# cluster/replication.py pins the same set on its serve probes.
_LEASE_BREAKERS = (
    int(Behavior.DURATION_IS_GREGORIAN)
    | int(Behavior.RESET_REMAINING)
    | _MULTI_REGION_I
    | _SKETCH_I
)

# Behaviors that need the dataclass path: GLOBAL (status cache + async
# queues), MULTI_REGION (region queues), Gregorian durations (per-item
# civil-time validation with error-in-response), SKETCH (the
# approximate limiter, not the bucket engine).
COLUMNAR_DISQUALIFIERS = (
    _GLOBAL_I | _MULTI_REGION_I | int(Behavior.DURATION_IS_GREGORIAN)
    | _SKETCH_I
)

HEALTHY = "healthy"
UNHEALTHY = "unhealthy"


class ServiceError(RuntimeError):
    """RPC-level error (maps to a gRPC status at the transport edge).

    The only RPC-level failure the contract allows is an oversized
    batch (reference: gubernator.go:212-216, 501-505); per-item
    problems travel in RateLimitResp.error.
    """

    def __init__(self, message: str, code: str = "OUT_OF_RANGE"):
        super().__init__(message)
        self.code = code


class _SubBatch:
    """Column view of a GLOBAL serve's engine sub-batch, shaped like a
    DecodedBatch so the group-commit window can concatenate it with
    concurrent submissions (net/wire_window.WireWindow)."""

    __slots__ = (
        "n", "key_buf", "key_offsets", "algo", "behavior", "hits",
        "limit", "duration", "burst", "fnv1a",
    )


def _slice_key_columns(key_buf: np.ndarray, key_offsets: np.ndarray, idx):
    """Vectorized sub-selection of a concatenated key buffer: returns
    (sub_buf, sub_offsets) for the items in `idx` without per-item
    Python (the GLOBAL wire route partitions batches this way)."""
    from gubernator_tpu.net.wire_codec import gather_key_slices

    lens = key_offsets[1:] - key_offsets[:-1]
    return gather_key_slices(key_buf, key_offsets[:-1][idx], lens[idx])


class _GlobalEntry:
    """One cached owner-broadcast status.  __slots__ + a hand-rolled
    __init__: broadcast receive is the cluster tier's highest-rate
    per-item loop (put_columns profiled at ~26% of a core under
    GLOBAL overload), so entry construction stays minimal."""

    __slots__ = ("resp", "algorithm", "expire_at", "cols")

    def __init__(self, resp, algorithm, expire_at, cols=()):
        self.resp = resp
        self.algorithm = algorithm
        self.expire_at = expire_at
        # (status, limit, remaining, reset) ints, preassembled at put
        # time so the columnar read does no attribute/enum work per
        # item.
        self.cols = cols


class _GlobalStatusCache:
    """Host cache of owner-broadcast GLOBAL statuses on non-owners.

    The reference stores a RateLimitResp (not bucket state) in the same
    size-bounded LRU as buckets (gubernator.go:470-490, read
    gubernator.go:440-453).  Our bucket state lives on device, so the
    non-owner overwrite dance gets its own host-side LRU with the same
    ExpireAt=ResetTime rule and capacity bound.
    """

    def __init__(self, capacity: int = 50_000) -> None:
        from collections import OrderedDict

        self.capacity = capacity
        # Keyed by the hash key BYTES: the columnar wire path reads
        # keys straight out of the decoded key buffer without ever
        # materializing Python strings.
        self._items: "OrderedDict[bytes, _GlobalEntry]" = OrderedDict()
        self._lock = threading.Lock()

    @staticmethod
    def _k(key) -> bytes:
        return key.encode() if isinstance(key, str) else key

    def get(self, key, now_ms: int) -> Optional[RateLimitResp]:
        with self._lock:
            return self._get_locked(self._k(key), now_ms)

    def get_many(
        self, keys: Sequence, now_ms: int
    ) -> List[Optional[RateLimitResp]]:
        """Batch lookup under ONE lock acquisition (VERDICT r1 weak 8:
        a lock per item on the GLOBAL read path becomes a contention
        point at wire batch sizes)."""
        with self._lock:
            return [self._get_locked(self._k(k), now_ms) for k in keys]

    def get_columns(self, keys: List[bytes], now_ms: int):
        """Columnar lookup: (hit bool[n], status i32[n], limit i64[n],
        remaining i64[n], reset i64[n]) — the GLOBAL wire fast path's
        read (no response objects, one lock)."""
        import numpy as np

        n = len(keys)
        hit = np.zeros(n, dtype=bool)
        status = np.zeros(n, dtype=np.int32)
        limit = np.zeros(n, dtype=np.int64)
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        with self._lock:
            items = self._items
            get = items.get
            move = items.move_to_end
            for i, k in enumerate(keys):
                e = get(k)
                if e is None:
                    continue
                if e.expire_at and now_ms >= e.expire_at:
                    del items[k]
                    continue
                move(k)
                hit[i] = True
                status[i], limit[i], remaining[i], reset[i] = e.cols
        return hit, status, limit, remaining, reset

    def _get_locked(self, key: bytes, now_ms: int) -> Optional[RateLimitResp]:
        e = self._items.get(key)
        if e is None:
            return None
        if e.expire_at and now_ms >= e.expire_at:
            del self._items[key]
            return None
        self._items.move_to_end(key)
        if e.resp is None:
            # Columnar puts (the broadcast wire path) defer the
            # response object; only the pb read path pays for it.
            st, lim, rem, rst = e.cols
            e.resp = RateLimitResp(
                status=Status(st), limit=lim, remaining=rem,
                reset_time=rst,
            )
        return e.resp

    def put_columns(self, dec) -> None:
        """Columnar insert from a decoded UpdatePeerGlobalsReq
        (net/wire_codec.DecodedGlobals) — no response objects.  The
        numpy→int conversions happen ONCE per batch via tolist();
        the loop body is dict ops only."""
        raw = dec.key_buf.tobytes()
        off = dec.key_offsets.tolist()
        has = dec.has_status.tolist()
        algo = dec.algo.tolist()
        status = dec.status.tolist()
        limit = dec.limit.tolist()
        remaining = dec.remaining.tolist()
        reset = dec.reset_time.tolist()
        entry = _GlobalEntry
        items = self._items
        move = items.move_to_end
        with self._lock:
            for i in range(dec.n):
                if not has[i]:
                    continue
                key = raw[off[i]:off[i + 1]]
                rst = reset[i]
                items[key] = entry(
                    None, algo[i], rst,
                    (status[i], limit[i], remaining[i], rst),
                )
                move(key)
            while len(items) > self.capacity:
                items.popitem(last=False)

    def put(self, key, resp: RateLimitResp, algorithm: int) -> None:
        with self._lock:
            self._put_locked(self._k(key), resp, algorithm)

    def put_many(self, entries) -> None:
        """Batch insert under ONE lock acquisition — UpdatePeerGlobals
        delivers up to MAX_BATCH_SIZE statuses per RPC and a lock per
        item contends with the serving path's get_many."""
        with self._lock:
            for key, resp, algorithm in entries:
                self._put_locked(self._k(key), resp, algorithm)

    def _put_locked(self, key: bytes, resp: RateLimitResp, algorithm: int) -> None:
        self._items[key] = _GlobalEntry(
            resp=resp,
            algorithm=algorithm,
            expire_at=resp.reset_time,
            cols=(
                int(resp.status), resp.limit, resp.remaining,
                resp.reset_time,
            ),
        )
        self._items.move_to_end(key)
        while len(self._items) > self.capacity:
            self._items.popitem(last=False)

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


class V1Instance:
    """The service core: routing + local engine + cluster managers."""

    def __init__(self, conf: Config, engine):
        """`engine` is a DecisionEngine or ShardedDecisionEngine (both
        expose get_rate_limits/sweep/cache_size/close)."""
        self.conf = conf
        self.engine = engine
        self.global_cache = _GlobalStatusCache(capacity=conf.cache_size)
        # Host-tier decision ledger (core/ledger.py): sticky over-limit
        # answers + bounded credit leases serve hot-key decisions with
        # zero device work.  The owner-broadcast status cache above is
        # its read-only tier (non-owner GLOBAL entries).
        self.ledger = None
        if getattr(conf, "ledger", True) and getattr(
            engine, "apply_columnar", None
        ) is not None and getattr(engine, "store", None) is None:
            from gubernator_tpu.core.ledger import DecisionLedger

            self.ledger = DecisionLedger(
                engine,
                lease_size=getattr(conf, "ledger_lease", 512),
                lease_ttl=getattr(conf, "ledger_lease_ttl", 0.2),
                hot_threshold=getattr(conf, "ledger_hot_threshold", 8),
                max_keys=getattr(conf, "ledger_keys", 65536),
                settle_interval=getattr(
                    conf, "ledger_settle_interval", 0.05
                ),
            )
            self.ledger.attach_readonly(self.global_cache)
        self.global_mgr = GlobalManager(conf.behaviors, self)
        self.multi_region_mgr = MultiRegionManager(conf.behaviors, self)
        from gubernator_tpu.cluster.hash_ring import make_picker

        # guberlint: guard local_picker, region_picker by _peer_lock
        self.local_picker: ReplicatedConsistentHash[PeerClient] = make_picker(
            getattr(conf, "peer_picker", "replicated-hash"),
            conf.hash_algorithm,
            getattr(conf, "picker_replicas", 512),
        )
        self.region_picker: RegionPicker[PeerClient] = RegionPicker(
            conf.hash_algorithm, getattr(conf, "picker_replicas", 512)
        )
        self._peer_lock = threading.RLock()
        self._forward_pool = ThreadPoolExecutor(
            max_workers=32, thread_name_prefix="guber-forward"
        )
        self._closed = False
        # Metric counters (reference: gubernator.go:59-113), scraped by
        # utils.metrics into the /metrics endpoint.
        self.counters = {
            "local": 0,
            "columnar": 0,  # items served via the columnar wire fast path
            "forward": 0,
            "global": 0,
            "sketch": 0,  # items decided by the approximate limiter
            # GLOBAL items served by a LOCAL eventually-consistent copy
            # (status-cache miss on a non-owner).  This is the source
            # of GLOBAL's bounded over-admission: worst case each
            # node's local copy admits up to `limit` before the first
            # broadcast converges the cache (see README, reference:
            # architecture.md:46-74).
            "global_miss_local": 0,
            "check_errors": 0,
            "async_retries": 0,
            # Forward retries that waited out a backoff window first
            # (the reference's loop re-picked with zero delay).
            "backoff_retries": 0,
            # Requests answered by OUR engine because every owner
            # candidate was circuit-open/unreachable (degraded mode,
            # GUBER_DEGRADED_LOCAL).  Each one is availability bought
            # with bounded over-admission — RESILIENCE.md.
            "degraded_answers": 0,
            # Peer-owned items answered LOCALLY from a replica credit
            # lease (cluster/replication.py) — the forward hops the
            # hot-key replication plane removed.
            "replicated_local": 0,
            # MULTI_REGION answers served while at least one remote
            # region's aggregate circuit was OPEN (the answer is
            # region-local as always, but cross-region convergence is
            # deferred): flagged metadata.degraded_region=true, drift
            # bounded at N_regions × limit (RESILIENCE.md §12).
            "degraded_region_answers": 0,
        }
        # Ownership-handoff traffic (cluster/handoff.py), exported as
        # gubernator_handoff_keys{event}: rows shipped to new owners,
        # rows forfeited at the epoch deadline, rows received and
        # restored here.  The membership manager (attached by the
        # daemon as `self.membership`) shares this dict.
        self.handoff_counters = {"shipped": 0, "forfeited": 0, "received": 0}
        # Highest handoff (boot, epoch) seen per source address — the
        # receiver's stale-window guard (cluster/handoff.py).
        self.handoff_epoch_seen: Dict[str, Tuple[str, int]] = {}
        # MembershipManager (cluster/membership.py), set by the daemon
        # after construction; None for bare library instances.
        self.membership = None
        # Peer-flush duration summary, shared by every PeerClient this
        # instance creates (reference: guber_batch_send_duration).
        self.flush_duration = DurationStat()
        # Stage timers: the cluster-tier p50 budget, end to end
        # (VERDICT r5 next-round #3).  Every serial stage a GLOBAL
        # decision can wait on is measured where it happens — the
        # client group-commit window, the engine dispatch, the hit
        # window, the owner RPC, and the broadcast's enqueue→delivered
        # age — and exported as gubernator_stage_duration{stage=...}.
        self.stage_timers = {
            "wire_window_wait": DurationStat(),
            "engine_serve": DurationStat(),
            "hits_window_wait": self.global_mgr.hits_window_wait,
            "owner_rpc": self.global_mgr.owner_rpc_duration,
            "broadcast_age": self.global_mgr.broadcast_age,
            # Cross-region hop budget (RESILIENCE.md §12 / PERF.md
            # §28): how long queued region deltas wait for their
            # window, and the per-region push RPC itself.
            "multiregion.window_wait": self.multi_region_mgr.window_wait,
            "multiregion.region_rpc": self.multi_region_mgr.region_rpc,
        }
        # Device-plane budget (PERF.md §24, mirroring the §10b host
        # stages): device.step is the per-dispatch wall time of the
        # fused decision kernel, device.readback the blocking d2h
        # materialization, device.window_wait the pump-queue wait of a
        # packed round before its fused dispatch.  All three ride
        # gubernator_stage_duration / gubernator_stage_quantile_seconds
        # and Daemon.stage_budget() → /debug/vars, so "where do device
        # milliseconds go" is answerable from a scrape.
        # getattr-guarded: jax-free smoke/test stubs stand in for the
        # engine without the device plane.
        round_dur = getattr(engine, "round_duration", None)
        if round_dur is not None:
            self.stage_timers["device.step"] = round_dur
        # The served wire route, stage by stage (utils/metrics.stage;
        # OBSERVABILITY.md §3): the codec's two ends here, the engine's
        # own stages (lock wait/hold, intern, pack, h2d, launch,
        # set_expiry, unpack, sweep; mesh.route on the sharded engine)
        # from the engine, the listener's queue wait from the daemon.
        self.stage_timers["wire.decode"] = DurationStat()
        self.stage_timers["service.hotkeys"] = DurationStat()
        self.stage_timers["wire.encode"] = DurationStat()
        self.stage_timers.update(getattr(engine, "stages", {}))
        transfer = getattr(
            getattr(engine, "readback", None), "transfer_duration", None
        )
        if transfer is not None:
            self.stage_timers["device.readback"] = transfer
        pump = getattr(engine, "_pump", None)
        if pump is not None:
            self.stage_timers["device.window_wait"] = pump.window_wait
        # Paged plane (GUBER_PAGED; PERF.md §30): device.page_fault is
        # the per-fault spill+refill wall time a non-resident key pays
        # before its round can dispatch.
        paging = getattr(engine, "paging", None)
        if paging is not None:
            self.stage_timers["device.page_fault"] = paging.fault_duration
        # Optional group-commit window for client wire batches
        # (net/wire_window.py; conf.local_batch_wait > 0 enables).
        self._wire_window = None
        if conf.local_batch_wait > 0:
            from gubernator_tpu.net.wire_window import WireWindow

            self._wire_window = WireWindow(
                engine,
                conf.local_batch_wait,
                adaptive=getattr(conf.behaviors, "adaptive_windows", True),
                wait_stat=self.stage_timers["wire_window_wait"],
                apply_stat=self.stage_timers["engine_serve"],
            )
        # GLOBAL serve-route group commit: concurrent engine
        # sub-batches (client serves + peer hit pushes + miss copies)
        # share one dispatch.  Load-adaptive — an isolated apply pays
        # no window (conf.global_serve_window caps the wait).
        self._global_window = None
        if getattr(conf, "global_serve_window", 0.0) > 0:
            from gubernator_tpu.net.wire_window import WireWindow

            self._global_window = WireWindow(
                engine,
                conf.global_serve_window,
                adaptive=getattr(conf.behaviors, "adaptive_windows", True),
                # Both group-commit windows report into the same two
                # stages: wire_window_wait is "time spent waiting for a
                # shared window" and engine_serve is "one observation
                # per device dispatch" wherever the dispatch happens.
                wait_stat=self.stage_timers["wire_window_wait"],
                apply_stat=self.stage_timers["engine_serve"],
            )
        # Count-min-sketch approximate limiter (Behavior.SKETCH),
        # created lazily on first flagged request (GUBER_SKETCH_*).
        self._sketch = None
        self._sketch_lock = threading.Lock()
        # Hot-key attribution: space-saving top-K over decision keys
        # (utils/hotkeys.py; GUBER_HOTKEYS / GUBER_HOTKEYS_K — None
        # when disabled, costing one attribute check per batch).
        # Served by /debug/hotkeys and gubernator_hotkeys.
        from gubernator_tpu.utils import hotkeys as _hotkeys

        self.hotkeys = _hotkeys.from_env()
        # Feed the paged plane's clock-hand heat ranking from the same
        # sketch (core/paging._maybe_refresh_hot): pages holding top-K
        # keys get one eviction grace pass.  The provider runs under
        # the engine lock, so the contains→intern pair is atomic (the
        # native table has no read-only key→slot lookup; intern on a
        # present key is a pure lookup).
        if paging is not None and self.hotkeys is not None:
            _sketch = self.hotkeys
            _table = engine.table
            _clock = engine.clock

            def _hot_slots() -> List[int]:
                out: List[int] = []
                now = _clock.now_ms()
                for key, rate, _lim, _dur in _sketch.top_rates(32):
                    if rate <= 0:
                        break
                    try:
                        ks = key.decode()
                    except UnicodeDecodeError:
                        continue
                    if _table.contains(ks):
                        out.append(_table.intern(ks, now, []))
                return out

            paging.hot_slots_provider = _hot_slots
        # Hot-key replication plane (cluster/replication.py), attached
        # by the daemon: peer-owned keys with a live replica lease
        # answer locally from pre-debited credit — zero forward hops.
        # None for bare library instances (one attribute check per
        # batch when absent).
        self.replication = None
        if self.ledger is not None and self.hotkeys is not None:
            # Native-plane drains surface per-key counts only at pull
            # time (core/ledger._undelegate_locked) — credit them so
            # natively-answered keys appear in /debug/hotkeys too.
            self.ledger.hotkeys = self.hotkeys
        # Tail flight recorder (utils/flight_recorder.py), attached by
        # the daemon when in-memory tracing is active; /debug/trace
        # serves its dump.
        self.flight_recorder = None
        # Native event collector (utils/native_events.py), attached by
        # the daemon when the h2 fast front runs with its event ring.
        self.native_events = None
        # Fleet observability plane (obs/): the rollup collector and
        # SLO watchdog, attached by the daemon (GUBER_OBS); None for
        # bare library instances.  The admission watch is always
        # present — it costs one attribute peek while no key is
        # watched, and the serve-path hooks need a stable handle.
        from gubernator_tpu.obs.slo import AdmissionWatch

        self.obs = None
        self.slo_watchdog = None
        self.admission_watch = AdmissionWatch()

    def sketch(self):
        if self._sketch is None:
            with self._sketch_lock:
                if self._sketch is None:
                    from gubernator_tpu.ops.sketch import SketchLimiter

                    self._sketch = SketchLimiter(
                        window_ms=getattr(self.conf, "sketch_window_ms", 1000),
                        depth=getattr(self.conf, "sketch_depth", 4),
                        width=getattr(self.conf, "sketch_width", 1 << 20),
                    )
        return self._sketch

    def _apply_sketch(
        self, keys, hits, limit, now_ms: int, key_hashes=None
    ):
        """Run one sketch batch → (status, limit, remaining, reset)
        columns.  remaining = limit - estimate (floored at 0); reset =
        end of the current sketch window."""
        sk = self.sketch()
        over, est = sk.apply(
            keys, np.asarray(hits, dtype=np.int64),
            np.asarray(limit, dtype=np.int64), now_ms,
            key_hashes=key_hashes,
        )
        limit64 = np.asarray(limit, dtype=np.int64)
        remaining = np.maximum(limit64 - est, 0)
        reset = np.full(
            len(est),
            (now_ms // sk.window_ms + 1) * sk.window_ms,
            dtype=np.int64,
        )
        self.counters["sketch"] += len(est)
        return over.astype(np.int32), limit64, remaining, reset

    # ------------------------------------------------------------------
    # Public API (reference: proto/gubernator.proto service V1)

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """reference: gubernator.go:197-317 (GetRateLimits)."""
        from gubernator_tpu.utils.tracing import span

        with span("service.get_rate_limits", batch=len(requests)):
            return self._get_rate_limits(requests)

    def _get_rate_limits(
        self, requests: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        if len(requests) > MAX_BATCH_SIZE:
            self.counters["check_errors"] += 1
            raise ServiceError(
                f"Requests.RateLimits list too large; max size is '{MAX_BATCH_SIZE}'"
            )
        n = len(requests)
        responses: List[Optional[RateLimitResp]] = [None] * n
        now_ms = self.engine.clock.now_ms()

        # 1. validate (reference: gubernator.go:231-243).  Sketch items
        # split off here: the approximate limiter is node-local, so
        # they must not pay the ring lookup below.
        candidates: List[int] = []
        sketch_idx: List[int] = []
        for i, r in enumerate(requests):
            if not r.unique_key:
                self.counters["check_errors"] += 1
                responses[i] = RateLimitResp(error="field 'unique_key' cannot be empty")
            elif not r.name:
                self.counters["check_errors"] += 1
                responses[i] = RateLimitResp(error="field 'namespace' cannot be empty")
            elif int(r.behavior) & _SKETCH_I:
                sketch_idx.append(i)
            else:
                candidates.append(i)

        # 2. one vectorized owner lookup for the batch
        keys = [requests[i].hash_key() for i in candidates]
        if self.hotkeys is not None and keys:
            self.hotkeys.offer_many_params(
                (
                    k.encode(),
                    max(requests[i].hits, 1),
                    # Lease-sizing aux: only rows the lease algebra
                    # could cover stamp their params (the promotion
                    # plane skips keys whose last limit reads 0).
                    requests[i].limit
                    if (
                        int(requests[i].algorithm) == _TOKEN_I
                        and not int(requests[i].behavior) & _LEASE_BREAKERS
                    )
                    else 0,
                    requests[i].duration,
                )
                for k, i in zip(keys, candidates)
            )
        with self._peer_lock:
            if self.local_picker.size() == 0:
                owners: List[Optional[PeerClient]] = [None] * len(candidates)
            else:
                owners = self.local_picker.get_batch(keys)

        # 3. partition
        local_idx: List[int] = []
        forward: Dict[str, Tuple[PeerClient, List[int]]] = {}
        global_items: List[Tuple[int, PeerClient]] = []
        global_miss: List[Tuple[int, PeerClient]] = []
        repl = self.replication
        repl_live = repl is not None and repl.has_leases
        for k, i, owner in zip(keys, candidates, owners):
            r = requests[i]
            if owner is None or owner.info.is_owner:
                local_idx.append(i)
                continue
            if repl_live:
                # Hot-key replication override (cluster/replication.py):
                # a peer-owned key with a live replica lease answers
                # HERE from pre-debited credit — no forward hop, and
                # (for GLOBAL items) no async hit queue either: the
                # owner already debited these hits at grant time.
                # (`k` is the hash key step 2 already built.)
                ans = repl.try_answer(
                    k.encode(), int(r.algorithm),
                    int(r.behavior), r.hits, r.limit, r.duration,
                    now_ms,
                )
                if ans is not None:
                    st, rem, rst = ans
                    self.counters["replicated_local"] += 1
                    responses[i] = RateLimitResp(
                        status=Status(st), limit=r.limit, remaining=rem,
                        reset_time=rst,
                        metadata={
                            "owner": owner.info.grpc_address,
                            "replicated": "true",
                        },
                    )
                    continue
            if int(r.behavior) & _GLOBAL_I:
                # reference: gubernator.go:276-287, 426-466
                global_items.append((i, owner))
            else:
                addr = owner.info.grpc_address
                forward.setdefault(addr, (owner, []))[1].append(i)

        # GLOBAL non-owners: batch the hit queueing and the status-cache
        # lookups (one lock each per wire batch, not per item).
        if global_items:
            self.counters["global"] += len(global_items)
            self.global_mgr.queue_hits_many(
                requests[i] for i, _ in global_items
            )
            cached_list = self.global_cache.get_many(
                [requests[i].hash_key() for i, _ in global_items], now_ms
            )
            for (i, owner), cached in zip(global_items, cached_list):
                if cached is not None:
                    responses[i] = replace(
                        cached,
                        metadata={"owner": owner.info.grpc_address},
                    )
                else:
                    # Cache miss: process locally as a NO_BATCHING copy
                    # (reference: gubernator.go:455-460).
                    global_miss.append((i, owner))
            self.counters["global_miss_local"] += len(global_miss)

        # 3b. sketch items: one approximate-limiter batch (node-local;
        # MULTI_REGION-flagged sketch items still queue region
        # replication so remote DCs' sketches see the hits).
        if sketch_idx:
            s_keys = [requests[i].hash_key().encode() for i in sketch_idx]
            st, lim, rem, rst = self._apply_sketch(
                s_keys,
                [requests[i].hits for i in sketch_idx],
                [requests[i].limit for i in sketch_idx],
                now_ms,
            )
            status_of = {int(s): s for s in Status}
            for j, i in enumerate(sketch_idx):
                responses[i] = RateLimitResp(
                    status=status_of[int(st[j])],
                    limit=int(lim[j]),
                    remaining=int(rem[j]),
                    reset_time=int(rst[j]),
                )
                if int(requests[i].behavior) & _MULTI_REGION_I:
                    self.multi_region_mgr.queue_hits(requests[i])

        # 4. local + global-miss items: ONE engine batch
        engine_items = local_idx + [i for i, _ in global_miss]
        if engine_items:
            engine_reqs = [requests[i] for i in local_idx]
            for i, _ in global_miss:
                engine_reqs.append(
                    replace(requests[i], behavior=int(Behavior.NO_BATCHING))
                )
            self.counters["local"] += len(local_idx)
            engine_resps = self.apply_local_batch(engine_reqs, now_ms=now_ms)
            for j, i in enumerate(engine_items):
                responses[i] = engine_resps[j]
            for i, owner in global_miss:
                responses[i].metadata = {"owner": owner.info.grpc_address}

        # 5. forward the rest (async per peer, 5-retry loop).  The
        # forward pool is another thread, so the caller's span context
        # travels explicitly (tracing.current_context is thread-local).
        if forward:
            from gubernator_tpu.utils import tracing

            fwd_ctx = tracing.current_context()
            futures = []
            for addr, (peer, idxs) in forward.items():
                self.counters["forward"] += len(idxs)
                futures.append(
                    self._forward_pool.submit(
                        self._forward_group, peer, idxs, requests,
                        responses, fwd_ctx,
                    )
                )
            for f in futures:
                f.result()

        aw = self.admission_watch
        if aw.active:
            # Admission-bound invariant feed (obs/slo.py): watched
            # finite-limit keys count their CLIENT-VISIBLE admitted
            # hits here, at the client-facing boundary — local,
            # forwarded, degraded, GLOBAL-cached and replica-lease
            # answers all land in `responses` by now.  Internal
            # re-applies (multiregion delta pushes, GLOBAL hit
            # windows, handoff restores) arrive via the peer routes
            # and are deliberately NOT counted: they re-play hits a
            # client was already answered for, and counting them
            # would double-bill the N×limit bound.
            aw.observe_batch(requests, responses)
        return responses  # type: ignore[return-value]

    def _degraded_answer(
        self,
        ids: List[int],
        requests: Sequence[RateLimitReq],
        responses: List[Optional[RateLimitResp]],
        owner_addr: str,
    ) -> None:
        """Serve forward items from OUR engine because their owner is
        unreachable (circuit open / retries exhausted).  The response
        is flagged (`metadata.degraded`) so callers can tell an
        authoritative answer from a partition-local one.  Availability
        over accuracy, exactly like the reference's design creed
        (architecture.md:5-11): worst case each partition side admits
        up to `limit` independently — N_partitions × limit total, the
        same shape as the GLOBAL broadcast-lag bound (RESILIENCE.md)."""
        from gubernator_tpu.utils import tracing

        tracing.add_event(
            "degraded_answer", owner=owner_addr, items=len(ids)
        )
        resps = self.apply_local_batch([requests[i] for i in ids])
        self.counters["degraded_answers"] += len(ids)
        for i, resp in zip(ids, resps):
            md = dict(resp.metadata) if resp.metadata else {}
            md["degraded"] = "true"
            md["owner"] = owner_addr
            resp.metadata = md
            responses[i] = resp

    def _forward_group(
        self,
        peer: PeerClient,
        idxs: List[int],
        requests: Sequence[RateLimitReq],
        responses: List[Optional[RateLimitResp]],
        parent_ctx=None,
    ) -> None:
        """Span shim re-anchoring the forward-pool thread to the
        caller's trace (tracing.current_context is thread-local); the
        ownership-migration loop lives in _forward_group_traced."""
        from gubernator_tpu.utils.tracing import span

        with span(
            "forward.group", parent_ctx=parent_ctx,
            peer=peer.info.grpc_address, batch=len(idxs),
        ):
            self._forward_group_traced(peer, idxs, requests, responses)

    def _forward_group_traced(
        self,
        peer: PeerClient,
        idxs: List[int],
        requests: Sequence[RateLimitReq],
        responses: List[Optional[RateLimitResp]],
    ) -> None:
        """Forward a same-owner group with the ownership-migration loop.

        reference: gubernator.go:333-422 (asyncRequests) — ≤5 retries on
        NotReady, re-picking the owner each time; if ownership migrated
        to us mid-flight, apply locally.  Beyond the reference (the
        health plane, RESILIENCE.md):

        - re-pick rounds after a REAL dial failure sleep a capped
          exponential backoff with full jitter (the reference's loop
          re-picked with zero delay — the tail-amplifying spin "When
          Two is Worse Than One" warns about);
        - a circuit-open owner fails in one dict probe (no dial); with
          degraded mode on the items are answered locally right away
          instead of burning retries that can only land on the same
          broken peer;
        - exhausted retries answer degraded too (the pre-circuit-open
          window) unless GUBER_DEGRADED_LOCAL=0 restores the
          reference's fail-closed error strings.

        Multi-item groups go as ONE unary GetPeerRateLimits RPC (our
        client batch already coalesced them); singletons ride the
        per-peer batching client so concurrent small requests still
        coalesce across windows (the reference's thundering-herd
        protection, peer_client.go:308-376).
        """
        groups: Dict[str, Tuple[PeerClient, List[int]]] = {
            peer.info.grpc_address: (peer, idxs)
        }
        behaviors = self.conf.behaviors
        degraded_on = behaviors.degraded_local
        attempts = 0
        while groups:
            if attempts > 5:
                for _, (p, ids) in groups.items():
                    if degraded_on:
                        self._degraded_answer(
                            ids, requests, responses, p.info.grpc_address
                        )
                        continue
                    for i in ids:
                        self.counters["check_errors"] += 1
                        responses[i] = RateLimitResp(
                            error=(
                                "GetPeer() keeps returning peers that are not "
                                f"connected for '{requests[i].hash_key()}'"
                            )
                        )
                return
            retry: List[int] = []
            dialed_and_failed = False
            for _, (p, ids) in groups.items():
                if attempts != 0 and p.info.is_owner:
                    # Ownership moved to us (reference: gubernator.go:368-383).
                    resps = self.apply_local_batch([requests[i] for i in ids])
                    for i, resp in zip(ids, resps):
                        responses[i] = resp
                    continue
                try:
                    if len(ids) == 1:
                        resps = [
                            p.get_peer_rate_limit(
                                requests[ids[0]],
                                timeout=behaviors.batch_timeout,
                            )
                        ]
                    else:
                        resps = p.get_peer_rate_limits(
                            [requests[i] for i in ids],
                            timeout=behaviors.batch_timeout,
                        )
                except PeerError as e:
                    if e.circuit_open:
                        from gubernator_tpu.utils import tracing

                        tracing.add_event(
                            "circuit_open", peer=p.info.grpc_address,
                            items=len(ids),
                        )
                    if e.circuit_open and degraded_on:
                        # Broken owner, no probe due: a re-pick hands
                        # back the same peer, so answer locally NOW —
                        # this is the no-connect-timeout-storm path.
                        self._degraded_answer(
                            ids, requests, responses, p.info.grpc_address
                        )
                        continue
                    if e.not_ready:
                        self.counters["async_retries"] += len(ids)
                        retry.extend(ids)
                        if not e.circuit_open:
                            # A real dial burned a timeout — the next
                            # round must wait, not spin.
                            dialed_and_failed = True
                        continue
                    for i in ids:
                        responses[i] = RateLimitResp(
                            error=(
                                "Error while fetching rate limit "
                                f"'{requests[i].hash_key()}' from peer: {e}"
                            )
                        )
                    continue
                for i, resp in zip(ids, resps):
                    resp.metadata = {"owner": p.info.grpc_address}
                    responses[i] = resp
            if not retry:
                return
            attempts += 1
            if dialed_and_failed:
                # Capped exponential + FULL jitter between re-pick
                # rounds (cluster/health.backoff_delay): decorrelates
                # the herd that all picked the same dead owner.
                delay = backoff_delay(
                    attempts - 1,
                    behaviors.forward_backoff,
                    behaviors.forward_backoff_cap,
                )
                if delay > 0:
                    self.counters["backoff_retries"] += len(retry)
                    time.sleep(delay)
            # Re-pick owners for the retried items; they may now map to
            # different peers or to us.
            groups = {}
            for i in retry:
                try:
                    p = self.get_peer(requests[i].hash_key())
                except Exception as pick_err:  # noqa: BLE001
                    responses[i] = RateLimitResp(
                        error=(
                            "Error finding peer that owns rate limit "
                            f"'{requests[i].hash_key()}': {pick_err}"
                        )
                    )
                    continue
                groups.setdefault(p.info.grpc_address, (p, []))[1].append(i)

    # ------------------------------------------------------------------
    # Columnar fast path (the wire-side counterpart of
    # DecisionEngine.apply_columnar — VERDICT r1 item 2: the served path
    # must be the same program as the benched one).

    def _owned_mask(self, dec):
        """Per-row local-ownership bool mask for a decoded wire batch,
        or None when the picker is empty (single-node: everything is
        ours)."""
        with self._peer_lock:
            picker = self.local_picker
        n_peers = picker.size()
        if n_peers == 0:
            return None
        if n_peers == 1:
            return np.full(dec.n, bool(picker.peers()[0].info.is_owner))
        owners = picker.get_batch_dual_hashed(dec.fnv1, dec.fnv1a)
        return np.fromiter((o.info.is_owner for o in owners), bool, dec.n)

    def all_locally_owned(self, dec) -> bool:
        """True when every key in a decoded wire batch is owned by this
        node (the columnar fast paths' gate; shared with the native h2
        front so the ownership semantics cannot drift between them)."""
        owned = self._owned_mask(dec)
        return owned is None or bool(owned.all())

    def _serve_wire_replicated(self, dec) -> Optional[bytes]:
        """Columnar serve of an all-peer-owned batch from replica
        credit leases (cluster/replication.py): every row must have a
        live lease covering it, or the whole batch declines to the pb
        path (which answers leased rows there and forwards the rest).
        The common shape — a flash crowd's single-hot-key RPCs — is
        all-or-nothing by construction."""
        repl = self.replication
        if repl is None or not repl.has_leases:
            return None
        from gubernator_tpu.net import wire_codec

        now_ms = self.engine.clock.now_ms()
        idx = np.arange(dec.n, dtype=np.int64)
        out = repl.try_answer_columns(dec, idx, now_ms)
        if out is None:
            return None
        st, rem, rst = out
        self.counters["replicated_local"] += dec.n
        self.counters["columnar"] += dec.n
        self._offer_hotkeys(dec)
        return self._encode(
            wire_codec.encode_resps,
            st.astype(np.int32), np.asarray(dec.limit, dtype=np.int64),
            rem, rst,
        )

    def _offer_hotkeys(self, dec, idx=None) -> None:
        """Columnar hot-key accounting with the lease-sizing aux
        params: rows the lease algebra could never cover stamp limit 0
        so the promotion plane skips them."""
        hk = self.hotkeys
        if hk is None:
            return
        # On the RPC's own thread, before the engine lock: a numpy
        # grouping, then one native call over the unique keys with the
        # interpreter lock released (utils/hotkeys.py; PERF.md §5).  A
        # batch of CPU_MIN_ITEMS items or more reads the thread's CPU.
        n = dec.n if idx is None else len(idx)
        with stage("service.hotkeys", self.stage_timers["service.hotkeys"],
                   cpu=n >= CPU_MIN_ITEMS):
            lim = np.asarray(dec.limit)
            elig = (
                (np.asarray(dec.algo) == _TOKEN_I)
                & ((np.asarray(dec.behavior) & _LEASE_BREAKERS) == 0)
                & (lim > 0)
            )
            hk.offer_columns(
                dec.key_buf, dec.key_offsets, dec.hits, idx=idx,
                hashes=dec.fnv1a, limit=np.where(elig, lim, 0),
                duration=dec.duration,
            )

    def serve_decoded_local(self, dec, want_async: bool = False):
        """Shared post-decode columnar serve for the native fronts —
        the h2 fast front's byte windows AND the columnar feeder's
        ring windows both land here, so the ownership gate, hot-key
        accounting, and ledger semantics cannot drift between them.
        Returns (status, limit, remaining, reset) columns, or None to
        decline (caller answers UNIMPLEMENTED / falls to the pb path).

        With `want_async` the engine's answer may come back still on
        the device: a `PendingColumnar`, launched and copying, whose
        `.get()` gives the same columns — the feeder's serve thread
        launches its next window before it asks.  The ledger route
        answers with finished columns either way (it learns from them
        before it returns).
        """
        engine = self.engine
        # Same engine guards as serve_wire_bytes: a write-through
        # store must not be bypassed, and an engine without the
        # columnar entry declines cleanly.
        if getattr(engine, "apply_columnar", None) is None or getattr(
            engine, "store", None
        ) is not None:
            return None
        # The fast fronts must never answer peer-owned keys locally —
        # clustered deployments route those through the full
        # listener's forward path.
        if not self.all_locally_owned(dec):
            return None
        self._offer_hotkeys(dec)
        if self.ledger is not None:
            return self._serve_decoded_ledger(dec)
        from gubernator_tpu.core.engine import PackedKeys

        packed = PackedKeys(dec.key_buf, dec.key_offsets, dec.n)
        kw = {}
        if hasattr(engine, "tables"):
            kw["route_hashes"] = dec.fnv1a
        if want_async:
            kw["want_async"] = True
        out = engine.apply_columnar(
            packed, dec.algo, dec.behavior, dec.hits, dec.limit,
            dec.duration, dec.burst, **kw,
        )
        return out.start_readback() if want_async else out

    def _serve_decoded_ledger(self, dec):
        """Ledger-aware columnar serve for the native fronts: hot-key
        rows (sticky over-limit, live lease credit) answer without any
        device work — for a fully hot window the engine is never
        dispatched at all, which is the fronts' whole point on a
        dispatch-bound backend."""
        from gubernator_tpu.core.engine import PackedKeys

        engine = self.engine
        plan = self.ledger.plan(dec, engine.clock.now_ms())
        if plan.full:
            return plan.dense_cols()
        lane = plan.build_engine_lane()
        packed = PackedKeys(lane.key_buf, lane.key_offsets, lane.n)
        try:
            if hasattr(engine, "tables"):
                out = engine.apply_columnar(
                    packed, lane.algo, lane.behavior, lane.hits,
                    lane.limit, lane.duration, lane.burst,
                    route_hashes=lane.fnv1a,
                )
            else:
                out = engine.apply_columnar(
                    packed, lane.algo, lane.behavior, lane.hits,
                    lane.limit, lane.duration, lane.burst,
                )
        except Exception:
            plan.rollback()
            raise
        st, lim, rem, rst = out
        plan.learn(st, lim, rem, rst)
        if not plan.answered_rows and lane is dec:
            return out
        return plan.merge_outputs(st, rem, rst)

    def _encode(self, encoder, *columns) -> bytes:
        """The columnar routes' response encode (net/wire_codec)."""
        with stage("wire.encode", self.stage_timers["wire.encode"]):
            return encoder(*columns)

    def serve_wire_bytes(
        self, raw: bytes, *, check_ownership: bool = True
    ) -> Optional[bytes]:
        """Serve one GetRateLimitsReq/GetPeerRateLimitsReq payload
        entirely through native code + the engine's columnar path:
        C wire decode → packed key schedule → device step → C wire
        encode.  Returns response bytes, or None to decline (codec
        unavailable, slow-path batch, store attached, peer-owned keys)
        — the caller then takes the protobuf path.  No per-item Python
        objects anywhere (PERF.md: the pb path costs ~3.2ms per
        1000-item batch)."""
        engine = self.engine
        if getattr(engine, "apply_columnar", None) is None or getattr(
            engine, "store", None
        ) is not None:
            return None
        from gubernator_tpu.net import wire_codec

        if wire_codec.load() is None:
            return None
        # Decode with GLOBAL/SKETCH allowed: all-GLOBAL and all-SKETCH
        # batches have their own columnar routes below; mixed batches
        # decline to the pb path.
        with stage("wire.decode", self.stage_timers["wire.decode"]):
            dec = wire_codec.decode_reqs(
                bytes(raw), MAX_BATCH_SIZE,
                COLUMNAR_DISQUALIFIERS & ~_GLOBAL_I & ~_SKETCH_I,
            )
        if dec is None:
            return None
        s_mask = (dec.behavior & _SKETCH_I) != 0
        if s_mask.any():
            if not s_mask.all():
                return None  # mixed batch → pb path partitions it
            # (MULTI_REGION+SKETCH can't reach here: the decode mask
            # still disqualifies MULTI_REGION → pb path replicates.)
            # Approximate limiter straight off the decoded hashes — no
            # key materialization, no engine dispatch.
            st, lim, rem, rst = self._apply_sketch(
                None, dec.hits, dec.limit,
                self.engine.clock.now_ms(), key_hashes=dec.fnv1a,
            )
            self.counters["columnar"] += dec.n
            if self.hotkeys is not None:
                self.hotkeys.offer_columns(
                    dec.key_buf, dec.key_offsets, dec.hits,
                    hashes=dec.fnv1a,
                )
            return self._encode(wire_codec.encode_resps, st, lim, rem, rst)
        g_mask = (dec.behavior & _GLOBAL_I) != 0
        if g_mask.any():
            if not g_mask.all():
                return None
            return self._serve_wire_global(dec, check_ownership)
        if check_ownership:
            owned = self._owned_mask(dec)
            if owned is not None and not bool(owned.all()):
                if not owned.any():
                    # Entirely peer-owned: a flash-crowd hot-key batch
                    # may answer from replica leases without touching
                    # the pb path at all.
                    return self._serve_wire_replicated(dec)
                return None  # mixed ownership → pb path partitions it
            self.counters["local"] += dec.n
        self.counters["columnar"] += dec.n
        self._offer_hotkeys(dec)

        if self.ledger is not None:
            return self._serve_columnar_ledger(dec)

        from gubernator_tpu.core.engine import PackedKeys

        if self._wire_window is not None:
            out = self._wire_window.submit(dec)
            if out is None:
                return None
            st, lim, rem, rst = out
            return self._encode(wire_codec.encode_resps, st, lim, rem, rst)
        packed = PackedKeys(dec.key_buf, dec.key_offsets, dec.n)
        t_serve = time.monotonic()
        if hasattr(engine, "tables"):  # sharded: codec hashes route shards
            st, lim, rem, rst = engine.apply_columnar(
                packed, dec.algo, dec.behavior, dec.hits, dec.limit,
                dec.duration, dec.burst, route_hashes=dec.fnv1a,
            )
        else:
            st, lim, rem, rst = engine.apply_columnar(
                packed, dec.algo, dec.behavior, dec.hits, dec.limit,
                dec.duration, dec.burst,
            )
        self.stage_timers["engine_serve"].observe(
            time.monotonic() - t_serve
        )
        return self._encode(wire_codec.encode_resps, st, lim, rem, rst)

    def _serve_columnar_ledger(self, dec) -> Optional[bytes]:
        """The local columnar route through the decision ledger: rows
        the ledger can answer exactly (sticky over-limit, live lease
        credit) skip the device entirely; the rest — with any settle
        rows prepended — ride the usual group-commit window / direct
        apply, and the engine's responses teach the ledger (lease
        grants, over-limit inserts)."""
        from gubernator_tpu.net import wire_codec

        engine = self.engine
        plan = self.ledger.plan(dec, engine.clock.now_ms())
        if plan.full:
            st, lim, rem, rst = plan.dense_cols()
            return self._encode(wire_codec.encode_resps, st, lim, rem, rst)
        lane = plan.build_engine_lane()
        out = self._dispatch_lane(lane)
        if out is None:
            plan.rollback()
            return None
        st, lim, rem, rst = out
        plan.learn(st, lim, rem, rst)
        if not plan.answered_rows and lane is dec:
            return self._encode(wire_codec.encode_resps, st, lim, rem, rst)
        return self._encode(
            wire_codec.encode_resps, *plan.merge_outputs(st, rem, rst)
        )

    def _dispatch_lane(self, lane):
        """Run one engine-lane column set through the group-commit
        window (preferred) or a direct columnar apply; returns the
        (status, limit, remaining, reset) columns or None on failure
        (callers roll the ledger back and fall to the pb path)."""
        from gubernator_tpu.core.engine import PackedKeys

        engine = self.engine
        if self._wire_window is not None:
            out = self._wire_window.submit(lane)
            if out is not None:
                return out
        packed = PackedKeys(lane.key_buf, lane.key_offsets, lane.n)
        t_serve = time.monotonic()
        try:
            if hasattr(engine, "tables"):
                return engine.apply_columnar(
                    packed, lane.algo, lane.behavior, lane.hits,
                    lane.limit, lane.duration, lane.burst,
                    route_hashes=lane.fnv1a,
                )
            return engine.apply_columnar(
                packed, lane.algo, lane.behavior, lane.hits, lane.limit,
                lane.duration, lane.burst,
            )
        except Exception:  # noqa: BLE001 — callers fall back to pb
            from gubernator_tpu.utils.metrics import record_swallowed

            record_swallowed("service.ledger_lane")
            log.exception("ledger engine-lane apply failed")
            return None
        finally:
            self.stage_timers["engine_serve"].observe(
                time.monotonic() - t_serve
            )

    def _serve_wire_global(
        self, dec, check_ownership: bool
    ) -> Optional[bytes]:
        """Columnar GLOBAL route (the cluster tier's hot path): owned
        items run the engine + queue a broadcast chunk; non-owned items
        queue a hits chunk and answer from the status cache (misses run
        locally, eventually consistent) — all with O(batch) numpy and
        zero per-item dataclasses.  Mirrors the pb partitioning at
        _get_rate_limits step 3 (reference: gubernator.go:426-466)."""
        from gubernator_tpu.core.engine import PackedKeys
        from gubernator_tpu.net import wire_codec

        engine = self.engine
        now_ms = engine.clock.now_ms()
        n = dec.n
        if check_ownership:
            with self._peer_lock:
                picker = self.local_picker
            n_peers = picker.size()
            single_addr = None
            if n_peers == 0:
                owned = np.ones(n, dtype=bool)
                owner_objs = None
            elif n_peers == 1:
                me = picker.peers()[0]
                owned = np.full(n, bool(me.info.is_owner))
                owner_objs = None
                single_addr = me.info.grpc_address
            else:
                owner_objs = picker.get_batch_dual_hashed(
                    dec.fnv1, dec.fnv1a
                )
                owned = np.fromiter(
                    (o.info.is_owner for o in owner_objs), bool, n
                )
        else:
            # Peer-forwarded batch: we are the owner of every item.
            owned = np.ones(n, dtype=bool)
            owner_objs = None
            single_addr = None
        owned_idx = np.nonzero(owned)[0]
        non_idx = np.nonzero(~owned)[0]

        status = np.zeros(n, dtype=np.int32)
        limit = np.asarray(dec.limit).copy()
        remaining = np.zeros(n, dtype=np.int64)
        reset = np.zeros(n, dtype=np.int64)
        owner_meta_idx = np.full(n, -1, dtype=np.int32)
        owner_strs: List[bytes] = []

        # Owner-side ledger: sticky over-limit and leased hot keys
        # answer without joining the merged engine apply (the answered
        # columns still ride the broadcast below — the ledger's view IS
        # the authoritative serve-time status).
        led_plan = None
        owned_eng = owned_idx
        if len(owned_idx) and self.ledger is not None:
            led_plan = self.ledger.plan(dec, now_ms, idx=owned_idx)
            aidx = led_plan.answered_idx
            if len(aidx):
                a_st, a_rem, a_rst = led_plan.answered_cols()
                status[aidx] = a_st
                remaining[aidx] = a_rem
                reset[aidx] = a_rst
            owned_eng = led_plan.fall_idx
        eng_parts = [owned_eng] if len(owned_eng) else []
        if len(non_idx):
            self.counters["global"] += len(non_idx)
            self.global_mgr.queue_hits_chunk(dec, non_idx)
            raw_keys = dec.key_buf.tobytes()
            off = dec.key_offsets
            keys = [raw_keys[off[i]:off[i + 1]] for i in non_idx.tolist()]
            hit, c_st, c_lim, c_rem, c_rst = self.global_cache.get_columns(
                keys, now_ms
            )
            hidx = non_idx[hit]
            midx = non_idx[~hit]
            status[hidx] = c_st[hit]
            limit[hidx] = c_lim[hit]
            remaining[hidx] = c_rem[hit]
            reset[hidx] = c_rst[hit]
            if len(midx):
                self.counters["global_miss_local"] += len(midx)
                eng_parts.append(midx)
            # Every non-owned response echoes its owner address
            # (reference: gubernator.go:448-452).
            addr_index: Dict[str, int] = {}
            for i in non_idx.tolist():
                addr = (
                    single_addr if owner_objs is None
                    else owner_objs[i].info.grpc_address
                )
                k = addr_index.get(addr)
                if k is None:
                    k = len(owner_strs)
                    addr_index[addr] = k
                    owner_strs.append(addr.encode())
                owner_meta_idx[i] = k
        if len(owned_idx):
            self.counters["local"] += len(owned_idx)

        if eng_parts:
            eng_idx = (
                eng_parts[0] if len(eng_parts) == 1
                else np.sort(np.concatenate(eng_parts))
            )
            sub_buf, sub_off = _slice_key_columns(
                dec.key_buf, dec.key_offsets, eng_idx
            )
            cols = tuple(
                np.ascontiguousarray(np.asarray(a)[eng_idx])
                for a in (dec.algo, dec.behavior, dec.hits, dec.limit,
                          dec.duration, dec.burst)
            )
            sub = _SubBatch()
            sub.n = len(eng_idx)
            sub.key_buf = sub_buf
            sub.key_offsets = sub_off
            (sub.algo, sub.behavior, sub.hits, sub.limit,
             sub.duration, sub.burst) = cols
            sub.fnv1a = np.ascontiguousarray(dec.fnv1a[eng_idx])
            n_settles = 0
            n_acq = 0
            n_eng = len(eng_idx)
            if led_plan is not None and (
                led_plan.n_settles or led_plan.n_acquires
            ):
                # Revoked leases return their credit IN this dispatch,
                # ahead of the rows that broke their preconditions;
                # lease acquisitions ride the tail.
                from gubernator_tpu.core.ledger import concat_lanes

                n_settles = led_plan.n_settles
                n_acq = led_plan.n_acquires
                pre = led_plan.settle_lane()
                if pre is not None:
                    sub = concat_lanes(pre, sub)
                post = led_plan.acq_lane()
                if post is not None:
                    sub = concat_lanes(sub, post)
            packed = PackedKeys(sub.key_buf, sub.key_offsets, sub.n)
            out = None
            if self._global_window is not None:
                # The window observes engine_serve itself — once per
                # merged dispatch, not once per grouped RPC.
                out = self._global_window.submit(sub)
            if out is not None:
                st, lim, rem, rst = out
            else:
                t_serve = time.monotonic()
                try:
                    if hasattr(engine, "tables"):
                        st, lim, rem, rst = engine.apply_columnar(
                            packed, sub.algo, sub.behavior, sub.hits,
                            sub.limit, sub.duration, sub.burst,
                            now_ms=now_ms, route_hashes=sub.fnv1a,
                        )
                    else:
                        st, lim, rem, rst = engine.apply_columnar(
                            packed, sub.algo, sub.behavior, sub.hits,
                            sub.limit, sub.duration, sub.burst,
                            now_ms=now_ms,
                        )
                except Exception:
                    # The lane never applied: restore consumed credits
                    # and re-queue the pulled return rows, or the
                    # revoked leases' unused credit would stay debited
                    # on the device forever.
                    if led_plan is not None:
                        led_plan.rollback()
                    raise
                finally:
                    self.stage_timers["engine_serve"].observe(
                        time.monotonic() - t_serve
                    )
            if led_plan is not None and (
                len(owned_eng) or n_settles or n_acq
            ):
                # Engine outputs for the return rows + the owned
                # fall-through rows + the acquisition rows teach the
                # ledger (reconciliation, over-limit inserts, lease
                # grants) — learn expects them in [settles..., fall...,
                # acquires...] lane order.
                pos = np.searchsorted(eng_idx, owned_eng) + n_settles
                lidx = np.concatenate(
                    [
                        np.arange(n_settles, dtype=np.int64),
                        pos,
                        np.arange(n_acq, dtype=np.int64)
                        + n_settles + n_eng,
                    ]
                )
                led_plan.learn(st[lidx], lim[lidx], rem[lidx], rst[lidx])
            if n_settles or n_acq:
                sl = slice(n_settles, n_settles + n_eng)
                st, lim, rem, rst = st[sl], lim[sl], rem[sl], rst[sl]
            status[eng_idx] = st
            limit[eng_idx] = lim
            remaining[eng_idx] = rem
            reset[eng_idx] = rst

        # Stamp the apply order as close to the apply as possible
        # (see GlobalManager.next_update_seq).
        apply_seq = (
            self.global_mgr.next_update_seq() if len(owned_idx) else 0
        )
        if len(owned_idx):
            # Owner-side GLOBAL items queue the broadcast (reference:
            # gubernator.go:621-654 via apply_local_batch) — WITH the
            # decision columns just computed: the broadcast window
            # pushes these captured statuses instead of re-reading the
            # engine (the re-read was one extra engine dispatch per
            # window plus a per-key Python materialization pass; the
            # owner's serve IS the authoritative read).  apply_seq
            # orders the capture by engine-apply completion so a
            # racing slower thread cannot broadcast a superseded
            # status last.
            self.global_mgr.queue_updates_chunk(
                dec, owned_idx, status[owned_idx], limit[owned_idx],
                remaining[owned_idx], reset[owned_idx],
                seq=apply_seq,
            )
        self.counters["columnar"] += n
        self._offer_hotkeys(dec)
        if owner_strs:
            return self._encode(
                wire_codec.encode_resps_owner,
                status, limit, remaining, reset, owner_meta_idx, owner_strs,
            )
        return self._encode(
            wire_codec.encode_resps, status, limit, remaining, reset
        )

    def apply_columnar_local(
        self,
        keys_str: List[str],
        keys_bytes: List[bytes],
        algo,
        behavior,
        hits,
        limit,
        duration,
        burst,
        *,
        check_ownership: bool = True,
    ):
        """Run an all-local batch through the engine's columnar path.

        Returns (status, limit, remaining, reset_time) numpy columns in
        request order, or None to decline (engine can't take columns, a
        write-through Store is attached, or some key is peer-owned) —
        the caller then falls back to the dataclass path.  The caller
        guarantees the batch has no GLOBAL / MULTI_REGION /
        DURATION_IS_GREGORIAN items and no invalid fields.
        """
        engine = self.engine
        apply_columnar = getattr(engine, "apply_columnar", None)
        if apply_columnar is None or getattr(engine, "store", None) is not None:
            return None
        if check_ownership:
            with self._peer_lock:
                picker = self.local_picker
            n_peers = picker.size()
            if n_peers == 1:
                # Single-node: the lone member is us iff marked owner.
                if not picker.peers()[0].info.is_owner:
                    return None
            elif n_peers > 1:
                owners = picker.get_batch(keys_str)
                if not all(o.info.is_owner for o in owners):
                    return None
            # Only the client-facing path counts as "local" traffic;
            # the dataclass peer path never bumps it either.
            self.counters["local"] += len(keys_bytes)
        self.counters["columnar"] += len(keys_bytes)
        if self.ledger is not None:
            # pb-decoded columns carry no fnv1a hashes, so this path
            # cannot consult the ledger — keep it coherent instead.
            self.ledger.invalidate_keys(keys_bytes)
        out = apply_columnar(
            keys_bytes, algo, behavior, hits, limit, duration, burst
        )
        aw = self.admission_watch
        if out is not None and aw.active and check_ownership:
            # Client-facing columnar answers only: the peer-side call
            # (check_ownership=False) serves batches a remote
            # client-facing node already counts from its responses.
            aw.observe_columns(keys_str, hits, out)
        return out

    def get_peer_batch(self, keys: Sequence[str]) -> List:
        """Owner clients for a key list — ONE lock + one vectorized
        ring pass (the GLOBAL hit windows look up every queued key)."""
        with self._peer_lock:
            if self.local_picker.size() == 0:
                return [None] * len(keys)
            return self.local_picker.get_batch(list(keys))

    def get_peer_batch_hashed(self, fnv1, fnv1a) -> Optional[List]:
        """Owner clients from precomputed key hashes (the columnar hit
        windows never materialize keys).  None when the picker is
        empty — callers fall back to local handling."""
        with self._peer_lock:
            picker = self.local_picker
            if picker.size() == 0:
                return None
            return picker.get_batch_dual_hashed(fnv1, fnv1a)

    def get_peer_rate_limits(
        self, requests: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """Owner side of a forwarded batch — answered authoritatively,
        never re-forwarded.

        reference: gubernator.go:493-559.  The reference fans items over
        a worker pool with an order-restoring collector; here the whole
        batch is one engine call, order preserved by construction.
        """
        from gubernator_tpu.utils.tracing import span

        if len(requests) > MAX_BATCH_SIZE:
            self.counters["check_errors"] += 1
            raise ServiceError(
                f"'PeerRequest.rate_limits' list too large; max size is '{MAX_BATCH_SIZE}'"
            )
        with span("service.get_peer_rate_limits", batch=len(requests)):
            return self.apply_local_batch(list(requests))

    def update_peer_globals(self, globals_: Sequence[UpdatePeerGlobal]) -> None:
        """Owner-broadcast GLOBAL statuses land in the host status cache.

        reference: gubernator.go:470-490.
        """
        self.global_cache.put_many(
            (g.key, g.status, g.algorithm)
            for g in globals_
            if g.status is not None
        )

    def update_peer_globals_columns(self, dec) -> None:
        """Columnar variant (raw wire path — net/server.py)."""
        self.global_cache.put_columns(dec)

    def receive_transfer(self, raw: bytes) -> int:
        """Ownership-handoff receiver (PeersV1/TransferBuckets):
        restore one shipped window of bucket rows into the local
        engine; returns rows applied (cluster/handoff.py documents
        the protocol and its over-admission bound)."""
        from gubernator_tpu.cluster.handoff import receive_transfer

        return receive_transfer(self, raw)

    def receive_replication(self, raw: bytes) -> bytes:
        """Hot-key replication receiver (PeersV1/ReplicateKeys): install
        or revoke replica credit leases granted by a key's owner;
        returns the JSON response bytes carrying superseded leases'
        (consumed, unused) for the owner's reconciliation
        (cluster/replication.py documents the protocol and its
        N_replicas × lease over-admission bound)."""
        repl = self.replication
        if repl is None:
            # No replication plane on this node: the owner reads this
            # as a failed grant and returns the credit immediately.
            return b'{"disabled":true,"returns":[]}'
        return repl.receive(raw)

    def obs_snapshot_raw(self) -> bytes:
        """Fleet rollup scrape receiver (PeersV1/ObsSnapshot): this
        node's metric families as raw JSON (obs/fleet.py documents
        the schema and merge semantics).  A node without the obs
        plane answers its disabled shape so the collector can count
        it instead of erroring."""
        obs = self.obs
        if obs is None:
            return b'{"v":1,"disabled":true}'
        return obs.local_snapshot_raw()

    def health_check(self) -> HealthCheckResp:
        """Aggregate recent peer errors. reference: gubernator.go:562-619."""
        errs: List[str] = []
        with self._peer_lock:
            local_peers = self.local_picker.peers()
            region_peers = self.region_picker.peers()
        for p in local_peers:
            for e in p.last_errs():
                errs.append(f"Error returned from local peer.GetLastErr: {e}")
        for p in region_peers:
            for e in p.last_errs():
                errs.append(f"Error returned from region peer.GetLastErr: {e}")
        resp = HealthCheckResp(
            status=HEALTHY, peer_count=len(local_peers) + len(region_peers)
        )
        if errs:
            resp.status = UNHEALTHY
            resp.message = "|".join(errs)
        return resp

    # ------------------------------------------------------------------
    # Local execution

    def apply_local_batch(
        self, reqs: List[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        """Run a batch on the local engine, handling behavior queues.

        reference: gubernator.go:621-654 (getRateLimit): GLOBAL items
        queue an owner broadcast, MULTI_REGION items queue region hits,
        then the algorithm runs (here: one vectorized engine call).
        """
        g_items = [r for r in reqs if int(r.behavior) & _GLOBAL_I]
        if g_items:
            self.global_mgr.queue_updates_many(g_items)
        mr_idx = [
            i for i, r in enumerate(reqs)
            if int(r.behavior) & _MULTI_REGION_I
        ]
        if mr_idx:
            self.multi_region_mgr.queue_hits_many(
                reqs[i] for i in mr_idx
            )
        if self.ledger is not None:
            # This batch runs on the engine outside the ledger: settle
            # and drop any ledger entry for its keys first, so the
            # engine computes on the sequential state (O(1) dict probe
            # per key; almost always a miss).
            self.ledger.invalidate_keys(
                [r.hash_key().encode() for r in reqs]
            )
        resps = self.engine.get_rate_limits(reqs, now_ms=now_ms)
        if mr_idx:
            # Honest degradation hints ("When Two is Worse Than One"):
            # while a remote region's aggregate circuit is OPEN, this
            # answer's cross-region convergence is deferred behind the
            # requeue backlog — flag it so callers can tell a
            # federated answer from a partition-local one.  The drift
            # stays bounded: each region admits at most `limit` from
            # local state, ≤ N_regions × limit cluster-wide
            # (RESILIENCE.md §12).
            open_regions = self.multi_region_mgr.open_regions()
            if open_regions:
                self.counters["degraded_region_answers"] += len(mr_idx)
                joined = ",".join(open_regions)
                for i in mr_idx:
                    resp = resps[i]
                    md = dict(resp.metadata) if resp.metadata else {}
                    md["degraded_region"] = "true"
                    md["degraded_regions"] = joined
                    resp.metadata = md
        return resps

    # ------------------------------------------------------------------
    # Peer management (reference: gubernator.go:657-765)

    def set_peers(self, peer_infos: Sequence[PeerInfo]) -> None:
        """Rebuild pickers from a fresh peer list, reusing existing
        clients and draining dropped ones.

        reference: gubernator.go:657-740 (SetPeers).
        """
        with self._peer_lock:
            # Snapshot INSIDE the lock: two concurrent set_peers calls
            # (discovery push racing a manual static update) must not
            # both build from the same superseded ring and silently
            # drop the other's peers on publish.
            local_picker = self.local_picker.new()
            region_picker = self.region_picker.new()
            creds = self.conf.peer_credentials
            # Our own advertise address (the is_owner entry): stamped
            # on every client as the fault injector's src key.
            me_addr = next(
                (p.grpc_address for p in peer_infos if p.is_owner), ""
            )
            local_members: List[PeerClient] = []
            for info in peer_infos:
                # Strict DC match, like the reference — a node with
                # datacenter="" treats only ""-DC peers as local
                # (reference: gubernator.go:661-676).
                if info.datacenter != self.conf.data_center:
                    existing = self.region_picker.get_by_peer_info(info)
                    peer = existing or PeerClient(
                        info,
                        self.conf.behaviors,
                        credentials=creds,
                        flush_stat=self.flush_duration,
                    )
                    peer.info = info
                    peer.src_addr = me_addr
                    region_picker.add(peer)
                else:
                    existing = self.local_picker.get_by_peer_info(info)
                    peer = existing or PeerClient(
                        info,
                        self.conf.behaviors,
                        credentials=creds,
                        flush_stat=self.flush_duration,
                    )
                    peer.info = info
                    peer.src_addr = me_addr
                    local_members.append(peer)
            local_picker.add_all(local_members)  # one ring rebuild

            old_local = self.local_picker
            old_region = self.region_picker
            self.local_picker = local_picker
            self.region_picker = region_picker

        # Drain peers that fell out of the pool (in the background, like
        # the reference's goroutine at gubernator.go:719-731).
        keep = {p.info.grpc_address for p in local_picker.peers()}
        keep |= {p.info.grpc_address for p in region_picker.peers()}
        dropped = [
            p
            for p in (old_local.peers() + old_region.peers())
            if p.info.grpc_address not in keep
        ]
        for p in dropped:
            # guberlint: ok thread — bounded one-shot drain mirroring
            # the reference's goroutine (gubernator.go:719-731);
            # peer.shutdown() has an internal flush timeout, and the
            # peer object is unreachable afterwards.
            threading.Thread(target=p.shutdown, daemon=True).start()

    def get_peer(self, key: str) -> PeerClient:
        """Owner of one key. reference: gubernator.go:743-765."""
        with self._peer_lock:
            return self.local_picker.get(key)

    def get_peer_list(self) -> List[PeerClient]:
        with self._peer_lock:
            return self.local_picker.peers()

    def get_region_pickers(self):
        with self._peer_lock:
            return self.region_picker.pickers()

    # ------------------------------------------------------------------

    def close(self) -> None:
        """reference: gubernator.go:159-192 (Close)."""
        if self._closed:
            return
        self._closed = True
        if self.ledger is not None:
            self.ledger.close()
        self.global_mgr.close()
        self.multi_region_mgr.close()
        self._forward_pool.shutdown(wait=True)
        with self._peer_lock:
            peers = self.local_picker.peers() + self.region_picker.peers()
        for p in peers:
            p.shutdown(timeout=1.0)
        self.engine.close()
