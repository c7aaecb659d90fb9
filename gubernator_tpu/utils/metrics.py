"""Prometheus metrics for the daemon's /metrics endpoint.

Mirrors the reference's metric catalog (reference: prometheus.md:17-36;
series defined across gubernator.go:59-113, lrucache.go:48-59,
global.go:41-57, grpc_stats.go:41-131).  Counters are kept as plain
ints on the hot-path objects (engine/service/managers) — zero
contention on the decision path — and exported through one custom
Collector at scrape time, which also serves as the test oracle
(SURVEY.md §4.2: metrics-as-oracle tests).

This file is the metric REGISTRY guberlint's drift pass anchors on:
every ``*MetricFamily`` name constructed here must appear in the
README catalog (or PERF/RESILIENCE/STATIC_ANALYSIS/OBSERVABILITY), and
every documented ``gubernator_*`` series must still be constructed
here — registering a metric without documenting it fails CI.
"""

from __future__ import annotations

import os
import threading
import time
from math import frexp as _frexp
from typing import TYPE_CHECKING, Iterable, Sequence

from prometheus_client.core import (
    CounterMetricFamily,
    GaugeMetricFamily,
    HistogramMetricFamily,
    SummaryMetricFamily,
)
from prometheus_client.registry import Collector, CollectorRegistry
from prometheus_client.samples import Exemplar

from gubernator_tpu.utils import tracing

if TYPE_CHECKING:
    from gubernator_tpu.service import V1Instance

_OFF_VALUES = ("0", "false", "no", "off")


_exemplars_enabled = None


def exemplars_enabled() -> bool:
    """GUBER_METRICS_EXEMPLARS (default on): retain the last sampled
    trace_id per histogram bucket and export it as an OpenMetrics
    exemplar — the metrics→traces link.  Costs nothing while tracing
    is disabled (the tracing.active() check short-circuits first).
    Parsed once and cached: DurationStat.observe runs at wire-batch
    rate and must not pay an environment read + string normalization
    per observation (every other knob reads once at construction)."""
    global _exemplars_enabled
    if _exemplars_enabled is None:
        _exemplars_enabled = os.environ.get(
            "GUBER_METRICS_EXEMPLARS", "1"
        ).strip().lower() not in _OFF_VALUES
    return _exemplars_enabled


# Swallowed-exception visibility (guberlint thread pass): background
# threads that catch-and-continue MUST count the swallow here so a
# failing loop is a metric spike, not silence.  Module-level because
# the swallow sites span discovery/cluster/core objects with no shared
# instance.
_swallowed_lock = threading.Lock()
_swallowed: dict = {}  # guberlint: guarded-by _swallowed_lock


def record_swallowed(site: str) -> None:
    """Count one swallowed exception for the
    ``gubernator_swallowed_exceptions{site=...}`` counter."""
    with _swallowed_lock:
        _swallowed[site] = _swallowed.get(site, 0) + 1


def swallowed_counts() -> dict:
    with _swallowed_lock:
        return dict(_swallowed)


class DurationStat:
    """Duration summary (count + sum + max seconds) PLUS a streaming
    fixed-bucket histogram for real quantiles — a mean-only stat let
    call sites advertise a "p50 budget" while reporting means, which
    hides exactly the tail the flight recorder exists to attribute.
    Buckets are log2-spaced from 1µs: bucket i covers
    [2^i µs, 2^(i+1) µs), 36 buckets reaching ~19h, so one observe is
    a frexp + an increment.  Observations happen on flush/round
    boundaries (ms-scale work), so a tiny lock is fine; the
    per-decision hot path never touches one."""

    __slots__ = ("count", "total", "max", "buckets", "exemplars", "_lock")

    N_BUCKETS = 36
    _BASE = 1e-6  # bucket 0 lower bound: 1µs

    # guberlint: guard count, total, max, buckets, exemplars by _lock

    def __init__(self) -> None:
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.buckets = [0] * self.N_BUCKETS
        # bucket index -> (trace_id, seconds): the LAST sampled trace
        # that landed in the bucket (bounded by N_BUCKETS entries by
        # construction; populated only while tracing is live AND
        # GUBER_METRICS_EXEMPLARS is on) — what turns a cluster p99
        # bucket into a link to a flight-recorder trace.
        self.exemplars: dict = {}
        self._lock = threading.Lock()

    @classmethod
    def bucket_of(cls, seconds: float) -> int:
        if seconds <= cls._BASE:
            return 0
        # frexp is exact and ~3x cheaper than log2 here: for
        # m * 2^e with m in [0.5, 1), floor(log2(x)) == e - 1.
        _m, e = _frexp(seconds / cls._BASE)
        return min(cls.N_BUCKETS - 1, max(0, e - 1))

    @classmethod
    def bucket_bounds(cls, i: int) -> tuple:
        return (cls._BASE * (1 << i), cls._BASE * (1 << (i + 1)))

    def observe(self, seconds: float) -> None:
        b = self.bucket_of(seconds)
        ex = None
        # Exemplar capture: observations happen at flush/window
        # boundaries (see class docstring), so the context lookup is
        # off the per-decision path; a disabled tracer short-circuits
        # at one global check.
        if exemplars_enabled():
            if tracing.active():
                ctx = tracing.current_context()
                if ctx is not None and ctx.sampled:
                    ex = (ctx.trace_id, seconds)
        with self._lock:
            self.count += 1
            self.total += seconds
            if seconds > self.max:
                self.max = seconds
            self.buckets[b] += 1
            if ex is not None:
                self.exemplars[b] = ex

    def observe_bucket_counts(self, counts, total=None, top=None) -> None:
        """Merge pre-bucketed counts (index-aligned with N_BUCKETS) —
        the native event collector drains per-stage C histograms this
        way, one lock per drain instead of one per event.  A caller
        that still holds the raw durations passes their exact `total`
        and `top` (seconds); without them both are read off the
        buckets, to an octave."""
        n = 0
        mid_total = mid_top = 0.0
        for i, c in enumerate(counts):
            if c:
                n += c
                lo, hi = self.bucket_bounds(i)
                mid_total += c * (lo + hi) / 2.0
                mid_top = (lo * hi) ** 0.5
        if not n:
            return
        if total is None:
            total = mid_total
        if top is None:
            top = mid_top
        with self._lock:
            self.count += int(n)
            self.total += total
            # Max at bucket resolution (the geometric midpoint of the
            # highest occupied bucket) — pre-bucketed merges lose the
            # exact extremum by construction.
            if top > self.max:
                self.max = top
            for i, c in enumerate(counts):
                if c:
                    self.buckets[i] += int(c)

    def bucket_snapshot(self) -> dict:
        """One consistent {count, total, max, buckets} view — the
        wire shape of the fleet rollup (obs/fleet.py): a peer ships
        this and the collector merges it exactly."""
        with self._lock:
            return {
                "count": self.count,
                "total": self.total,
                "max": self.max,
                "buckets": list(self.buckets),
            }

    def merge_snapshot(self, snap: dict) -> None:
        """EXACT merge of another DurationStat's bucket_snapshot():
        counts/totals/max add, buckets add index-aligned — unlike
        observe_bucket_counts there is no midpoint approximation, so
        a fleet-merged mean is the true cluster mean and the merged
        quantiles are real histogram quantiles, not means-of-means."""
        buckets = snap.get("buckets") or []
        with self._lock:
            self.count += int(snap.get("count", 0))
            self.total += float(snap.get("total", 0.0))
            m = float(snap.get("max", 0.0))
            if m > self.max:
                self.max = m
            for i, c in enumerate(buckets[: self.N_BUCKETS]):
                if c:
                    self.buckets[i] += int(c)

    def exemplar_snapshot(self) -> dict:
        """{bucket index: (trace_id, seconds)} of live exemplars.
        Exemplars whose trace the in-memory tracer has fully evicted
        are pruned HERE (from the snapshot and the retained table):
        a metrics→trace link must never point at a trace that no
        longer exists."""
        with self._lock:
            out = dict(self.exemplars)
        if not out:
            return out
        has = getattr(tracing.current_tracer(), "has_trace", None)
        if has is None:
            return out
        for b, (tid, _v) in list(out.items()):
            if not has(tid):
                del out[b]
                with self._lock:
                    cur = self.exemplars.get(b)
                    if cur is not None and cur[0] == tid:
                        del self.exemplars[b]
        return out

    def mean(self) -> float:
        # Under the lock so count/total come from the same observation
        # (a torn pair between two observes skews the scrape).
        with self._lock:
            return self.total / self.count if self.count else 0.0

    def quantile(self, q: float) -> float:
        """Streaming quantile from the histogram (geometric bucket
        midpoint; resolution is a factor of 2 — honest for budget
        attribution, not for micro-benchmarks)."""
        with self._lock:
            n = self.count
            if not n:
                return 0.0
            rank = q * (n - 1)
            seen = 0
            for i, c in enumerate(self.buckets):
                seen += c
                if seen > rank:
                    lo, hi = self.bucket_bounds(i)
                    return (lo * hi) ** 0.5
            return self.max

    def p50(self) -> float:
        return self.quantile(0.50)

    def p99(self) -> float:
        return self.quantile(0.99)

    def snapshot_ms(self, digits: int = 3) -> dict:
        """The canonical {count, mean_ms, p50_ms, p99_ms, max_ms}
        rendering — shared by stage_budget(), /debug/vars, and the
        bench artifacts so the shape cannot drift between them."""
        with self._lock:
            count = self.count
            mean_s = self.total / count if count else 0.0
            max_s = self.max
        # The quantiles take the lock themselves; an observation
        # landing between the reads skews one scrape by one event.
        return {
            "count": count,
            "mean_ms": round(mean_s * 1e3, digits),
            "p50_ms": round(self.p50() * 1e3, digits),
            "p99_ms": round(self.p99() * 1e3, digits),
            "max_ms": round(max_s * 1e3, digits),
        }


# The served path's stage vocabulary (OBSERVABILITY.md §3, PERF.md §3):
# one name per site for the histogram, the request span and the
# profiler annotation.  Both engines create exactly these stats
# (`engine_stages`); the service registers them in `stage_timers`, so
# /metrics, /debug/vars and obs/fleet.py carry them without further
# code.  `device.step` / `device.readback` / `device.window_wait`
# predate the primitive and live on the objects that own them.
ENGINE_STAGES = (
    "engine.lock_wait",
    "engine.lock_hold",
    "engine.intern",
    "engine.pack",
    "device.h2d",
    "device.launch",
    "engine.set_expiry",
    "engine.evict_clear",
    "engine.unpack",
    "engine.sweep",
)


def engine_stages(extra: Sequence[str] = ()) -> dict:
    """A fresh {stage name: DurationStat} for one engine."""
    return {name: DurationStat() for name in ENGINE_STAGES + tuple(extra)}


_annotation_cls = None  # jax.profiler.TraceAnnotation, False when jax is absent


def _trace_annotation(name: str):
    """A TraceMe for the profiler's host plane, or None where jax is
    absent (the jax-free smoke stubs).  Inert unless a profile is being
    captured; then it lands in the same xplane as the device ops, on
    the profiler's one timeline."""
    global _annotation_cls
    if _annotation_cls is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation_cls = TraceAnnotation
        except ImportError:
            _annotation_cls = False
    return _annotation_cls(name) if _annotation_cls else None


class stage:
    """One measured site of the served path, three sinks under one
    name: it always observes `stat` (two clock reads and one observe);
    while the tracer is active it is a child span of the RPC's tree
    (utils/tracing.py, the flight recorder's feed); and when `work`
    is true the body runs under a `jax.profiler.TraceAnnotation`.

    Two rules keep the profiler's timeline attributable (a gap
    between device ops is named by the host event that overlaps it
    most): annotate LEAF stages only — never one `work` stage inside
    another on a thread — and pass `work=False` for a WAIT (lock wait,
    window wait, a blocking readback): a waiting thread would claim
    every gap that another thread's work caused.

    A context manager; `start()` / `stop()` serve the one shape a
    `with` cannot: an interval that ends inside the block that follows
    it (waiting for a lock that a `with` then holds)."""

    __slots__ = ("_name", "_stat", "_work", "_cm", "span", "_ann", "_t0")

    def __init__(self, name: str, stat: DurationStat, work: bool = True):
        self._name = name
        self._stat = stat
        self._work = work
        self._cm = None
        self.span = None  # the open request span, for attributes
        self._ann = None

    def start(self) -> "stage":
        # The clock first and last: what the instrumentation itself
        # costs belongs to the stage it instruments, not to nobody
        # (the leaf stages under the engine lock tile engine.lock_hold).
        self._t0 = time.monotonic()
        if tracing.active():
            self._cm = tracing.span(self._name)
            self.span = self._cm.__enter__()
        if self._work:
            self._ann = _trace_annotation(self._name)
            if self._ann is not None:
                self._ann.__enter__()
        return self

    def stop(self, exc_type=None, exc=None, tb=None) -> None:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        # Observed inside the span: the histogram's exemplar links the
        # bucket to this RPC's trace.
        self._stat.observe(time.monotonic() - self._t0)
        if self._cm is not None:
            self._cm.__exit__(exc_type, exc, tb)

    __enter__ = start

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.stop(exc_type, exc, tb)
        return False


class InstanceCollector(Collector):
    """Exports engine + service + manager counters.

    reference: V1Instance itself implements prometheus.Collector
    (gubernator.go:780-809).
    """

    def __init__(self, instance: "V1Instance"):
        self.instance = instance

    def collect(self) -> Iterable:
        inst = self.instance
        eng = inst.engine

        c = CounterMetricFamily(
            "gubernator_check_counter",
            "The number of rate limits checked.",
        )
        c.add_metric([], eng.requests_total)
        yield c

        c = CounterMetricFamily(
            "gubernator_over_limit_counter",
            "The number of rate limit checks that are over the limit.",
        )
        c.add_metric([], eng.over_limit_total)
        yield c

        c = CounterMetricFamily(
            "gubernator_check_error_counter",
            "The number of errors while checking rate limits.",
        )
        c.add_metric([], inst.counters["check_errors"])
        yield c

        c = CounterMetricFamily(
            "gubernator_getratelimit_counter",
            "The count of getRateLimit() calls by calltype.",
            labels=["calltype"],
        )
        c.add_metric(["local"], inst.counters["local"])
        c.add_metric(["forward"], inst.counters["forward"])
        c.add_metric(["global"], inst.counters["global"])
        c.add_metric(["sketch"], inst.counters.get("sketch", 0))
        c.add_metric(
            ["replicated"], inst.counters.get("replicated_local", 0)
        )
        yield c

        c = CounterMetricFamily(
            "gubernator_global_miss_local",
            "GLOBAL items served by a LOCAL eventually-consistent copy "
            "(status-cache miss on a non-owner) — the source of "
            "GLOBAL's bounded over-admission (<= n_nodes * limit per "
            "broadcast lag window).",
        )
        c.add_metric([], inst.counters.get("global_miss_local", 0))
        yield c

        c = CounterMetricFamily(
            "gubernator_asyncrequest_retries",
            "The count of retries in the forward path.",
        )
        c.add_metric([], inst.counters["async_retries"])
        yield c

        # ---- peer health plane (cluster/health.py; RESILIENCE.md) ----
        c = CounterMetricFamily(
            "gubernator_degraded_answers",
            "Requests answered by THIS node's engine because every "
            "owner candidate was circuit-open/unreachable "
            "(GUBER_DEGRADED_LOCAL).  Availability bought with bounded "
            "over-admission: <= N_partitions * limit per key.",
        )
        c.add_metric([], inst.counters.get("degraded_answers", 0))
        yield c

        c = CounterMetricFamily(
            "gubernator_backoff_retries",
            "Forward retries that waited out a capped-exponential "
            "backoff window before re-picking an owner.",
        )
        c.add_metric([], inst.counters.get("backoff_retries", 0))
        yield c

        g = GaugeMetricFamily(
            "gubernator_peer_state",
            "Per-peer circuit state (1 on the current state's series): "
            "healthy | suspect | broken | half-open.",
            labels=["peer", "state"],
        )
        transitions = CounterMetricFamily(
            "gubernator_circuit_transitions",
            "Circuit state transitions per peer, by to-state.",
            labels=["peer", "to"],
        )
        for peer in inst.get_peer_list():
            try:
                if peer.info.is_owner:
                    # The self-peer is never dialed; every other
                    # health surface (Daemon.peer_health, harness
                    # health_states) filters it, and the scrape must
                    # agree with them.
                    continue
                addr = peer.info.grpc_address
                g.add_metric([addr, peer.health.state()], 1)
                for to, n in sorted(peer.health.transition_counts().items()):
                    transitions.add_metric([addr, to], n)
            except Exception:  # noqa: BLE001 — peer mid-shutdown
                record_swallowed("metrics.peer_health_scrape")
                continue
        yield g
        yield transitions

        # ---- elastic membership (cluster/membership.py; RESILIENCE
        # §10): epoch counter, handoff row traffic, dual-window time.
        mem = getattr(inst, "membership", None)
        if mem is not None:
            g = GaugeMetricFamily(
                "gubernator_membership_epoch",
                "This node's membership epoch (bumps on every observed "
                "view change; equal across nodes once a transition "
                "settles).",
            )
            g.add_metric([], mem.epoch())
            yield g
            g = GaugeMetricFamily(
                "gubernator_membership_dual",
                "1 while a dual-ring cutover window is open (old + new "
                "rings both valid), else 0.",
            )
            g.add_metric([], 1 if mem.phase() == "dual" else 0)
            yield g
            c = CounterMetricFamily(
                "gubernator_ring_dual_window_seconds",
                "Cumulative seconds this node has spent inside "
                "dual-ring cutover windows.",
            )
            c.add_metric([], mem.dual_seconds())
            yield c
        hoff = getattr(inst, "handoff_counters", None)
        if hoff is not None:
            c = CounterMetricFamily(
                "gubernator_handoff_keys",
                "Ownership-handoff bucket rows by event: shipped to a "
                "new owner, forfeited at the epoch deadline (bounded "
                "over-admission, RESILIENCE.md §10), or received and "
                "restored here.",
                labels=["event"],
            )
            c.add_metric(["shipped"], hoff["shipped"])
            c.add_metric(["forfeited"], hoff["forfeited"])
            c.add_metric(["received"], hoff["received"])
            yield c

        # ---- hot-key replication plane (cluster/replication.py;
        # RESILIENCE.md §11): promotion/demotion lifecycle, grant
        # traffic, replica-answered decisions, and credit accounting
        # under the N_replicas × lease bound.
        repl = getattr(inst, "replication", None)
        if repl is not None:
            rs = repl.stats()
            g = GaugeMetricFamily(
                "gubernator_replication_keys",
                "Live hot-key replication state by role: promoted = "
                "keys THIS node (as owner) currently replicates; "
                "replica_leases = remote credit leases held here.",
                labels=["role"],
            )
            g.add_metric(["promoted"], rs["promoted_keys"])
            g.add_metric(["replica_leases"], rs["replica_leases"])
            yield g
            c = CounterMetricFamily(
                "gubernator_replication_events",
                "Hot-key replication lifecycle events by kind "
                "(promoted | demoted | grants_sent | grants_failed | "
                "grants_received | revokes_received | stale_dropped | "
                "expired).",
                labels=["event"],
            )
            for ev_name in (
                "promoted", "demoted", "grants_sent", "grants_failed",
                "grants_received", "revokes_received", "stale_dropped",
                "expired",
            ):
                c.add_metric([ev_name], rs[ev_name])
            yield c
            c = CounterMetricFamily(
                "gubernator_replication_answered",
                "Peer-owned decisions answered locally from a replica "
                "credit lease (the forward hops replication removed; "
                "natively answered drains fold in at pull time).",
            )
            c.add_metric([], rs["answered"])
            yield c
            c = CounterMetricFamily(
                "gubernator_replication_credit",
                "Replication credit flow in hits, by event: granted "
                "(pre-debited onto replica leases), returned (unused "
                "credit settled back), forfeited (lost to unreachable "
                "replicas — bounded by N_replicas × lease per window).",
                labels=["event"],
            )
            c.add_metric(["granted"], rs["credit_granted"])
            c.add_metric(["returned"], rs["credit_returned"])
            c.add_metric(["forfeited"], rs["credit_forfeited"])
            yield c

        c = CounterMetricFamily(
            "gubernator_hits_requeue",
            "GLOBAL hit-window re-queue traffic toward unreachable "
            "owners, by event (requeued | dropped at the age cap).",
            labels=["event"],
        )
        c.add_metric(["requeued"], inst.global_mgr.hits_requeued)
        c.add_metric(["dropped"], inst.global_mgr.hits_requeue_dropped)
        yield c

        c = CounterMetricFamily(
            "gubernator_broadcasts_skipped",
            "Per-peer broadcast pushes skipped, by reason: "
            "circuit_open (the peer is broken) or inflight (its "
            "previous push outlived the fan-out deadline — slow but "
            "healthy).  Supersedable traffic; the peer catches up "
            "from later windows.",
            labels=["reason"],
        )
        c.add_metric(["circuit_open"], inst.global_mgr.broadcasts_skipped)
        c.add_metric(
            ["inflight"], inst.global_mgr.broadcasts_skipped_inflight
        )
        yield c

        g = GaugeMetricFamily(
            "gubernator_cache_size",
            "The number of bucket slots currently interned.",
        )
        g.add_metric([], eng.cache_size())
        yield g

        # reference: lrucache.go:148-159 — a full cache evicts its
        # oldest item; evictions of items that had not expired are
        # the operator's sign of an undersized cache.
        tables = getattr(eng, "tables", None) or [eng.table]
        c = CounterMetricFamily(
            "gubernator_evictions_count",
            "Keys evicted from a full cache, least recently used first.",
        )
        c.add_metric([], sum(t.evictions for t in tables))
        yield c
        c = CounterMetricFamily(
            "gubernator_unexpired_evictions_count",
            "Count the number of cache items which were evicted while "
            "unexpired.",
        )
        c.add_metric([], sum(t.unexpired_evictions for t in tables))
        yield c
        c = CounterMetricFamily(
            "gubernator_loaded_rows_count",
            "Rows restored through the Loader (engine.load; stage "
            "engine.load has the time).",
        )
        c.add_metric([], eng.rows_loaded_total)
        yield c

        c = CounterMetricFamily(
            "gubernator_global_async_sends",
            "The count of GLOBAL async hit windows flushed to owners.",
        )
        c.add_metric([], inst.global_mgr.async_sends)
        yield c

        c = CounterMetricFamily(
            "gubernator_global_broadcasts",
            "The count of GLOBAL broadcast windows pushed to peers.",
        )
        c.add_metric([], inst.global_mgr.broadcasts)
        yield c

        # ---- multi-region federation (cluster/multiregion.py;
        # RESILIENCE.md §12): window/push traffic, per-region circuit
        # state, requeue-and-converge accounting, degraded answers.
        mrs = inst.multi_region_mgr.stats()
        c = CounterMetricFamily(
            "gubernator_multiregion_windows",
            "Cross-region hit windows flushed (each window fans out "
            "to every remote region under the fan-out barrier).",
        )
        c.add_metric([], mrs["windows"])
        yield c
        c = CounterMetricFamily(
            "gubernator_multiregion_region_sends",
            "Successful per-region delta pushes, by remote region.",
            labels=["region"],
        )
        for region, n in sorted(mrs["region_sends_by"].items()):
            c.add_metric([region], n)
        yield c
        c = CounterMetricFamily(
            "gubernator_multiregion_hits_requeued",
            "Cross-region deltas re-queued toward an unreachable "
            "region (bounded, age-capped, delivered after heal).",
        )
        c.add_metric([], mrs["hits_requeued"])
        yield c
        c = CounterMetricFamily(
            "gubernator_multiregion_hits_dropped",
            "Cross-region deltas dropped at the requeue age/key cap "
            "or toward a departed region — counted, never silent; the "
            "drift bound covers what they would have reconciled.",
        )
        c.add_metric([], mrs["hits_dropped"])
        yield c
        g = GaugeMetricFamily(
            "gubernator_multiregion_region_state",
            "Aggregate circuit state per remote region (1 on the "
            "current state's series): healthy | degraded | open.",
            labels=["region", "state"],
        )
        for region, st in sorted(mrs["region_states"].items()):
            g.add_metric([region, st], 1)
        yield g
        c = CounterMetricFamily(
            "gubernator_multiregion_degraded_answers",
            "MULTI_REGION answers served while a remote region's "
            "circuit was open (metadata.degraded_region=true; "
            "over-admission bounded at N_regions x limit per window).",
        )
        c.add_metric([], inst.counters.get("degraded_region_answers", 0))
        yield c

        c = CounterMetricFamily(
            "gubernator_engine_batches",
            "Engine batches applied (device step groups).",
        )
        c.add_metric([], eng.batches_total)
        yield c

        c = CounterMetricFamily(
            "gubernator_engine_rounds",
            "Device kernel rounds executed (≥1 per batch; >1 when a "
            "batch repeats keys).",
        )
        c.add_metric([], eng.rounds_total)
        yield c

        # Paged device state (GUBER_PAGED; core/paging.py, PERF.md
        # §30).  Absent on dense engines — the scrape stays drift-free
        # both ways because the whole family is gated on the plane.
        paging = getattr(eng, "paging", None)
        if paging is not None:
            g = GaugeMetricFamily(
                "gubernator_paged_pages_resident",
                "Device frames resident (pages the clock hand ranks); "
                "total pages = ceil(logical capacity / page size).",
            )
            g.add_metric([], paging.frames)
            yield g

            c = CounterMetricFamily(
                "gubernator_paged_faults",
                "Page faults: batches touching a non-resident key "
                "paid a spill+refill before their round dispatched.",
            )
            c.add_metric([], paging.faults)
            yield c

            c = CounterMetricFamily(
                "gubernator_paged_spills",
                "Cold pages spilled to the host store (one d2h gather "
                "of the page's raw words each).",
            )
            c.add_metric([], paging.spills)
            yield c

            s = SummaryMetricFamily(
                "gubernator_paged_refill_wait",
                "Seconds a faulting batch waited for its page refill "
                "scatter (h2d + donated update).",
                count_value=paging.refill_wait.count,
                sum_value=paging.refill_wait.total,
            )
            yield s

        # Queue-depth gauges (reference: guber_queue_length /
        # guber_pool_queue_length, gubernator.go:70-84).
        g = GaugeMetricFamily(
            "gubernator_queue_length",
            "Per-peer batch queue depth (requests awaiting a flush).",
            labels=["peer"],
        )
        for peer in inst.get_peer_list():
            try:
                g.add_metric([peer.info.grpc_address], peer.queue_length())
            except Exception:  # noqa: BLE001 — peer mid-shutdown
                record_swallowed("metrics.peer_queue_scrape")
                continue
        yield g

        g = GaugeMetricFamily(
            "gubernator_global_queue_length",
            "GLOBAL manager queue depths by queue.",
            labels=["queue"],
        )
        g.add_metric(["hits"], inst.global_mgr._hits.pending())
        g.add_metric(["broadcasts"], inst.global_mgr._updates.pending())
        yield g

        # Backlog age: seconds the oldest queued item has waited.  A
        # healthy batcher stays near sync_wait; sustained growth means
        # the flush pipeline cannot drain the enqueue rate (the GLOBAL
        # tail mechanism — PERF.md §15).
        g = GaugeMetricFamily(
            "gubernator_global_backlog_age_seconds",
            "Age of the oldest queued GLOBAL item by queue.",
            labels=["queue"],
        )
        g.add_metric(["hits"], inst.global_mgr._hits.backlog_age())
        g.add_metric(["broadcasts"], inst.global_mgr._updates.backlog_age())
        yield g

        c = CounterMetricFamily(
            "gubernator_global_dropped",
            "GLOBAL queue items shed under overload (supersedable "
            "broadcasts only; hits block instead of dropping).",
            labels=["queue"],
        )
        c.add_metric(["hits"], inst.global_mgr._hits.dropped)
        c.add_metric(["broadcasts"], inst.global_mgr._updates.dropped)
        yield c

        # Batch-duration summaries (reference: guber_batch_send_duration
        # gubernator.go:100-106; guber_async_durations /
        # guber_broadcast_durations global.go:41-57;
        # guber_grpc_request_duration analog for engine rounds).
        s = SummaryMetricFamily(
            "gubernator_batch_send_duration",
            "Seconds spent flushing peer request batches.",
            count_value=inst.flush_duration.count,
            sum_value=inst.flush_duration.total,
        )
        yield s

        s = SummaryMetricFamily(
            "gubernator_global_send_duration",
            "Seconds spent sending GLOBAL hit windows to owners.",
            count_value=inst.global_mgr.hits_duration.count,
            sum_value=inst.global_mgr.hits_duration.total,
        )
        yield s

        s = SummaryMetricFamily(
            "gubernator_broadcast_duration",
            "Seconds spent broadcasting GLOBAL statuses to peers.",
            count_value=inst.global_mgr.broadcast_duration.count,
            sum_value=inst.global_mgr.broadcast_duration.total,
        )
        yield s

        # The latency budget, stage by stage: the cluster tier's
        # (client window wait, engine serve, hit-window wait, owner
        # RPC, broadcast enqueue→delivered age) and the served wire
        # route's (OBSERVABILITY.md §3: listener.queue_wait,
        # wire.decode/encode, engine.*, device.h2d/launch/readback,
        # mesh.route).  device.step is the host's ENQUEUE wall of one
        # dispatch (= device.h2d + device.launch), not device time.
        s = SummaryMetricFamily(
            "gubernator_stage_duration",
            "Seconds per pipeline stage (host wall; device.step is "
            "the enqueue of one dispatch, not device time).",
            labels=["stage"],
        )
        for stage, stat in inst.stage_timers.items():
            s.add_metric([stage], count_value=stat.count, sum_value=stat.total)
        yield s

        # Streaming stage quantiles (DurationStat's fixed-bucket
        # histogram): the p50/p99 the budget tables used to fake with
        # means.  One series per (stage, quantile); native stages (the
        # event-ring histograms) join under a native_ prefix.
        g = GaugeMetricFamily(
            "gubernator_stage_quantile_seconds",
            "Streaming per-stage latency quantiles (log2-bucket "
            "histogram; resolution one octave).  Stages: the pipeline "
            "stage timers plus the event-ring stages under their own "
            "names (native_serve / window_wait / window_serve).",
            labels=["stage", "quantile"],
        )
        quantile_stats = dict(inst.stage_timers)
        ev = getattr(inst, "native_events", None)
        if ev is not None:
            # The collector's stage names (native_serve / window_wait /
            # window_serve) are already distinct from the stage-timer
            # keys and must match gubernator_native_events' labels —
            # joins on the stage label depend on it.
            quantile_stats.update(ev.histograms())
        for stage, stat in quantile_stats.items():
            g.add_metric([stage, "0.5"], stat.p50())
            g.add_metric([stage, "0.99"], stat.p99())
        yield g

        # The RAW per-stage histograms behind the quantile gauge: a
        # cross-node scraper (obs/fleet.py) needs the bucket counts
        # to MERGE histograms
        # into real cluster quantiles — averaging per-node p99s is
        # the means-of-means lie the rollup exists to retire.  Tail
        # buckets carry OpenMetrics exemplars (last sampled trace_id)
        # when tracing is live, so a p99 bucket links straight to a
        # flight-recorder trace (classic exposition drops them;
        # /metrics?exemplars=1 serves the OpenMetrics rendering).
        h = HistogramMetricFamily(
            "gubernator_stage_seconds",
            "Per-stage latency histogram (36 log2 buckets from 1µs; "
            "the raw counts behind gubernator_stage_quantile_seconds, "
            "mergeable across nodes into real cluster quantiles).",
            labels=["stage"],
        )
        for stage, stat in quantile_stats.items():
            snap = stat.bucket_snapshot()
            exs = stat.exemplar_snapshot()
            cum = 0
            buckets = []
            for i, c in enumerate(snap["buckets"]):
                cum += c
                _lo, hi = DurationStat.bucket_bounds(i)
                ex = exs.get(i)
                if ex is not None:
                    buckets.append(
                        (
                            f"{hi:.9g}", float(cum),
                            Exemplar({"trace_id": ex[0]}, float(ex[1])),
                        )
                    )
                else:
                    buckets.append((f"{hi:.9g}", float(cum)))
            buckets.append(("+Inf", float(snap["count"])))
            h.add_metric([stage], buckets, sum_value=snap["total"])
        yield h

        # SLO watchdog gauges (obs/slo.py, attached by the daemon):
        # the continuously-evaluated burn rates of the declared SLIs
        # and the live admission-bound headroom — RESILIENCE.md's
        # N×limit proofs as a gauge instead of a bench-only assert.
        wd = getattr(inst, "slo_watchdog", None)
        if wd is not None:
            snap = wd.metrics_snapshot()
            g = GaugeMetricFamily(
                "gubernator_slo_burn_rate",
                "Error-budget burn rate per declared SLI and window "
                "(>1 = burning budget faster than the SLO allows; "
                "multi-window multi-burn-rate alerting, obs/slo.py).",
                labels=["sli", "window"],
            )
            for (sli, window), v in sorted(snap["burn"].items()):
                g.add_metric([sli, window], v)
            yield g
            g = GaugeMetricFamily(
                "gubernator_invariant_headroom",
                "Per watched finite-limit key: derived admission "
                "bound minus observed admitted hits in the current "
                "window (negative = a RESILIENCE.md invariant was "
                "violated; the bound label names the derivation).",
                labels=["key", "bound"],
            )
            for (key, bound), v in sorted(snap["headroom"].items()):
                g.add_metric([key, bound], v)
            yield g

        # Native event ring (core/native/event_ring.cpp, drained by
        # utils/native_events.py): per-stage C-front latency events and
        # the ring's overflow drops — the first per-decision visibility
        # inside the native plane.
        if ev is not None:
            c = CounterMetricFamily(
                "gubernator_native_events",
                "Event-ring records drained from the C front, by "
                "stage (native_serve | window_wait | window_serve).",
                labels=["stage"],
            )
            for stage, n in sorted(ev.event_counts().items()):
                c.add_metric([stage], n)
            yield c
            rs = ev.ring_stats()
            c = CounterMetricFamily(
                "gubernator_native_ring_dropped",
                "Event-ring writes dropped because the ring was full "
                "(the C front never blocks on observability).",
            )
            c.add_metric([], rs.get("dropped", 0))
            yield c
            s = SummaryMetricFamily(
                "gubernator_native_stage_duration",
                "Seconds per native-front stage, from the event ring.",
                labels=["stage"],
            )
            for stage, stat in ev.histograms().items():
                s.add_metric(
                    [stage], count_value=stat.count, sum_value=stat.total
                )
            yield s

        # Connection plane of the native h2 front (h2_server.cpp):
        # open connections and the idle reaper's cumulative kills —
        # the C100K surface the event front exists for (PERF.md §26).
        front = getattr(inst, "h2_front", None)
        if front is not None:
            cs = front.conn_stats()
            g = GaugeMetricFamily(
                "gubernator_h2_conns",
                "Native h2 front connections by state: open = currently "
                "held fds; idle_reaped = cumulative idle-timeout kills "
                "(GUBER_H2_IDLE_TIMEOUT; GOAWAY + close).",
                labels=["state"],
            )
            g.add_metric(["open"], float(cs["conns_open"]))
            g.add_metric(["idle_reaped"], float(cs["conns_idle_reaped"]))
            yield g

        # Hot-key attribution (utils/hotkeys.py space-saving sketch):
        # the top-K decision keys by estimated hit count, so load and
        # the p99 tail can be attributed to specific keys
        # (/debug/hotkeys serves the same table with error bounds).
        hk = getattr(inst, "hotkeys", None)
        if hk is not None:
            g = GaugeMetricFamily(
                "gubernator_hotkeys",
                "Estimated hits for the top-K decision keys "
                "(space-saving sketch; over-estimate bounded by the "
                "reported error).",
                labels=["key"],
            )
            for key, count, _err in hk.top(10):
                g.add_metric(
                    [key.decode(errors="replace")], float(count)
                )
            yield g
            # Which table serves the sketch: the native one (one call
            # an RPC, interpreter lock released) or the Python fallback
            # — a per-key loop on every RPC's thread.
            g = GaugeMetricFamily(
                "gubernator_hotkeys_native",
                "1 when the hot-key sketch's table is the native one "
                "(core/native/hotkeys.cpp), 0 when the Python fallback "
                "serves.",
            )
            g.add_metric([], float(hk.tier == "native"))
            yield g

        # Decision-ledger counters (core/ledger.py): decisions answered
        # on the host without a device dispatch, rows that fell through
        # to the engine, lease lifecycle, and settle traffic.
        led = getattr(inst, "ledger", None)
        if led is not None:
            c = CounterMetricFamily(
                "gubernator_ledger_answered",
                "Decisions answered by the host decision ledger "
                "(sticky over-limit + lease credit) with zero device "
                "work.",
            )
            c.add_metric([], led.answered)
            yield c
            c = CounterMetricFamily(
                "gubernator_ledger_fallthrough",
                "Ledger-considered rows that fell through to the "
                "engine.",
            )
            c.add_metric([], led.fallthrough)
            yield c
            c = CounterMetricFamily(
                "gubernator_ledger_leases",
                "Lease lifecycle events by kind.",
                labels=["event"],
            )
            c.add_metric(["granted"], led.leases_granted)
            c.add_metric(["revoked"], led.leases_revoked)
            yield c
            c = CounterMetricFamily(
                "gubernator_ledger_settles",
                "Settle rows applied back to the device (consumed "
                "lease credits reconciled).",
            )
            c.add_metric([], led.settles)
            yield c
            s = SummaryMetricFamily(
                "gubernator_ledger_settle_lag",
                "Seconds from lease revocation to the settle apply.",
                count_value=led.settle_lag.count,
                sum_value=led.settle_lag.total,
            )
            yield s
        # One dp_stats round trip per scrape — the value feeds both the
        # counter and the dispatches-per-decision denominator.
        native_answered = led.native_answered() if led else 0
        if led is not None:
            c = CounterMetricFamily(
                "gubernator_ledger_native_answered",
                "Decisions answered by the native decision plane "
                "(C-resident ledger fast path: zero GIL, zero Python "
                "frames, zero device work).",
            )
            c.add_metric([], native_answered)
            yield c
        # Device dispatches per decision: the number the ledger exists
        # to push below 1 on hot-key traffic.  Decisions = engine rows
        # + ledger answers (Python AND native); dispatches = engine
        # kernel rounds.
        decisions = eng.requests_total + (
            led.answered + native_answered if led else 0
        )
        g = GaugeMetricFamily(
            "gubernator_dispatches_per_decision",
            "Engine kernel rounds per rate-limit decision "
            "(cumulative ratio).",
        )
        g.add_metric([], eng.rounds_total / decisions if decisions else 0.0)
        yield g

        # Window-size gauges: what the adaptive batching windows are
        # actually waiting right now (0 when idle, the configured cap
        # under sustained fill).
        g = GaugeMetricFamily(
            "gubernator_adaptive_window_seconds",
            "Current load-adaptive batching window by queue.",
            labels=["queue"],
        )
        g.add_metric(["hits"], inst.global_mgr._hits.current_wait())
        g.add_metric(["broadcasts"], inst.global_mgr._updates.current_wait())
        if inst._wire_window is not None:
            g.add_metric(["wire_window"], inst._wire_window.next_wait())
        if inst._global_window is not None:
            g.add_metric(["global_serve"], inst._global_window.next_wait())
        yield g

        # Swallowed exceptions by site: background threads that catch
        # and continue count here (guberlint thread pass) — a failing
        # loop shows as a rate spike instead of silence.
        c = CounterMetricFamily(
            "gubernator_swallowed_exceptions",
            "Exceptions swallowed by catch-and-continue sites, by site.",
            labels=["site"],
        )
        for site, n in sorted(swallowed_counts().items()):
            c.add_metric([site], n)
        yield c

        # XLA backend compiles observed at runtime (utils/jit_guard).
        # Flat after warmup in a healthy steady-state server; growth
        # means an unpinned shape/dtype reached a jit program in the
        # serve path (the trace pass + recompile-guard soak).
        from gubernator_tpu.utils import jit_guard

        c = CounterMetricFamily(
            "gubernator_jit_recompiles",
            "XLA backend compiles observed since process start "
            "(0 when the jax monitoring hook is unavailable).",
        )
        c.add_metric([], jit_guard.compile_count())
        yield c


class FleetRollupCollector(Collector):
    """Exports ONE merged fleet rollup (obs/fleet.FleetCollector
    .collect()) as gubernator_fleet_* families — served by any node
    at /metrics?fleet=1 so a single scrape answers for the cluster:
    counters SUM, gauges label-join by peer/region, and stage
    histograms merge via the 36-bucket path so the fleet p50/p99 are
    real quantiles.  Registered into a throwaway registry per scrape
    (the rollup is a point-in-time fan-out, not node state)."""

    def __init__(self, rollup: dict):
        self.rollup = rollup

    def collect(self) -> Iterable:
        r = self.rollup
        regions = r.get("regions") or {}
        g = GaugeMetricFamily(
            "gubernator_fleet_nodes",
            "Nodes merged into this fleet rollup, by region.",
            labels=["region"],
        )
        for region, sub in sorted(regions.items()):
            g.add_metric([region or "default"], sub.get("nodes", 0))
        yield g
        c = CounterMetricFamily(
            "gubernator_fleet_counter",
            "Fleet-summed node counters by name and region (the "
            "per-region subtotals come from the nodes' DC tags; the "
            "cluster total is the sum over regions).",
            labels=["counter", "region"],
        )
        for region, sub in sorted(regions.items()):
            for name, v in sorted((sub.get("counters") or {}).items()):
                c.add_metric([name, region or "default"], v)
        yield c
        g = GaugeMetricFamily(
            "gubernator_fleet_gauge",
            "Per-node gauges label-joined by peer and region (gauges "
            "do not sum — cache sizes and queue depths are per-node "
            "facts).",
            labels=["gauge", "peer", "region"],
        )
        for name, by_peer in sorted((r.get("gauges") or {}).items()):
            for peer, (region, v) in sorted(by_peer.items()):
                g.add_metric([name, peer, region or "default"], v)
        yield g
        g = GaugeMetricFamily(
            "gubernator_fleet_stage_quantile_seconds",
            "REAL cluster-wide per-stage quantiles from histogram "
            "merge (DurationStat.merge_snapshot over every node's "
            "36-bucket histogram) — not means of per-node quantiles.",
            labels=["stage", "quantile"],
        )
        for stage, q in sorted((r.get("quantiles") or {}).items()):
            g.add_metric([stage, "0.5"], q.get("p50_ms", 0.0) / 1e3)
            g.add_metric([stage, "0.99"], q.get("p99_ms", 0.0) / 1e3)
        yield g
        scrape = r.get("scrape") or {}
        g = GaugeMetricFamily(
            "gubernator_fleet_scrape",
            "The rollup fan-out's own health, by outcome: peers that "
            "answered (ok), failed inside the budget (failed), or "
            "were skipped because their circuit was open (skipped).",
            labels=["outcome"],
        )
        for outcome in ("ok", "failed", "skipped"):
            g.add_metric([outcome], scrape.get(outcome, 0))
        yield g


def build_fleet_registry(rollup: dict) -> CollectorRegistry:
    """Throwaway registry for one /metrics?fleet=1 scrape."""
    reg = CollectorRegistry()
    reg.register(FleetRollupCollector(rollup))
    return reg


def build_registry(
    instance: "V1Instance", metric_flags: Sequence[str] = ()
) -> CollectorRegistry:
    """Fresh registry per daemon (reference: daemon.go:85-99).

    `metric_flags` mirrors GUBER_METRIC_FLAGS (reference:
    flags.go:19-57, daemon.go:251-263): "os" adds the process
    CPU/RSS/fd collector; "python" adds the GC + platform collectors
    (the Go-runtime collector analog); "all" adds both."""
    reg = CollectorRegistry()
    reg.register(InstanceCollector(instance))
    flags = {f.strip().lower() for f in metric_flags if f.strip()}
    if flags & {"os", "all"}:
        from prometheus_client import ProcessCollector

        ProcessCollector(registry=reg)
    if flags & {"python", "golang", "all"}:
        from prometheus_client import GCCollector, PlatformCollector

        GCCollector(registry=reg)
        PlatformCollector(registry=reg)
    return reg
