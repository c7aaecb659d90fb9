"""Configuration structs + env-driven loading (GUBER_* surface).

reference: config.go — BehaviorConfig (:44-65, defaults :113-123),
library Config (:68-110), DaemonConfig (:169-229), env loading
SetupDaemonConfig (:247-451) with optional KEY=VALUE config file
(fromEnvFile :556-584).
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

# ----------------------------------------------------------------------
# Durations are float seconds host-side; the wire/kernels use int ms.

MICROSECOND = 1e-6
MILLISECOND = 1e-3


@dataclass
class BehaviorConfig:
    """Batching / GLOBAL / multi-region knobs.

    reference: config.go:44-65; defaults config.go:113-123 (500µs wait,
    500ms timeout, 1000-item limit for each tier).
    """

    # Peer-forward batching (reference: peer_client.go:380-453).
    batch_timeout: float = 0.5
    batch_wait: float = 500 * MICROSECOND
    batch_limit: int = 1000

    # GLOBAL manager (reference: global.go).
    global_timeout: float = 0.5
    global_sync_wait: float = 500 * MICROSECOND
    global_batch_limit: int = 1000

    # Multi-region manager (reference: multiregion.go; grown into the
    # federation plane of RESILIENCE.md §12).
    multi_region_timeout: float = 0.5
    multi_region_sync_wait: float = 500 * MICROSECOND
    multi_region_batch_limit: int = 1000
    # Total wall budget for one cross-region fan-out barrier, seconds
    # (GUBER_MULTI_REGION_FANOUT_DEADLINE): one slow/dead region must
    # not stall a flush window past this, whatever the per-RPC
    # timeout is.
    multi_region_fanout_deadline: float = 2.0
    # Cross-region deltas that failed to reach a region are re-queued
    # (bound to that region) until this old, seconds; older deltas
    # drop COUNTED (gubernator_multiregion_hits_dropped) — the healed
    # region's buckets have moved on and replaying stale deltas would
    # double-count (GUBER_MULTI_REGION_REQUEUE_AGE; 0 disables
    # re-queueing, restoring the pre-§12 fire-and-forget drop — but
    # still counted).
    multi_region_requeue_age: float = 10.0
    # Per-region retry backoff between failed push rounds — capped
    # exponential with FULL jitter (cluster/health.backoff_delay;
    # GUBER_MULTI_REGION_BACKOFF / _CAP).  Rides the batcher's
    # deferred re-admission, so an open region circuit cannot spin a
    # flush worker.
    multi_region_backoff: float = 0.05
    multi_region_backoff_cap: float = 2.0

    # Load-adaptive batching windows (GUBER_ADAPTIVE_WINDOWS, default
    # on): every *_wait above becomes a CAP — idle batchers flush
    # immediately and the wait grows toward the cap only while batches
    # actually fill (cluster/batch_loop.AdaptiveWait; VERDICT r5 weak
    # #2's stacked-window fix).  Off restores fixed waits (tests that
    # drive syncs manually; operators who want the exact reference
    # cadence).
    adaptive_windows: bool = True

    # ---- peer health plane (cluster/health.py; RESILIENCE.md) -------
    # Consecutive transport failures before a peer's circuit opens
    # (GUBER_CIRCUIT_FAILURES).
    circuit_failures: int = 3
    # Initial circuit open period, seconds; doubles per consecutive
    # re-open up to the cap (GUBER_CIRCUIT_BACKOFF / _CAP).
    circuit_backoff: float = 0.5
    circuit_backoff_cap: float = 30.0
    # Forward retry-loop backoff between owner re-pick attempts —
    # capped exponential with full jitter (GUBER_FORWARD_BACKOFF /
    # _CAP).  The reference's loop re-picked with zero delay.
    forward_backoff: float = 0.01
    forward_backoff_cap: float = 0.25
    # Degraded-mode local answering (GUBER_DEGRADED_LOCAL, default
    # on): when every owner candidate is circuit-open/unreachable,
    # answer from this node's own engine (flagged in response
    # metadata) instead of returning an error string.  Off restores
    # the reference's fail-closed semantics.  Availability costs
    # bounded over-admission: ≤ N_partitions × limit per key
    # (RESILIENCE.md derives the bound).
    degraded_local: bool = True
    # Total wall budget for one GLOBAL fan-out barrier, seconds
    # (GUBER_GLOBAL_FANOUT_DEADLINE): one dead peer must not stall a
    # flush cycle past this, whatever the per-RPC timeout is.
    global_fanout_deadline: float = 2.0
    # GLOBAL hits that failed to reach their owner are re-queued for
    # the next window until this old, seconds; older hits are dropped
    # (counted) — the owner's state has moved on and replaying stale
    # hits would double-count against fresh windows
    # (GUBER_HIT_REQUEUE_AGE; 0 disables re-queueing).
    hit_requeue_age: float = 5.0


@dataclass
class Config:
    """Library-level config for a service instance.

    reference: config.go:68-110 (Config struct); defaults
    SetDefaults config.go:112-147.
    """

    behaviors: BehaviorConfig = field(default_factory=BehaviorConfig)
    # Total bucket slots on this node (reference default 50k cache size,
    # config.go:294; here: slots across the device mesh).
    cache_size: int = 50_000
    # Consistent-hash function for the cluster ring ("fnv1" | "fnv1a").
    # reference: config.go:395-417
    hash_algorithm: str = "fnv1"
    # Picker type: "replicated-hash" (default) | "consistent-hash"
    # (GUBER_PEER_PICKER; reference: config.go:395-417).
    peer_picker: str = "replicated-hash"
    # Virtual ring points per peer for replicated-hash
    # (GUBER_REPLICATED_HASH_REPLICAS; reference default 512).
    picker_replicas: int = 512
    # This node's datacenter (MULTI_REGION routing).
    data_center: str = ""
    # Local peer identity; set by the daemon once listeners are bound.
    instance_id: str = ""
    # grpc.ChannelCredentials for dialing peers (None = plaintext);
    # set by the daemon when TLS is configured.
    peer_credentials: Optional[object] = None
    # Group-commit window for client-facing wire batches (seconds);
    # 0 disables.  Concurrent RPCs inside the window share ONE engine
    # dispatch — the local-tier analog of the peer BatchWait
    # (net/wire_window.py; SURVEY §7.1's batching front-end).
    local_batch_wait: float = 0.0
    # Group-commit cap for the GLOBAL serve route's engine sub-batches
    # (GUBER_GLOBAL_SERVE_WINDOW; 0 disables).  On a GLOBAL node the
    # engine is hit from several directions at once — client serves,
    # peer hit pushes, local miss copies — each paying its own device
    # dispatch.  This window (load-adaptive, like every round-6
    # window: an isolated apply fires immediately) lets concurrent
    # GLOBAL applies share one dispatch, which is what keeps the
    # cluster-tier median flat when the hit pipeline runs hot.
    global_serve_window: float = 0.002
    # Count-min-sketch approximate limiter (Behavior.SKETCH;
    # GUBER_SKETCH_*): window / depth / width of the two-epoch sketch
    # (ops/sketch.py; BASELINE config 5).
    sketch_window_ms: int = 1_000
    sketch_depth: int = 4
    sketch_width: int = 1 << 20
    # Host-tier decision ledger (core/ledger.py; GUBER_LEDGER, default
    # on): sticky over-limit answers + bounded credit leases serve
    # hot-key decisions without a device dispatch.  GUBER_LEDGER=0
    # restores the dispatch-per-decision path exactly.
    ledger: bool = True
    # Per-key lease credit budget — also the per-key over-admission
    # bound when an external racer reads the device before the lease
    # settles (GUBER_LEDGER_LEASE).
    ledger_lease: int = 512
    # Lease lifetime (seconds); expiry settles consumed credits back to
    # the device off the critical path (GUBER_LEDGER_LEASE_TTL).
    ledger_lease_ttl: float = 0.2
    # Hits within a 1s window before a key is granted a lease
    # (GUBER_LEDGER_HOT_THRESHOLD).
    ledger_hot_threshold: int = 8
    # Ledger entry LRU capacity (GUBER_LEDGER_KEYS).
    ledger_keys: int = 65536
    # Background settle flush period, seconds; 0 = manual/tests only
    # (GUBER_LEDGER_SETTLE_INTERVAL).
    ledger_settle_interval: float = 0.05


# ----------------------------------------------------------------------
# Canonical GUBER_* env-surface index (guberlint's drift pass pins it:
# every knob read ANYWHERE must appear in this file and in the README
# knob table).  Daemon knobs load in setup_daemon_config below; the
# debug/infra knobs here are read at their point of use — they gate
# process bootstrap (before a DaemonConfig exists) or test-only builds,
# so hauling them through the dataclass would be ceremony.  Each entry
# names its read site.

KNOWN_ENV_KNOBS = (
    # Engine / device plane.
    "GUBER_PLATFORM",         # daemon.py: `cpu` forces the host backend
    "GUBER_PUMP",             # core/engine.py: step-pump mode override
    "GUBER_PUMP_SCAN",        # core/pump.py: fused-scan round loop toggle
    "GUBER_WINDOW_DEPTH",     # core/pump.py + core/readback.py:
                              # double-buffered h2d/d2h window depth
    "GUBER_PSUM_MERGE",       # parallel/sharded_engine.py: psum column
                              # merge over the mesh (0 disables)
    "GUBER_MULTI_THREADS",    # core/native.py: native scheduler threads
    "GUBER_SHARDS_SINGLE_PROGRAM",  # parallel/sharded_engine.py: one
                              # pjit program across shards vs per-shard
    # Paged device bucket state (core/paging.py; PERF.md §30).
    "GUBER_PAGED",            # config.env_paged → core/engine.py: page
                              # the bucket state behind a page table
                              # (0 keeps the dense plane, the A/B arm)
    "GUBER_PAGE_SIZE",        # config.env_page_size → core/engine.py:
                              # bucket rows per device page (pow2 ≥ 16)
    "GUBER_PAGED_RESIDENT",   # config.env_paged_resident →
                              # core/engine.py: resident device frames
                              # (pages); 0 = every page resident
    # Build / test infra.
    "GUBER_NATIVE_SAN",       # core/native_build.py: TSan/ASan build tag
    # Process bootstrap (read before config loads).
    "GUBER_LOG_LEVEL",        # utils/logging_setup.py
    "GUBER_LOG_FORMAT",       # utils/logging_setup.py ("json" | "text")
    "GUBER_TRACING",          # utils/tracing.py ("memory" recorder)
    # Observability plane (OBSERVABILITY.md) — read at point of use.
    "GUBER_TRACE_TAIL_FACTOR",   # utils/flight_recorder.py: p99 multiple
    "GUBER_TRACE_TAIL_MIN_MS",   # utils/flight_recorder.py: floor, ms
    "GUBER_TRACE_TAIL_CAP",      # utils/flight_recorder.py: ring size
    "GUBER_HOTKEYS",             # utils/hotkeys.py: top-K sketch on/off
    "GUBER_HOTKEYS_K",           # utils/hotkeys.py: counter capacity
    "GUBER_HOTKEYS_WINDOW",      # utils/hotkeys.py: rate decay window, s
    "GUBER_NATIVE_EVENTS",       # net/h2_fast.py: C event ring on/off
    "GUBER_NATIVE_EVENTS_CAP",   # net/h2_fast.py: ring record capacity
    "GUBER_NATIVE_EVENTS_INTERVAL",  # utils/native_events.py: drain period
    # Fleet observability plane (obs/; OBSERVABILITY.md §§9-10).
    "GUBER_OBS",                 # daemon.py: fleet rollup + watchdog on/off
    "GUBER_OBS_RPC_TIMEOUT",     # obs/fleet.py: per-peer ObsSnapshot timeout
    "GUBER_OBS_FANOUT_DEADLINE",  # obs/fleet.py: rollup fan-out barrier
    "GUBER_SLO_INTERVAL",        # obs/slo.py: watchdog tick period (0=off)
    "GUBER_SLO_FLEET",           # obs/slo.py: ticks scrape the whole fleet
    "GUBER_SLO_FAST_WINDOWS",    # obs/slo.py: fast burn pair "short,long" s
    "GUBER_SLO_SLOW_WINDOWS",    # obs/slo.py: slow burn pair "short,long" s
    "GUBER_SLO_WATCH_KEYS",      # obs/slo.py: admission-bound watched keys
    "GUBER_METRICS_EXEMPLARS",   # utils/metrics.py: bucket trace exemplars
    # Event front (net/h2_fast.py; h2_server.cpp reactors, PERF §26).
    "GUBER_H2_EVENT_FRONT",      # net/h2_fast.py: epoll reactor front on/off
    "GUBER_H2_REACTORS",         # net/h2_fast.py: reactor threads (0=ncpu-1)
    "GUBER_H2_IDLE_TIMEOUT",     # net/h2_fast.py: idle-conn reap (GOAWAY)
    # Columnar feeder plane (net/h2_fast.py; columnar_feeder.cpp).
    "GUBER_NATIVE_FEEDER",       # net/h2_fast.py: C columnar feeder on/off
    "GUBER_FEEDER_RING_SLOTS",   # net/h2_fast.py: ring window count
    "GUBER_FEEDER_RING_ROWS",    # net/h2_fast.py: rows per ring window
    "GUBER_FEEDER_RING_KEYBYTES",  # net/h2_fast.py: key bytes per window
    "GUBER_RETRY_HINTS",         # net/h2_fast.py: retry_after_ms metadata
                              # on native OVER_LIMIT answers
    # Discovery plane (read by the k8s watcher, not the daemon config).
    "GUBER_K8S_NAMESPACE",    # discovery/kubernetes.py
    "GUBER_K8S_POD_SELECTOR",  # discovery/kubernetes.py
    # Multi-region federation plane (cluster/multiregion.py;
    # RESILIENCE.md §12).  These are daemon knobs — they load in
    # setup_daemon_config below like every BehaviorConfig field — and
    # are ALSO indexed here because they define the cross-region
    # resilience surface operators tune as one unit.
    "GUBER_MULTI_REGION_FANOUT_DEADLINE",  # setup_daemon_config:
                              # cross-region fan-out barrier budget
    "GUBER_MULTI_REGION_REQUEUE_AGE",  # setup_daemon_config: retry
                              # backlog age cap (drops counted past it)
    "GUBER_MULTI_REGION_BACKOFF",  # setup_daemon_config: per-region
                              # retry backoff base (full jitter)
    "GUBER_MULTI_REGION_BACKOFF_CAP",  # setup_daemon_config: per-region
                              # retry backoff ceiling
)


def env_window_depth(default: int = 2) -> int:
    """The GUBER_WINDOW_DEPTH knob, shared by the step pump's h2d
    pre-staging and the readback combiner's d2h window prefetch
    (core/pump.py / core/readback.py) — one parser so the two sides
    cannot drift."""
    try:
        return int(os.environ.get("GUBER_WINDOW_DEPTH", "") or default)
    except ValueError:
        return default


def env_paged() -> bool:
    """GUBER_PAGED: page the device bucket state behind a page table
    with LRU host spill (core/paging.py).  Default off — the dense
    plane is the A/B control arm (PERF.md §30)."""
    return os.environ.get("GUBER_PAGED", "").strip() == "1"


def env_page_size(default: int = 512) -> int:
    """GUBER_PAGE_SIZE: bucket rows per device page.  Must be a power
    of two ≥ 16 (slot→(page,row) splits are shift/mask on the
    translate hot path; the clear/restore scatter floor is 16);
    anything else falls back to the default."""
    try:
        v = int(os.environ.get("GUBER_PAGE_SIZE", "") or default)
    except ValueError:
        return default
    if v < 16 or v & (v - 1):
        return default
    return v


def env_paged_resident(default: int = 0) -> int:
    """GUBER_PAGED_RESIDENT: device frames (resident pages).  0 keeps
    every page resident — paged layout, dense footprint; a smaller
    value is what buys the 10-100x key space over device memory."""
    try:
        return max(0, int(os.environ.get("GUBER_PAGED_RESIDENT", "") or default))
    except ValueError:
        return default


def _env(d: Dict[str, str], key: str, default: str = "") -> str:
    return d.get(key, os.environ.get(key, default)) or default


def _env_int(d: Dict[str, str], key: str, default: int) -> int:
    v = _env(d, key)
    return int(v) if v else default


def _env_float_seconds(d: Dict[str, str], key: str, default: float) -> float:
    """Parse Go-style duration strings ("500us", "30s", "1m") or float
    seconds. reference duration envs like GUBER_BATCH_WAIT."""
    v = _env(d, key)
    if not v:
        return default
    return parse_duration(v)


_DURATION_UNITS = [
    ("ms", MILLISECOND),
    ("us", MICROSECOND),
    ("µs", MICROSECOND),
    ("ns", 1e-9),
    ("s", 1.0),
    ("m", 60.0),
    ("h", 3600.0),
]


def parse_duration(v: str) -> float:
    """Parse a Go duration string into float seconds."""
    v = v.strip()
    try:
        return float(v)
    except ValueError:
        pass
    # Compound forms like "1m30s" parse unit-by-unit.
    total = 0.0
    num = ""
    i = 0
    while i < len(v):
        c = v[i]
        if c.isdigit() or c in ".+-":
            num += c
            i += 1
            continue
        for unit, mult in _DURATION_UNITS:
            if v.startswith(unit, i) and (
                i + len(unit) == len(v) or v[i + len(unit)].isdigit() or v[i + len(unit)] in ".+-"
            ):
                if not num:
                    raise ValueError(f"bad duration {v!r}")
                total += float(num) * mult
                num = ""
                i += len(unit)
                break
        else:
            raise ValueError(f"bad duration {v!r}")
    if num:
        raise ValueError(f"bad duration {v!r}")
    return total


def load_env_file(path: str) -> Dict[str, str]:
    """Read a KEY=VALUE config file (reference: config.go:556-584).

    Lines starting with # and blank lines are ignored; values are also
    exported into os.environ, matching the reference's behavior of
    loading the file into the environment.
    """
    out: Dict[str, str] = {}
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                raise ValueError(f"{path}:{lineno}: expected KEY=VALUE")
            k, _, v = line.partition("=")
            out[k.strip()] = v.strip()
    os.environ.update(out)
    return out


@dataclass
class DaemonConfig:
    """Process-level config. reference: config.go:169-229."""

    grpc_listen_address: str = "localhost:81"
    http_listen_address: str = "localhost:80"
    # Optional plain-HTTP status listener when mTLS is on
    # (reference: daemon.go:279-307).
    http_status_listen_address: str = ""
    advertise_address: str = ""
    cache_size: int = 50_000
    data_center: str = ""
    behaviors: BehaviorConfig = field(default_factory=BehaviorConfig)
    hash_algorithm: str = "fnv1"

    # Peer discovery: "member-list" | "etcd" | "dns" | "k8s" | "none"
    # (reference default member-list, config.go:300).
    peer_discovery_type: str = "none"
    # Static cluster membership for discovery "none"
    # (GUBER_STATIC_PEERS): comma-separated peer gRPC addresses
    # (including this node's advertise address).  The fixed-topology
    # deployment mode — compose files, systemd units, bench clusters —
    # where running a discovery plane would be ceremony.
    static_peers: List[str] = field(default_factory=list)
    # Static seed peers / memberlist known hosts.
    member_list_address: str = ""
    known_hosts: List[str] = field(default_factory=list)
    advertise_port: int = 7946  # reference: config.go:373
    # DNS discovery.
    dns_fqdn: str = ""
    dns_poll_interval: float = 300.0
    # etcd discovery (auth/TLS block — reference: config.go:363-370,
    # 440-496).
    etcd_endpoints: List[str] = field(default_factory=list)
    etcd_key_prefix: str = "/gubernator/peers/"
    etcd_dial_timeout: float = 5.0
    etcd_user: str = ""
    etcd_password: str = ""
    etcd_advertise_address: str = ""  # default: the node advertise addr
    etcd_data_center: str = ""  # default: the node data center
    etcd_tls_ca: str = ""
    etcd_tls_cert: str = ""
    etcd_tls_key: str = ""
    etcd_tls_skip_verify: bool = False

    # Picker selection (see Config.peer_picker / picker_replicas).
    peer_picker: str = "replicated-hash"
    picker_replicas: int = 512

    # gRPC keepalive: close server connections older than this many
    # seconds (0 = never; reference: daemon.go:110-115).
    grpc_max_conn_age_sec: int = 0

    # gRPC server handler threads (GUBER_GRPC_WORKERS).  The engine is
    # a serial device resource, so a handler count far above the CPU
    # count only grows the lock/GIL convoy: excess RPCs queue in the
    # executor (FIFO, cheap) instead of as runnable threads.  The
    # reference sizes its worker pool by NumCPU the same way
    # (gubernator_pool.go:128-149).
    grpc_workers: int = 32

    # Debug logging (GUBER_DEBUG; reference: config.go:275).
    debug: bool = False

    # Approximate limiter (see Config.sketch_*).
    sketch_window_ms: int = 1_000
    sketch_depth: int = 4
    sketch_width: int = 1 << 20

    # Host-tier decision ledger (see Config.ledger_*).
    ledger: bool = True
    ledger_lease: int = 512
    ledger_lease_ttl: float = 0.2
    ledger_hot_threshold: int = 8
    ledger_keys: int = 65536
    ledger_settle_interval: float = 0.05

    # TLS (None = plaintext); see gubernator_tpu.net.tls.
    tls: Optional["object"] = None

    # Device-mesh shape for the sharded engine; None = all local devices.
    device_count: Optional[int] = None

    # Period of the device expiry sweep that reclaims slots of expired
    # buckets (the LRU evicts on pressure regardless; the sweep keeps
    # cache_size metrics honest and slots recycled).  0 disables.
    sweep_interval: float = 30.0
    # Client-facing wire group-commit window (0 = off); see Config.
    local_batch_wait: float = 0.0
    # GLOBAL serve-route group-commit cap (see Config).
    global_serve_window: float = 0.002
    # Native h2 fast front (net/h2_fast.py): "" = disabled;
    # "127.0.0.1:0" binds an ephemeral port.
    h2_fast_address: str = ""
    h2_fast_window: float = 0.002
    # SO_REUSEPORT listener lanes for the fast front (GUBER_H2_LANES);
    # 0 = one lane per CPU.  Accept/framing/decide run on per-lane /
    # per-connection C threads, so lanes are what lets the front scale
    # across cores instead of serializing on one listener.
    h2_lanes: int = 0
    # ---- elastic membership (cluster/membership.py; RESILIENCE §10) -
    # Wall budget for one epoch transition, seconds: a handoff that
    # cannot deliver (target broken/suspect) delays the epoch commit
    # up to this long, then forfeits the undeliverable rows
    # (GUBER_MEMBERSHIP_EPOCH_TIMEOUT).
    membership_epoch_timeout: float = 30.0
    # Bucket rows per TransferBuckets RPC during ownership handoff
    # (GUBER_HANDOFF_WINDOW).
    handoff_window: int = 512
    # Wall budget for a planned-leave drain to ship every held bucket,
    # seconds (GUBER_DRAIN_DEADLINE).  A clean drain reports zero
    # forfeited rows well inside it.
    drain_deadline: float = 30.0

    # ---- hot-key replication plane (cluster/replication.py;
    # RESILIENCE.md §11) ----------------------------------------------
    # Master switch (GUBER_REPLICATION, default on): promote the
    # measured hottest keys to replicated ownership — the owner splits
    # the limit into per-replica PRE-DEBITED credit leases, every
    # replica answers locally, demotion on cooldown.  Off restores
    # consistent-hash-only routing exactly.
    replication: bool = True
    # Observed hits/sec (hotkeys windowed rate) before the owner
    # promotes a key (GUBER_REPL_PROMOTE_RATE).  Demotion arms at half
    # this rate.
    repl_promote_rate: float = 2000.0
    # Seconds a promoted key must stay below the demote rate before it
    # converges back to single-owner (GUBER_REPL_COOLDOWN hysteresis).
    repl_cooldown: float = 10.0
    # Per-replica credit slice per grant — also the per-replica term
    # of the N_replicas × lease over-admission bound
    # (GUBER_REPL_LEASE).
    repl_lease: int = 2048
    # Replica lease lifetime, seconds (GUBER_REPL_LEASE_TTL); the
    # owner refreshes ahead of it, and a broken replica's lease
    # expires into the bound.
    repl_lease_ttl: float = 1.0
    # Promotion/demotion scan period, seconds (GUBER_REPL_INTERVAL).
    repl_interval: float = 0.5
    # Max concurrently replicated keys per owner (GUBER_REPL_MAX_KEYS).
    repl_max_keys: int = 16
    # Replica-count policy (GUBER_REPL_MAX_REPLICAS): cap each hot
    # key's grant fan-out to the N least-loaded local-DC peers (load =
    # in-flight RPCs + queued batch items toward the peer) instead of
    # every peer.  0 = unlimited (every local-DC peer, the pre-policy
    # behavior).  Cuts grant-refresh fan-out on big clusters while the
    # over-admission bound tightens with it (≤ N × lease).
    repl_max_replicas: int = 0

    # Native decision plane (GUBER_NATIVE_LEDGER, default on): delegate
    # the ledger's exact fast path (sticky over-limit + lease drains)
    # into the C front so hot-key RPCs never enter Python.  Only
    # engaged when the decision ledger itself is on and the engine runs
    # the live system clock.
    native_ledger: bool = True

    metric_flags: List[str] = field(default_factory=list)


def setup_daemon_config(
    config_file: Optional[str] = None, env: Optional[Dict[str, str]] = None
) -> DaemonConfig:
    """Build a DaemonConfig from GUBER_* env vars (+ optional file).

    reference: config.go:247-451 (SetupDaemonConfig).
    """
    d: Dict[str, str] = dict(env or {})
    if config_file:
        d.update(load_env_file(config_file))

    behaviors = BehaviorConfig(
        batch_timeout=_env_float_seconds(d, "GUBER_BATCH_TIMEOUT", 0.5),
        batch_wait=_env_float_seconds(d, "GUBER_BATCH_WAIT", 500 * MICROSECOND),
        batch_limit=_env_int(d, "GUBER_BATCH_LIMIT", 1000),
        global_timeout=_env_float_seconds(d, "GUBER_GLOBAL_TIMEOUT", 0.5),
        global_sync_wait=_env_float_seconds(
            d, "GUBER_GLOBAL_SYNC_WAIT", 500 * MICROSECOND
        ),
        global_batch_limit=_env_int(d, "GUBER_GLOBAL_BATCH_LIMIT", 1000),
        multi_region_timeout=_env_float_seconds(d, "GUBER_MULTI_REGION_TIMEOUT", 0.5),
        multi_region_sync_wait=_env_float_seconds(
            d, "GUBER_MULTI_REGION_SYNC_WAIT", 500 * MICROSECOND
        ),
        multi_region_batch_limit=_env_int(d, "GUBER_MULTI_REGION_BATCH_LIMIT", 1000),
        multi_region_fanout_deadline=_env_float_seconds(
            d, "GUBER_MULTI_REGION_FANOUT_DEADLINE", 2.0
        ),
        multi_region_requeue_age=_env_float_seconds(
            d, "GUBER_MULTI_REGION_REQUEUE_AGE", 10.0
        ),
        multi_region_backoff=_env_float_seconds(
            d, "GUBER_MULTI_REGION_BACKOFF", 0.05
        ),
        multi_region_backoff_cap=_env_float_seconds(
            d, "GUBER_MULTI_REGION_BACKOFF_CAP", 2.0
        ),
        adaptive_windows=_env(d, "GUBER_ADAPTIVE_WINDOWS", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        circuit_failures=_env_int(d, "GUBER_CIRCUIT_FAILURES", 3),
        circuit_backoff=_env_float_seconds(d, "GUBER_CIRCUIT_BACKOFF", 0.5),
        circuit_backoff_cap=_env_float_seconds(
            d, "GUBER_CIRCUIT_BACKOFF_CAP", 30.0
        ),
        forward_backoff=_env_float_seconds(
            d, "GUBER_FORWARD_BACKOFF", 0.01
        ),
        forward_backoff_cap=_env_float_seconds(
            d, "GUBER_FORWARD_BACKOFF_CAP", 0.25
        ),
        degraded_local=_env(d, "GUBER_DEGRADED_LOCAL", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        global_fanout_deadline=_env_float_seconds(
            d, "GUBER_GLOBAL_FANOUT_DEADLINE", 2.0
        ),
        hit_requeue_age=_env_float_seconds(d, "GUBER_HIT_REQUEUE_AGE", 5.0),
    )

    peer_picker = _env(d, "GUBER_PEER_PICKER", "replicated-hash")
    # Validate via the single source of truth (cluster.hash_ring).
    from gubernator_tpu.cluster.hash_ring import make_picker

    make_picker(peer_picker, "fnv1")
    # When the picker is selected explicitly, the reference defaults
    # its hash to fnv1a (config.go:403); otherwise fnv1.
    hash_default = "fnv1a" if _env(d, "GUBER_PEER_PICKER") else "fnv1"
    hash_algorithm = _env(d, "GUBER_PEER_PICKER_HASH", hash_default)
    if hash_algorithm not in ("fnv1", "fnv1a"):
        raise ValueError(
            f"GUBER_PEER_PICKER_HASH={hash_algorithm!r}: want fnv1 or fnv1a"
        )
    picker_replicas = _env_int(d, "GUBER_REPLICATED_HASH_REPLICAS", 512)
    discovery = _env(d, "GUBER_PEER_DISCOVERY_TYPE", "none")
    if discovery not in ("none", "member-list", "etcd", "dns", "k8s"):
        raise ValueError(
            f"GUBER_PEER_DISCOVERY_TYPE={discovery!r}: want none, "
            "member-list, etcd, dns or k8s"
        )

    tls = None
    if _env(d, "GUBER_TLS_CA") or _env(d, "GUBER_TLS_CERT") or _env(d, "GUBER_TLS_AUTO"):
        from gubernator_tpu.net.tls import TLSConfig

        tls = TLSConfig(
            ca_file=_env(d, "GUBER_TLS_CA"),
            ca_key_file=_env(d, "GUBER_TLS_CA_KEY"),
            cert_file=_env(d, "GUBER_TLS_CERT"),
            key_file=_env(d, "GUBER_TLS_KEY"),
            auto_tls=_env(d, "GUBER_TLS_AUTO") in ("1", "true", "yes"),
            client_auth=_env(d, "GUBER_TLS_CLIENT_AUTH"),
            client_auth_ca_file=_env(d, "GUBER_TLS_CLIENT_AUTH_CA_CERT"),
            client_auth_cert_file=_env(d, "GUBER_TLS_CLIENT_AUTH_CERT"),
            client_auth_key_file=_env(d, "GUBER_TLS_CLIENT_AUTH_KEY"),
        )

    dc = _env(d, "GUBER_DATA_CENTER")
    device_count = _env_int(d, "GUBER_DEVICE_COUNT", 0) or None

    return DaemonConfig(
        grpc_listen_address=_env(d, "GUBER_GRPC_ADDRESS", "localhost:81"),
        http_listen_address=_env(d, "GUBER_HTTP_ADDRESS", "localhost:80"),
        http_status_listen_address=_env(d, "GUBER_STATUS_HTTP_ADDRESS", ""),
        advertise_address=_env(d, "GUBER_ADVERTISE_ADDRESS", ""),
        cache_size=_env_int(d, "GUBER_CACHE_SIZE", 50_000),
        data_center=dc,
        behaviors=behaviors,
        hash_algorithm=hash_algorithm,
        peer_discovery_type=discovery,
        static_peers=[
            h.strip()
            for h in _env(d, "GUBER_STATIC_PEERS", "").split(",")
            if h.strip()
        ],
        member_list_address=_env(d, "GUBER_MEMBERLIST_ADDRESS", ""),
        known_hosts=[
            h.strip()
            for h in _env(d, "GUBER_MEMBERLIST_KNOWN_NODES", "").split(",")
            if h.strip()
        ],
        advertise_port=_env_int(d, "GUBER_MEMBERLIST_ADVERTISE_PORT", 7946),
        dns_fqdn=_env(d, "GUBER_DNS_FQDN", ""),
        dns_poll_interval=_env_float_seconds(d, "GUBER_DNS_POLL_INTERVAL", 300.0),
        etcd_endpoints=[
            h.strip()
            for h in _env(d, "GUBER_ETCD_ENDPOINTS", "").split(",")
            if h.strip()
        ],
        etcd_key_prefix=_env(d, "GUBER_ETCD_KEY_PREFIX", "/gubernator/peers/"),
        etcd_dial_timeout=_env_float_seconds(d, "GUBER_ETCD_DIAL_TIMEOUT", 5.0),
        etcd_user=_env(d, "GUBER_ETCD_USER"),
        etcd_password=_env(d, "GUBER_ETCD_PASSWORD"),
        etcd_advertise_address=_env(d, "GUBER_ETCD_ADVERTISE_ADDRESS"),
        etcd_data_center=_env(d, "GUBER_ETCD_DATA_CENTER", dc),
        etcd_tls_ca=_env(d, "GUBER_ETCD_TLS_CA"),
        etcd_tls_cert=_env(d, "GUBER_ETCD_TLS_CERT"),
        etcd_tls_key=_env(d, "GUBER_ETCD_TLS_KEY"),
        etcd_tls_skip_verify=_env(d, "GUBER_ETCD_TLS_SKIP_VERIFY")
        in ("1", "true", "yes"),
        peer_picker=peer_picker,
        picker_replicas=picker_replicas,
        grpc_max_conn_age_sec=_env_int(d, "GUBER_GRPC_MAX_CONN_AGE_SEC", 0),
        grpc_workers=_env_int(d, "GUBER_GRPC_WORKERS", 32),
        debug=_env(d, "GUBER_DEBUG") in ("1", "true", "yes"),
        sketch_window_ms=int(
            _env_float_seconds(d, "GUBER_SKETCH_WINDOW", 1.0) * 1000
        ),
        sketch_depth=_env_int(d, "GUBER_SKETCH_DEPTH", 4),
        sketch_width=_env_int(d, "GUBER_SKETCH_WIDTH", 1 << 20),
        ledger=_env(d, "GUBER_LEDGER", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        ledger_lease=_env_int(d, "GUBER_LEDGER_LEASE", 512),
        ledger_lease_ttl=_env_float_seconds(
            d, "GUBER_LEDGER_LEASE_TTL", 0.2
        ),
        ledger_hot_threshold=_env_int(d, "GUBER_LEDGER_HOT_THRESHOLD", 8),
        ledger_keys=_env_int(d, "GUBER_LEDGER_KEYS", 65536),
        ledger_settle_interval=_env_float_seconds(
            d, "GUBER_LEDGER_SETTLE_INTERVAL", 0.05
        ),
        tls=tls,
        device_count=device_count,
        sweep_interval=_env_float_seconds(d, "GUBER_SWEEP_INTERVAL", 30.0),
        local_batch_wait=_env_float_seconds(d, "GUBER_LOCAL_BATCH_WAIT", 0.0),
        global_serve_window=_env_float_seconds(
            d, "GUBER_GLOBAL_SERVE_WINDOW", 0.002
        ),
        replication=_env(d, "GUBER_REPLICATION", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        repl_promote_rate=float(
            _env(d, "GUBER_REPL_PROMOTE_RATE") or 2000.0
        ),
        repl_cooldown=_env_float_seconds(d, "GUBER_REPL_COOLDOWN", 10.0),
        repl_lease=_env_int(d, "GUBER_REPL_LEASE", 2048),
        repl_lease_ttl=_env_float_seconds(
            d, "GUBER_REPL_LEASE_TTL", 1.0
        ),
        repl_interval=_env_float_seconds(d, "GUBER_REPL_INTERVAL", 0.5),
        repl_max_keys=_env_int(d, "GUBER_REPL_MAX_KEYS", 16),
        repl_max_replicas=_env_int(d, "GUBER_REPL_MAX_REPLICAS", 0),
        membership_epoch_timeout=_env_float_seconds(
            d, "GUBER_MEMBERSHIP_EPOCH_TIMEOUT", 30.0
        ),
        handoff_window=_env_int(d, "GUBER_HANDOFF_WINDOW", 512),
        drain_deadline=_env_float_seconds(d, "GUBER_DRAIN_DEADLINE", 30.0),
        h2_fast_address=_env(d, "GUBER_H2_FAST_ADDRESS", ""),
        h2_fast_window=_env_float_seconds(d, "GUBER_H2_FAST_WINDOW", 0.002),
        h2_lanes=_env_int(d, "GUBER_H2_LANES", 0),
        native_ledger=_env(d, "GUBER_NATIVE_LEDGER", "1").strip().lower()
        not in ("0", "false", "no", "off"),
        metric_flags=[
            f.strip()
            for f in _env(d, "GUBER_METRIC_FLAGS", "").split(",")
            if f.strip()
        ],
    )


def resolve_advertise_address(listen: str, advertise: str = "") -> str:
    """Resolve 0.0.0.0/:: listen addresses to a routable advertise
    address. reference: net.go:28-49."""
    if advertise:
        return advertise
    host, _, port = listen.rpartition(":")
    if host in ("0.0.0.0", "::", ""):
        host = socket.gethostbyname(socket.gethostname())
    return f"{host}:{port}"
