"""Daemon — process bootstrap: engine + service + listeners + discovery.

reference: daemon.go.  `spawn_daemon(conf)` builds the TPU decision
engine (single-device or mesh-sharded), wires the V1 service, starts
the gRPC server + HTTP gateway (+ optional plain status listener when
mTLS is on), hooks up peer discovery, and exposes `set_peers` for
membership pushes (daemon.go:370-380 marks self by address match).
"""

from __future__ import annotations

import logging
import os
import ssl
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Sequence

import grpc

from gubernator_tpu.clock import SYSTEM_CLOCK, Clock
from gubernator_tpu.config import Config, DaemonConfig, resolve_advertise_address
from gubernator_tpu.net.gateway import Gateway
from gubernator_tpu.net.grpc_service import (
    V1Stub,
    add_peers_v1_to_server,
    add_v1_to_server,
    dial,
)
from gubernator_tpu.net.server import GrpcPeersV1Adapter, GrpcV1Adapter
from gubernator_tpu.service import V1Instance
from gubernator_tpu.types import PeerInfo
from gubernator_tpu.utils.metrics import DurationStat, build_registry

log = logging.getLogger("gubernator_tpu.daemon")


class _StampedExecutor(ThreadPoolExecutor):
    """The gRPC server's handler pool, observing `listener.queue_wait`:
    how long an RPC waits for one of the `grpc_workers` threads.
    `submit` stamps the task; its wrapper observes stamp → start on the
    worker.  Histogram only: the wait ends before the handler's root
    span exists, and a wait never carries a profiler annotation."""

    def __init__(self, wait_stat: DurationStat, **kwargs) -> None:
        super().__init__(**kwargs)
        self._wait_stat = wait_stat

    def submit(self, fn, /, *args, **kwargs):
        queued = time.monotonic()

        def started():
            self._wait_stat.observe(time.monotonic() - queued)
            return fn(*args, **kwargs)

        return super().submit(started)


class Daemon:
    """One gubernator_tpu process. reference: daemon.go:56-80."""

    def __init__(
        self,
        conf: DaemonConfig,
        *,
        clock: Clock = SYSTEM_CLOCK,
        engine=None,
        store=None,  # write-through Store (reference: config.go Store field)
        loader=None,  # bulk Loader (reference: config.go Loader field)
    ):
        self.conf = conf
        self.clock = clock
        self._engine = engine
        self._store = store
        self._loader = loader
        self.instance: Optional[V1Instance] = None
        self.grpc_server: Optional[grpc.Server] = None
        self.gateway: Optional[Gateway] = None
        self.status_gateway: Optional[Gateway] = None
        self.registry = None
        self.grpc_address = conf.grpc_listen_address
        self.http_address = conf.http_listen_address
        self._tls_bundle = None
        self._discovery = None
        self.membership = None
        self.replication = None
        self.obs = None
        self.slo = None
        self._closed = False

    # ------------------------------------------------------------------

    def _apply_platform_override(self) -> None:
        """GUBER_PLATFORM=cpu forces the host backend before any
        backend touch — honored HERE so every entry point (binary,
        spawn_daemon, harness) gets it, not just cmd/daemon.py.  Any
        other value leaves the choice to jax (JAX_PLATFORMS).  One
        process per chip: the backend initializes in this process, and
        one that cannot start fails with its own error."""
        if os.environ.get("GUBER_PLATFORM", "").lower() == "cpu":
            from gubernator_tpu.platform_guard import force_cpu_platform

            force_cpu_platform(self.conf.device_count or None)

    def _build_engine(self):
        if self._engine is not None:
            return self._engine
        import jax

        devices = jax.devices()
        n = self.conf.device_count or len(devices)
        if n > 1:
            from gubernator_tpu.parallel.mesh import make_mesh
            from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine

            mesh = make_mesh(devices[:n])
            return ShardedDecisionEngine(
                shard_capacity=max(1, self.conf.cache_size // n),
                mesh=mesh,
                clock=self.clock,
                store=self._store,
            )
        from gubernator_tpu.core.engine import DecisionEngine

        return DecisionEngine(
            capacity=self.conf.cache_size,
            clock=self.clock,
            device=devices[0],
            store=self._store,
        )

    def start(self) -> None:
        """reference: daemon.go:82-339 (Daemon.Start)."""
        conf = self.conf
        # Count XLA compiles from before the first engine build so the
        # gubernator_jit_recompiles metric covers warmup too; a healthy
        # daemon's count is flat after start() returns.
        from gubernator_tpu.utils import jit_guard

        jit_guard.install()
        self._apply_platform_override()
        engine = self._build_engine()
        self._log_device(engine)
        self._warmup(engine)
        if self._loader is not None:
            # Restore persisted buckets before serving
            # (reference: gubernator.go:146-152).
            engine.load(self._loader)

        creds = None
        if conf.tls is not None:
            self._tls_bundle = conf.tls.setup()
            creds = self._tls_bundle.client_credentials()

        service_conf = Config(
            behaviors=conf.behaviors,
            cache_size=conf.cache_size,
            hash_algorithm=conf.hash_algorithm,
            peer_picker=conf.peer_picker,
            picker_replicas=conf.picker_replicas,
            data_center=conf.data_center,
            peer_credentials=creds,
            local_batch_wait=conf.local_batch_wait,
            global_serve_window=conf.global_serve_window,
            sketch_window_ms=conf.sketch_window_ms,
            sketch_depth=conf.sketch_depth,
            sketch_width=conf.sketch_width,
            ledger=conf.ledger,
            ledger_lease=conf.ledger_lease,
            ledger_lease_ttl=conf.ledger_lease_ttl,
            ledger_hot_threshold=conf.ledger_hot_threshold,
            ledger_keys=conf.ledger_keys,
            ledger_settle_interval=conf.ledger_settle_interval,
        )
        self.instance = V1Instance(service_conf, engine)
        # Elastic membership plane (cluster/membership.py): every peer
        # list this daemon observes — discovery pushes, static config,
        # harness — flows through set_peers into the manager, which
        # drives epoch transitions and ownership handoff.
        from gubernator_tpu.cluster.membership import MembershipManager

        self.membership = MembershipManager(
            self,
            epoch_timeout=conf.membership_epoch_timeout,
            handoff_window=conf.handoff_window,
            drain_deadline=conf.drain_deadline,
        )
        self.instance.membership = self.membership
        # Hot-key replication plane (cluster/replication.py): observed
        # load reshapes ownership — the hottest measured keys promote
        # to replicated credit leases, demote on cooldown.  Needs the
        # hot-key sketch for its rate source; inert without it.
        if conf.replication and self.instance.hotkeys is not None:
            from gubernator_tpu.cluster.replication import (
                ReplicationManager,
            )

            self.replication = ReplicationManager(
                self,
                promote_rate=conf.repl_promote_rate,
                cooldown=conf.repl_cooldown,
                lease=conf.repl_lease,
                lease_ttl=conf.repl_lease_ttl,
                interval=conf.repl_interval,
                max_keys=conf.repl_max_keys,
                max_replicas=conf.repl_max_replicas,
            )
            self.instance.replication = self.replication
            self.replication.start()
        # Tail flight recorder (utils/flight_recorder.py): when the
        # in-memory tracer is live (GUBER_TRACING=memory or a harness
        # set_tracer), retain full span trees of tail decisions for
        # /debug/trace.  OTel backends do their own tail sampling
        # upstream; disabled tracing costs nothing here.
        from gubernator_tpu.utils import tracing as _tracing
        from gubernator_tpu.utils.tracing import InMemoryTracer

        tracer = _tracing.current_tracer()
        if isinstance(tracer, InMemoryTracer):
            from gubernator_tpu.utils.flight_recorder import FlightRecorder

            # One recorder per tracer: in-process multi-daemon
            # harnesses share the global tracer, and each daemon
            # re-hooking on_root_finish would orphan its siblings'
            # recorders.
            fr = getattr(tracer, "_flight_recorder", None)
            if fr is None:
                fr = FlightRecorder.from_env(tracer)
                tracer._flight_recorder = fr
            self.instance.flight_recorder = fr
        self.registry = build_registry(
            self.instance, metric_flags=conf.metric_flags
        )
        # gRPC request counts/durations (reference: grpc_stats.go).
        from gubernator_tpu.utils.grpc_stats import GrpcStats

        grpc_stats = GrpcStats()
        self.registry.register(grpc_stats)

        # gRPC server (both services on one listener; the reference's
        # second loopback server exists only for grpc-gateway's dial,
        # which our native gateway doesn't need).
        queue_wait = DurationStat()
        self.instance.stage_timers["listener.queue_wait"] = queue_wait
        self.grpc_server = grpc.server(
            _StampedExecutor(
                queue_wait,
                max_workers=max(1, conf.grpc_workers),
                thread_name_prefix="guber-grpc",
            ),
            interceptors=[grpc_stats],
            options=[
                ("grpc.max_receive_message_length", 1024 * 1024),  # daemon.go:103
            ]
            + (
                # Only when configured, like the reference
                # (GUBER_GRPC_MAX_CONN_AGE_SEC; daemon.go:110-115).
                [("grpc.max_connection_age_ms", conf.grpc_max_conn_age_sec * 1000)]
                if conf.grpc_max_conn_age_sec > 0
                else []
            ),
        )
        add_v1_to_server(GrpcV1Adapter(self.instance), self.grpc_server)
        add_peers_v1_to_server(GrpcPeersV1Adapter(self.instance), self.grpc_server)
        if self._tls_bundle is not None:
            port = self.grpc_server.add_secure_port(
                conf.grpc_listen_address, self._tls_bundle.server_credentials()
            )
        else:
            port = self.grpc_server.add_insecure_port(conf.grpc_listen_address)
        if port == 0:
            raise RuntimeError(f"failed to bind gRPC on {conf.grpc_listen_address}")
        host = conf.grpc_listen_address.rpartition(":")[0]
        self.grpc_address = f"{host}:{port}"
        self.grpc_server.start()

        # HTTP gateway (+ /metrics).  Under TLS the gateway serves HTTPS
        # (reference: daemon.go:311-328).
        ssl_ctx = None
        if self._tls_bundle is not None:
            ssl_ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            with tempfile.NamedTemporaryFile(suffix=".pem") as cf, tempfile.NamedTemporaryFile(
                suffix=".pem"
            ) as kf:
                cf.write(self._tls_bundle.server_cert_pem)
                cf.flush()
                kf.write(self._tls_bundle.server_key_pem)
                kf.flush()
                ssl_ctx.load_cert_chain(cf.name, kf.name)
        self.gateway = Gateway(
            self.instance,
            conf.http_listen_address,
            self.registry,
            ssl_context=ssl_ctx,
        )
        self.gateway.start()
        host = conf.http_listen_address.rpartition(":")[0]
        self.http_address = f"{host}:{self.gateway.port}"

        # Optional native h2 fast front: one-method serving with zero
        # per-RPC Python (net/h2_fast.py documents the scope).
        self.h2_fast = None
        if conf.h2_fast_address:
            from gubernator_tpu.net.h2_fast import H2FastFront

            port = int(conf.h2_fast_address.rpartition(":")[2] or 0)
            self.h2_fast = H2FastFront(
                self.instance,
                port=port,
                window_s=conf.h2_fast_window,
                lanes=conf.h2_lanes or None,
                # The config field is authoritative (setup_daemon_config
                # parsed GUBER_NATIVE_LEDGER once); the front still
                # applies its live-clock gate.
                native_ledger=conf.native_ledger,
            )
            self.h2_fast_address = self.h2_fast.address
            # Connection-plane gauge source (gubernator_h2_conns):
            # the collector scrapes conn_stats() off the instance.
            self.instance.h2_front = self.h2_fast
            # Native event collector: drain the C front's event ring
            # into histograms/metrics/span stubs (utils/native_events;
            # GUBER_NATIVE_EVENTS=0 disables the ring entirely).
            if self.h2_fast._ring is not None:
                from gubernator_tpu.utils.native_events import (
                    NativeEventCollector,
                )

                self.instance.native_events = NativeEventCollector.from_env(
                    self.h2_fast
                )
            log.info("native h2 front serving: %s", self.h2_fast.settings())

        # Fleet observability plane (obs/; OBSERVABILITY.md §§9-10):
        # the cluster rollup collector behind /debug/fleet +
        # /metrics?fleet=1 + PeersV1/ObsSnapshot, and the SLO/
        # invariant burn-rate watchdog behind /debug/slo and the
        # gubernator_slo_* gauges.  GUBER_OBS=0 removes the whole
        # plane (the fleetobs bench's A/B arm).
        self.obs = None
        self.slo = None
        if os.environ.get("GUBER_OBS", "1").strip().lower() not in (
            "0", "false", "no", "off",
        ):
            from gubernator_tpu.obs.fleet import FleetCollector
            from gubernator_tpu.obs.slo import (
                SLOWatchdog,
                watch_keys_from_env,
            )

            self.obs = FleetCollector.from_env(
                self.instance,
                addr=resolve_advertise_address(
                    self.grpc_address, conf.advertise_address
                ),
                region=conf.data_center,
            )
            self.instance.obs = self.obs
            watch_keys_from_env(self.instance.admission_watch)
            self.slo = SLOWatchdog.from_env(
                self.obs, self.instance.admission_watch
            )
            self.instance.slo_watchdog = self.slo

        # Optional plain-HTTP status listener for probes when mTLS
        # would block them (reference: daemon.go:279-307).
        if conf.http_status_listen_address:
            self.status_gateway = Gateway(
                self.instance,
                conf.http_status_listen_address,
                self.registry,
                serve_metrics=True,
            )
            self.status_gateway.start()

        self._start_discovery()

        # Periodic device expiry sweep reclaiming slots of expired
        # buckets (the reference's cache drops expired items on read,
        # lrucache.go:112-138; device-resident state needs an explicit
        # sweep kernel — SURVEY.md §7.3 item 6).
        if self.conf.sweep_interval > 0:
            self._sweep_stop = threading.Event()
            self._sweeper = threading.Thread(
                target=self._sweep_loop, name="guber-sweep", daemon=True
            )
            self._sweeper.start()

    # Windows swept per tick: bounds how long each periodic sweep holds
    # the engine lock (a full pass at 100M slots is ~763 windows of
    # device round-trips — serving p99 would spike for its whole
    # duration).  The cursor resumes next tick, so full coverage still
    # happens, just spread across ticks.
    SWEEP_WINDOWS_PER_TICK = 16

    def _sweep_loop(self) -> None:
        while not self._sweep_stop.wait(self.conf.sweep_interval):
            try:
                self.instance.engine.sweep(
                    max_windows=self.SWEEP_WINDOWS_PER_TICK
                )
            except Exception:  # noqa: BLE001 — sweeping must not die
                from gubernator_tpu.utils.metrics import record_swallowed

                record_swallowed("daemon.sweep")
                log.exception("expiry sweep failed")

    @staticmethod
    def _log_device(engine) -> None:
        """Say what this daemon serves on (the same block /debug/vars
        carries as `device`)."""
        from gubernator_tpu.core import device_info

        info = device_info.describe(engine)
        log.info(
            "serving on platform=%s device_kind=%s devices=%d engine=%s "
            "rows=%d pump=%s pump_scan=%s probes=%s",
            info["platform"], info["device_kind"], info["device_count"],
            info["engine"], info["rows"],
            info["pump"], info["pump_scan"], info["probes"],
        )
        if info["cpu_unrequested"]:
            log.warning(
                "jax resolved to the CPU backend without having been "
                "told to (no GUBER_PLATFORM=cpu / JAX_PLATFORMS=cpu): "
                "decisions are NOT served from an accelerator"
            )

    def _warmup(self, engine) -> None:
        """Pay the kernel jit compiles before serving, not on the first
        client requests (an XLA compile can exceed the peer batch
        timeout).  The default ladder (64..1024) covers every width the
        wire can produce — MAX_BATCH_SIZE=1000 pads to 1024 — for BOTH
        serving programs (dataclass + columnar); engine-level callers
        that exceed it (bench harnesses) warm their own widths.
        Group-commit windows MERGE wire batches, so with a window
        enabled the ladder extends to the window's merge bound (4096)
        — a mid-serving compile of an unseen merged width was a
        measured multi-second p99 spike.
        tests/test_warmup.py pins zero compile-cache misses."""
        conf = self.conf
        windows = (
            conf.global_serve_window > 0
            or conf.local_batch_wait > 0
            or bool(conf.h2_fast_address)  # the native front's window
        )
        engine.warmup(max_width=4096 if windows else 1024)

    # ------------------------------------------------------------------

    def _start_discovery(self) -> None:
        """reference: daemon.go:185-220 (discovery selection switch)."""
        kind = self.conf.peer_discovery_type
        if kind == "none":
            if self.conf.static_peers:
                # Fixed-topology cluster (GUBER_STATIC_PEERS): the full
                # membership is configuration, not discovery.  set_peers
                # marks whichever entry matches our advertise address
                # as self.
                self.set_peers(
                    [
                        PeerInfo(
                            grpc_address=a,
                            http_address="",
                            datacenter=self.conf.data_center,
                        )
                        for a in self.conf.static_peers
                    ]
                )
            else:
                self.set_peers([self.peer_info()])
            return
        from gubernator_tpu.discovery import create_discovery

        self._discovery = create_discovery(self.conf, self)
        self._discovery.start()

    def peer_info(self) -> PeerInfo:
        advertise = resolve_advertise_address(
            self.grpc_address, self.conf.advertise_address
        )
        return PeerInfo(
            grpc_address=advertise,
            http_address=self.http_address,
            datacenter=self.conf.data_center,
        )

    def set_peers(self, peers: Sequence[PeerInfo]) -> None:
        """Mark ourselves in the list, then hand to the service.

        reference: daemon.go:370-380 (SetPeers).
        """
        me = self.peer_info()
        marked: List[PeerInfo] = []
        for p in peers:
            marked.append(
                PeerInfo(
                    grpc_address=p.grpc_address,
                    http_address=p.http_address,
                    datacenter=p.datacenter,
                    is_owner=p.grpc_address == me.grpc_address,
                )
            )
        if not any(p.is_owner for p in marked):
            me.is_owner = True
            marked.append(me)
        assert self.instance is not None
        self.instance.set_peers(marked)
        # New routing is live; now let the membership plane observe
        # the view — on a real change it bumps the epoch, opens the
        # dual-ring window, and ships moved buckets to their new
        # owners in the background (cluster/membership.py).
        if self.membership is not None:
            self.membership.apply_view(marked)

    # ------------------------------------------------------------------

    def wait_for_connect(self, timeout: float = 10.0) -> None:
        """Block until our own gRPC endpoint answers HealthCheck.

        reference: daemon.go:330-337, 398-437 (WaitForConnect).
        """
        from gubernator_tpu.net.pb import gubernator_pb2 as pb

        deadline = time.monotonic() + timeout
        creds = (
            self._tls_bundle.client_credentials() if self._tls_bundle else None
        )
        addr = self.grpc_address
        if addr.startswith("0.0.0.0:") or addr.startswith(":::"):
            addr = "127.0.0.1:" + addr.rpartition(":")[2]
        last_err: Optional[Exception] = None
        while time.monotonic() < deadline:
            try:
                channel = dial(addr, credentials=creds)
                V1Stub(channel).HealthCheck(pb.HealthCheckReq(), timeout=1.0)
                channel.close()
                return
            except grpc.RpcError as e:  # pragma: no cover - timing
                last_err = e
                time.sleep(0.05)
        raise TimeoutError(f"daemon at {addr} never became ready: {last_err}")

    def peer_health(self) -> dict:
        """This node's view of every peer's circuit state + transition
        counts (cluster/health.py) — the operator/bench entry for the
        same numbers /metrics exports as gubernator_peer_state and
        gubernator_circuit_transitions (bench artifacts embed it)."""
        assert self.instance is not None
        out = {}
        for p in self.instance.get_peer_list():
            if p.info.is_owner:
                continue
            out[p.info.grpc_address] = {
                "state": p.health.state(),
                "transitions": p.health.transition_counts(),
            }
        return out

    def membership_stats(self) -> dict:
        """This node's membership-plane view: epoch, phase
        (stable|dual), cumulative dual-window seconds, and handoff
        row counters — the same numbers /metrics exports as
        gubernator_membership_epoch / gubernator_handoff_keys /
        gubernator_ring_dual_window_seconds (bench artifacts embed
        it, like peer_health())."""
        if self.membership is None:
            return {}
        return self.membership.stats()

    def replication_stats(self) -> dict:
        """This node's hot-key replication view: promotion/demotion
        counters, grant traffic, credit accounting, and the live
        promoted/replica-lease key counts — the same numbers /metrics
        exports as gubernator_replication_* (bench artifacts embed
        it, like membership_stats())."""
        if self.replication is None:
            return {}
        return self.replication.stats()

    def multiregion_stats(self) -> dict:
        """This node's cross-region federation view: window/push
        counters, per-region sends and circuit states, the retry
        backlog, and the window-wait / region-RPC hop budget — the
        same numbers /metrics exports as gubernator_multiregion_*
        (bench artifacts embed it, like peer_health())."""
        if self.instance is None:
            return {}
        return self.instance.multi_region_mgr.stats()

    def fleet_stats(self, peers: bool = True) -> dict:
        """One cluster rollup from this node's vantage (obs/fleet.py)
        — the same merged view /debug/fleet and /metrics?fleet=1
        serve (bench artifacts embed it, like peer_health())."""
        if self.obs is None:
            return {}
        return self.obs.collect(peers=peers)

    def slo_status(self) -> dict:
        """The SLO watchdog's live view: declared SLIs, current burn
        rates, invariant headroom, and the bounded breach log — the
        same shape /debug/slo serves."""
        if self.slo is None:
            return {}
        return self.slo.status()

    def drain(self, deadline: Optional[float] = None) -> dict:
        """Planned leave: ship EVERY held bucket to its owner under
        the ring-without-self (cluster/membership.py), bounded by
        `deadline` seconds (default GUBER_DRAIN_DEADLINE).  Returns
        {"shipped", "forfeited", "targets"}; the caller then removes
        this node from the cluster (deregister / peer push) and calls
        close() — state first, then topology."""
        if self.membership is None:
            return {"shipped": 0, "forfeited": 0, "targets": 0}
        return self.membership.drain(deadline)

    def stage_budget(self) -> dict:
        """The measured GLOBAL-path latency budget on this node:
        per-stage {count, mean_ms, p50_ms, p99_ms, max_ms} for the
        five pipeline stages (client window wait, engine serve,
        hit-window wait, owner RPC, broadcast age).  p50/p99 are REAL
        streaming quantiles from DurationStat's histogram — earlier
        rounds advertised a "p50 budget" while reporting means, which
        is exactly how the lease-TTL-churn tail stayed hidden
        (PERF.md §23).  The same numbers /metrics exports as
        gubernator_stage_duration + gubernator_stage_quantile_seconds;
        /debug/vars serves them live."""
        assert self.instance is not None
        return {
            stage: stat.snapshot_ms()
            for stage, stat in self.instance.stage_timers.items()
        }

    def close(self) -> None:
        """Graceful stop. reference: daemon.go:342-367 (Close)."""
        if self._closed:
            return
        self._closed = True
        if getattr(self, "_sweep_stop", None) is not None:
            self._sweep_stop.set()
            # A sweep tick may be mid-flight inside engine.sweep();
            # join before tearing the engine down under it.
            self._sweeper.join(timeout=5.0)
        if self._discovery is not None:
            self._discovery.close()
        if self.membership is not None:
            # Join any in-flight epoch transition before tearing the
            # engine down under its snapshot/ship pass.
            self.membership.close()
        if self.replication is not None:
            # Demote what we promoted (returns replica credit while
            # peers are still up) and drop replica leases BEFORE the
            # native front frees the decision plane below.
            self.replication.close()
        if getattr(self, "slo", None) is not None:
            # Watchdog before the obs collector: a tick mid-teardown
            # must not fan out through a closed scrape pool.
            self.slo.close()
        if getattr(self, "obs", None) is not None:
            self.obs.close()
        if self.instance is not None and self.instance.native_events is not None:
            # Stop the drain thread BEFORE the front frees the ring
            # (single-consumer contract; a drain into a freed ring is
            # a use-after-free).  If the thread outlived the join,
            # leak the ring instead of freeing it.
            if not self.instance.native_events.close():
                if getattr(self, "h2_fast", None) is not None:
                    self.h2_fast.abandon_ring()
        if getattr(self, "h2_fast", None) is not None:
            self.h2_fast.close()
        if self.gateway is not None:
            self.gateway.close()
        if self.status_gateway is not None:
            self.status_gateway.close()
        if self.grpc_server is not None:
            self.grpc_server.stop(grace=1.0).wait()
        if self.instance is not None:
            if self._loader is not None:
                # Persist the cache on shutdown
                # (reference: gubernator.go:159-192 → Loader.Save).
                self.instance.engine.save(self._loader)
            self.instance.close()


def spawn_daemon(
    conf: DaemonConfig,
    *,
    clock: Clock = SYSTEM_CLOCK,
    engine=None,
    store=None,
    loader=None,
) -> Daemon:
    """Start a daemon and wait for readiness.

    reference: daemon.go:66-80 (SpawnDaemon).
    """
    d = Daemon(conf, clock=clock, engine=engine, store=store, loader=loader)
    d.start()
    d.wait_for_connect()
    return d
