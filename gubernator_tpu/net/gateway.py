"""HTTP/JSON gateway: REST facade over the service + /metrics.

reference: gubernator.pb.gw.go + daemon.go:222-268 — grpc-gateway v2
semantics: `POST /v1/GetRateLimits` and `GET /v1/HealthCheck` with
proto-JSON marshaling in snake_case (`UseProtoNames`), int64 as JSON
strings, enums as names; plus the prometheus `/metrics` endpoint and
`/healthz` for probes (reference: daemon.go:279-307 status listener).

Implemented directly on the service core (no loopback gRPC hop — the
reference only dials loopback because grpc-gateway needs a channel).
protobuf's own json_format does the marshaling, so the JSON contract is
byte-compatible with the reference gateway.
"""

from __future__ import annotations

import json
import shutil
import ssl
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from google.protobuf import json_format

from prometheus_client import generate_latest
from prometheus_client.registry import CollectorRegistry

from gubernator_tpu.net import serde
from gubernator_tpu.net.pb import gubernator_pb2 as pb
from gubernator_tpu.net.pb import peers_pb2 as peers_pb
from gubernator_tpu.service import ServiceError, V1Instance


# jax's profiler is one per process, so is this: a second capture
# (from any gateway of an in-process cluster) answers 409.
_PROFILE_LOCK = threading.Lock()
MAX_PROFILE_SECONDS = 30.0
# The last capture's directory (4-17 MB at 100 M rows): the next
# capture removes it, so the process leaves at most one behind.
_last_profile_dir: Optional[str] = None  # guarded by _PROFILE_LOCK


class _Handler(BaseHTTPRequestHandler):
    # Set by the server factory.
    instance: V1Instance
    registry: Optional[CollectorRegistry] = None
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _reply(self, code: int, body: bytes, content_type: str = "application/json"):
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_error(self, http_code: int, grpc_code: int, message: str):
        # grpc-gateway error shape: {"code": ..., "message": ...}.
        self._reply(
            http_code,
            json.dumps({"code": grpc_code, "message": message}).encode(),
        )

    def do_GET(self):  # noqa: N802 (stdlib naming)
        path, _, query = self.path.partition("?")
        if path == "/v1/HealthCheck" or path == "/healthz":
            resp = serde.health_check_resp_to_pb(self.instance.health_check())
            self._reply(
                200,
                json_format.MessageToJson(
                    resp,
                    preserving_proto_field_name=True,
                    always_print_fields_with_no_presence=True,
                ).encode(),
            )
        elif path == "/metrics" and self.registry is not None:
            self._serve_metrics(query)
        elif path == "/debug/trace":
            self._reply(200, json.dumps(self._debug_trace()).encode())
        elif path == "/debug/profile":
            self._debug_profile(query)
        elif path == "/debug/hotkeys":
            self._reply(200, json.dumps(self._debug_hotkeys()).encode())
        elif path == "/debug/vars":
            self._reply(200, json.dumps(self._debug_vars()).encode())
        elif path == "/debug/fleet":
            self._reply(200, json.dumps(self._debug_fleet()).encode())
        elif path == "/debug/slo":
            self._reply(200, json.dumps(self._debug_slo()).encode())
        else:
            self._reply_error(404, 5, "not found")

    def _serve_metrics(self, query: str) -> None:
        """The /metrics scrape, with two opt-in extensions:

        - ``?fleet=1`` appends the gubernator_fleet_* rollup families
          (one ObsSnapshot fan-out, merged — any node answers for the
          cluster);
        - ``?exemplars=1`` switches to the OpenMetrics exposition so
          the stage-histogram buckets carry their trace_id exemplars
          (the classic format has no exemplar syntax and drops them).
        """
        from urllib.parse import parse_qs

        qs = parse_qs(query)

        def _flag(name: str) -> bool:
            return (qs.get(name, ["0"])[0] or "0") not in ("0", "false")

        want_exemplars = _flag("exemplars")
        if want_exemplars:
            from prometheus_client.openmetrics.exposition import (
                generate_latest as om_generate_latest,
            )

            gen = om_generate_latest
            ctype = (
                "application/openmetrics-text; version=1.0.0; "
                "charset=utf-8"
            )
        else:
            gen = generate_latest
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        body = gen(self.registry)
        if _flag("fleet"):
            obs = getattr(self.instance, "obs", None)
            if obs is not None:
                from gubernator_tpu.utils.metrics import (
                    build_fleet_registry,
                )

                extra = gen(build_fleet_registry(obs.collect()))
                if want_exemplars and body.endswith(b"# EOF\n"):
                    # OpenMetrics ends every exposition with "# EOF";
                    # splicing two outputs keeps exactly one.
                    body = body[: -len(b"# EOF\n")]
                body += extra
        self._reply(200, body, content_type=ctype)

    # -- /debug fleet/SLO surface (obs/; OBSERVABILITY.md §§9-10) ------

    def _debug_fleet(self) -> dict:
        """One cluster rollup from this node's vantage: the merged
        counters/gauges/quantiles plus the SLO evaluation OVER that
        rollup (read-only — the on-demand view must not pollute the
        watchdog's periodic sample cadence)."""
        obs = getattr(self.instance, "obs", None)
        if obs is None:
            return {"enabled": False}
        rollup = obs.collect()
        out = {"enabled": True}
        out.update(rollup)
        wd = getattr(self.instance, "slo_watchdog", None)
        if wd is not None:
            # Windowed (ratio/drops) burns only when the watchdog's
            # recorded history shares this rollup's FLEET scope — a
            # local-slice history differenced against a fleet rollup
            # would report other nodes' lifetime totals as window
            # traffic (phantom breaches).  Quantile + invariant SLIs
            # always evaluate (no history needed).
            out["slo"] = wd.evaluate(
                rollup, record=False, windowed=wd.fleet_scope
            )
        return out

    def _debug_slo(self) -> dict:
        wd = getattr(self.instance, "slo_watchdog", None)
        if wd is None:
            return {"enabled": False}
        return wd.status()

    # -- /debug introspection surface (OBSERVABILITY.md) ---------------

    def _debug_trace(self) -> dict:
        """Tail flight recorder dump: the retained span trees of
        decisions that exceeded the adaptive threshold."""
        fr = getattr(self.instance, "flight_recorder", None)
        if fr is None:
            return {"enabled": False, "traces": []}
        out = fr.dump()
        out["enabled"] = True
        return out

    def _debug_profile(self, query: str) -> None:
        """``GET /debug/profile?seconds=N``: capture N (≤ 30) seconds
        of the jax profiler from inside the process that holds the
        chip, into a fresh temporary directory.  The stages' `work`
        annotations (utils/metrics.stage) land on the host plane of
        the same xplane as the device ops.  Answers with the directory
        and the /debug/vars `device` block read inside the capture at
        both ends, so what the device was given while the trace ran is
        known to the count.  One capture at a time: 409 while one
        runs.  A capture removes the directory of the one before it:
        copy what is to be kept before asking again."""
        global _last_profile_dir
        from urllib.parse import parse_qs

        try:
            seconds = float(parse_qs(query).get("seconds", ["3"])[0])
        except ValueError:
            seconds = -1.0
        if not 0 < seconds <= MAX_PROFILE_SECONDS:
            self._reply_error(
                400, 3, f"seconds must be in (0, {MAX_PROFILE_SECONDS:g}]"
            )
            return
        if not _PROFILE_LOCK.acquire(blocking=False):
            self._reply_error(409, 10, "a profile capture is running")
            return
        try:
            import jax

            from gubernator_tpu.core import device_info

            # As the benchmark's launcher captures: the host's Python
            # frames would fill the file; TraceMe level 2 keeps the
            # annotations and PJRT's own events.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            if _last_profile_dir is not None:
                shutil.rmtree(_last_profile_dir, ignore_errors=True)
            _last_profile_dir = tempfile.mkdtemp(prefix="guber-profile-")
            out = {"path": _last_profile_dir}
            jax.profiler.start_trace(out["path"], profiler_options=opts)
            try:
                out["t_start"] = time.time()
                start = device_info.describe(self.instance.engine)
                time.sleep(seconds)
                stop = device_info.describe(self.instance.engine)
                out["t_stop"] = time.time()
            finally:
                jax.profiler.stop_trace()
            out["device"] = {"start": start, "stop": stop}
        except Exception as e:  # noqa: BLE001 — reported to the caller
            self._reply_error(500, 13, f"{type(e).__name__}: {e}")
            return
        finally:
            _PROFILE_LOCK.release()
        self._reply(200, json.dumps(out).encode())

    def _debug_hotkeys(self) -> dict:
        hk = getattr(self.instance, "hotkeys", None)
        if hk is None:
            return {"enabled": False, "top": []}
        out = hk.stats()
        out["enabled"] = True
        out["top"] = [
            {
                "key": key.decode(errors="replace"),
                "count": count,
                "err": err,
            }
            for key, count, err in hk.top(50)
        ]
        return out

    def _debug_vars(self) -> dict:
        """One JSON snapshot of the node's live internals: counters,
        stage budget (real quantiles), ledger/native/ring stats, peer
        health, membership, and queue depths — the flight recorder's
        companion when attributing a tail."""
        inst = self.instance
        out: dict = {"counters": dict(inst.counters)}
        out["stage_budget"] = {
            stage: stat.snapshot_ms()
            for stage, stat in inst.stage_timers.items()
        }
        led = getattr(inst, "ledger", None)
        if led is not None:
            try:
                out["ledger"] = led.stats()
            except Exception:  # noqa: BLE001 — snapshot best-effort
                out["ledger"] = None
        ev = getattr(inst, "native_events", None)
        if ev is not None:
            out["native_events"] = ev.stats()
        front = getattr(inst, "h2_front", None)
        if front is not None:
            out["h2_front"] = front.debug_vars()
        out["peer_health"] = {}
        for p in inst.get_peer_list():
            if p.info.is_owner:
                continue
            out["peer_health"][p.info.grpc_address] = {
                "state": p.health.state(),
                "transitions": p.health.transition_counts(),
                "queue_length": p.queue_length(),
            }
        mem = getattr(inst, "membership", None)
        if mem is not None:
            try:
                out["membership"] = mem.stats()
            except Exception:  # noqa: BLE001 — snapshot best-effort
                out["membership"] = None
        out["handoff"] = dict(inst.handoff_counters)
        # PR 13/14 planes (hot-key replication, multi-region
        # federation): the same numbers /metrics exports as
        # gubernator_replication_* / gubernator_multiregion_*, in the
        # one-stop snapshot the other planes already had.
        repl = getattr(inst, "replication", None)
        if repl is not None:
            try:
                out["replication"] = repl.stats()
            except Exception:  # noqa: BLE001 — snapshot best-effort
                out["replication"] = None
        try:
            out["multiregion"] = inst.multi_region_mgr.stats()
        except Exception:  # noqa: BLE001 — snapshot best-effort
            out["multiregion"] = None
        out["global"] = {
            "hits_pending": inst.global_mgr._hits.pending(),
            "broadcasts_pending": inst.global_mgr._updates.pending(),
            "async_sends": inst.global_mgr.async_sends,
            "broadcasts": inst.global_mgr.broadcasts,
        }
        out["cache_size"] = inst.engine.cache_size()
        # What this node serves on: platform, device kind and count,
        # engine class, step form, pump/scan state and each compile
        # probe's verdict with its reason (core/device_info.py).
        from gubernator_tpu.core import device_info

        out["device"] = device_info.describe(inst.engine)
        return out

    def _read_json(self, msg):
        length = int(self.headers.get("Content-Length", "0"))
        body = self.rfile.read(length)
        return json_format.Parse(body or b"{}", msg, ignore_unknown_fields=True)

    def _reply_json(self, msg):
        self._reply(
            200,
            json_format.MessageToJson(
                msg,
                preserving_proto_field_name=True,
                always_print_fields_with_no_presence=True,
            ).encode(),
        )

    def do_POST(self):  # noqa: N802
        path = self.path.split("?", 1)[0]
        try:
            if path == "/v1/GetRateLimits":
                req = self._read_json(pb.GetRateLimitsReq())
                resps = self.instance.get_rate_limits(
                    [serde.rate_limit_req_from_pb(m) for m in req.requests]
                )
                self._reply_json(serde.get_rate_limits_resp_to_pb(resps))
            elif path == "/pb.gubernator.PeersV1/GetPeerRateLimits":
                # Peer-service REST routes: grpc-gateway's unbound-method
                # default paths (reference: peers.pb.gw.go:108-143).
                req = self._read_json(peers_pb.GetPeerRateLimitsReq())
                resps = self.instance.get_peer_rate_limits(
                    [serde.rate_limit_req_from_pb(m) for m in req.requests]
                )
                self._reply_json(serde.peer_rate_limits_resp_to_pb(resps))
            elif path == "/pb.gubernator.PeersV1/UpdatePeerGlobals":
                req = self._read_json(peers_pb.UpdatePeerGlobalsReq())
                self.instance.update_peer_globals(
                    [serde.update_peer_global_from_pb(g) for g in req.globals]
                )
                self._reply_json(peers_pb.UpdatePeerGlobalsResp())
            else:
                self._reply_error(404, 5, "not found")
        except json_format.ParseError as e:
            self._reply_error(400, 3, str(e))  # INVALID_ARGUMENT
        except ServiceError as e:
            self._reply_error(400, 11, str(e))  # OUT_OF_RANGE


class Gateway:
    """The HTTP listener (gateway + metrics + health probes)."""

    def __init__(
        self,
        instance: V1Instance,
        address: str,
        registry: Optional[CollectorRegistry] = None,
        *,
        ssl_context: Optional[ssl.SSLContext] = None,
        serve_metrics: bool = True,
    ):
        host, _, port = address.rpartition(":")
        handler = type(
            "BoundHandler",
            (_Handler,),
            {
                "instance": instance,
                "registry": registry if serve_metrics else None,
            },
        )
        self._server = ThreadingHTTPServer((host or "0.0.0.0", int(port)), handler)
        self._server.daemon_threads = True
        if ssl_context is not None:
            self._server.socket = ssl_context.wrap_socket(
                self._server.socket, server_side=True
            )
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"guber-gateway-{address}",
            daemon=True,
        )

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> None:
        self._thread.start()

    def close(self) -> None:
        self._server.shutdown()
        # serve_forever returns after shutdown(); reap the thread so
        # the socket close below never races a final accept.
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self._server.server_close()
