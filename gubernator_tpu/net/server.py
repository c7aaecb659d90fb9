"""gRPC servicer adapters: pb messages ↔ V1Instance.

The service core (gubernator_tpu.service) speaks dataclasses; these
adapters sit at the transport edge, converting once per RPC and mapping
ServiceError to gRPC status codes (the only RPC-level error the
contract allows — oversized batches; reference: gubernator.go:212-216).
"""

from __future__ import annotations

import contextlib

from typing import Optional, Tuple

import grpc
import numpy as np

from gubernator_tpu.net import serde
from gubernator_tpu.net.pb import gubernator_pb2 as pb
from gubernator_tpu.net.pb import peers_pb2 as peers_pb
from gubernator_tpu.service import ServiceError, V1Instance
from gubernator_tpu.types import MAX_BATCH_SIZE, Behavior

_CODE = {
    "OUT_OF_RANGE": grpc.StatusCode.OUT_OF_RANGE,
    "INVALID_ARGUMENT": grpc.StatusCode.INVALID_ARGUMENT,
    "INTERNAL": grpc.StatusCode.INTERNAL,
}

# Behaviors that need the dataclass path (defined next to the service
# core; the native wire codec shares the same mask).
from gubernator_tpu.service import COLUMNAR_DISQUALIFIERS as _COLUMNAR_DISQUALIFIERS  # noqa: E402


def _decode_columns(items) -> Optional[Tuple]:
    """One pass over the pb batch into numpy columns, or None if any
    item needs the dataclass path (special behavior or a field error).

    This skips dataclass materialization entirely for the common case —
    the decoded columns feed DecisionEngine.apply_columnar, the same
    program the wire route serves (reference hot path: gubernator.go:197-317).
    """
    n = len(items)
    if n == 0 or n > MAX_BATCH_SIZE:
        return None
    keys_str = [""] * n
    keys_bytes: list = [b""] * n
    algo = np.empty(n, dtype=np.int32)
    behavior = np.empty(n, dtype=np.int32)
    hits = np.empty(n, dtype=np.int64)
    limit = np.empty(n, dtype=np.int64)
    duration = np.empty(n, dtype=np.int64)
    burst = np.empty(n, dtype=np.int64)
    for i, m in enumerate(items):
        b = m.behavior
        if b & _COLUMNAR_DISQUALIFIERS:
            return None
        name = m.name
        uk = m.unique_key
        if not name or not uk:
            return None
        k = name + "_" + uk  # canonical hash key (reference: client.go:37-39)
        keys_str[i] = k
        keys_bytes[i] = k.encode()
        algo[i] = m.algorithm
        behavior[i] = b
        hits[i] = m.hits
        limit[i] = m.limit
        duration[i] = m.duration
        burst[i] = m.burst
    return keys_str, keys_bytes, algo, behavior, hits, limit, duration, burst


def _fill_rate_limit_resps(field, cols) -> None:
    """Fill a repeated RateLimitResp field from the engine's output
    columns."""
    status, limit, remaining, reset_time = cols
    for st, li, rem, rt in zip(
        status.tolist(), limit.tolist(), remaining.tolist(), reset_time.tolist()
    ):
        field.add(status=st, limit=li, remaining=rem, reset_time=rt)



def _handler_span(name: str, context):
    """Span for one inbound RPC, joined to the caller's trace via the
    ``traceparent`` metadata pair (utils/tracing) — a contextmanager
    that is free when tracing is off (one global check, no metadata
    read)."""
    from gubernator_tpu.utils import tracing

    if not tracing.active():
        return contextlib.nullcontext()
    return tracing.span(
        name,
        remote_parent=tracing.remote_parent_from_metadata(
            context.invocation_metadata()
        ),
    )


class GrpcV1Adapter:
    """Public service (reference: proto/gubernator.proto:27-45)."""

    def __init__(self, instance: V1Instance):
        self.instance = instance

    def GetRateLimits(self, request, context):
        with _handler_span("rpc.get_rate_limits", context):
            return self._get_rate_limits(request, context)

    def _get_rate_limits(self, request, context):
        # The method handler passes RAW request bytes (grpc_service
        # _unary_raw): the native codec path serves the whole RPC in
        # compiled code when it can.
        if isinstance(request, (bytes, memoryview)):
            out_raw = self.instance.serve_wire_bytes(request)
            if out_raw is not None:
                return out_raw
            try:
                request = pb.GetRateLimitsReq.FromString(request)
            except Exception:  # noqa: BLE001 — match the framework
                # deserializer's client-visible INTERNAL status.
                context.abort(
                    grpc.StatusCode.INTERNAL, "Exception deserializing request!"
                )
        cols = _decode_columns(request.requests)
        if cols is not None:
            keys_str, keys_bytes, *columns = cols
            out = self.instance.apply_columnar_local(keys_str, keys_bytes, *columns)
            if out is not None:
                resp = pb.GetRateLimitsResp()
                _fill_rate_limit_resps(resp.responses, out)
                return resp
        reqs = [serde.rate_limit_req_from_pb(m) for m in request.requests]
        try:
            resps = self.instance.get_rate_limits(reqs)
        except ServiceError as e:
            context.abort(_CODE.get(e.code, grpc.StatusCode.INTERNAL), str(e))
        return serde.get_rate_limits_resp_to_pb(resps)

    def HealthCheck(self, request, context):
        return serde.health_check_resp_to_pb(self.instance.health_check())


class GrpcPeersV1Adapter:
    """Peer-only service (reference: proto/peers.proto:28-34)."""

    def __init__(self, instance: V1Instance):
        self.instance = instance

    def GetPeerRateLimits(self, request, context):
        with _handler_span("rpc.get_peer_rate_limits", context):
            return self._get_peer_rate_limits(request, context)

    def _get_peer_rate_limits(self, request, context):
        # Owner side of a forwarded batch: answered authoritatively
        # (never re-forwarded), so no ownership check is needed.
        if isinstance(request, (bytes, memoryview)):
            out_raw = self.instance.serve_wire_bytes(
                request, check_ownership=False
            )
            if out_raw is not None:
                return out_raw
            try:
                request = peers_pb.GetPeerRateLimitsReq.FromString(request)
            except Exception:  # noqa: BLE001 — see GetRateLimits
                context.abort(
                    grpc.StatusCode.INTERNAL, "Exception deserializing request!"
                )
        cols = _decode_columns(request.requests)
        if cols is not None:
            keys_str, keys_bytes, *columns = cols
            out = self.instance.apply_columnar_local(
                keys_str, keys_bytes, *columns, check_ownership=False
            )
            if out is not None:
                resp = peers_pb.GetPeerRateLimitsResp()
                _fill_rate_limit_resps(resp.rate_limits, out)
                return resp
        reqs = [serde.rate_limit_req_from_pb(m) for m in request.requests]
        try:
            resps = self.instance.get_peer_rate_limits(reqs)
        except ServiceError as e:
            context.abort(_CODE.get(e.code, grpc.StatusCode.INTERNAL), str(e))
        return serde.peer_rate_limits_resp_to_pb(resps)

    def UpdatePeerGlobals(self, request, context):
        with _handler_span("rpc.update_peer_globals", context):
            return self._update_peer_globals(request, context)

    def _update_peer_globals(self, request, context):
        # Raw-bytes fast path: the broadcast plane is the cluster
        # tier's highest-rate message; decode straight into status-
        # cache columns (net/wire_codec.decode_globals).
        if isinstance(request, (bytes, memoryview)):
            from gubernator_tpu.net import wire_codec
            from gubernator_tpu.types import MAX_BATCH_SIZE

            dec = wire_codec.decode_globals(
                bytes(request), MAX_BATCH_SIZE
            )
            if dec is not None:
                self.instance.update_peer_globals_columns(dec)
                return b""  # empty UpdatePeerGlobalsResp
            try:
                request = peers_pb.UpdatePeerGlobalsReq.FromString(
                    bytes(request)
                )
            except Exception:  # noqa: BLE001 — see GetRateLimits
                context.abort(
                    grpc.StatusCode.INTERNAL,
                    "Exception deserializing request!",
                )
        self.instance.update_peer_globals(
            [serde.update_peer_global_from_pb(g) for g in request.globals]
        )
        return peers_pb.UpdatePeerGlobalsResp()

    def TransferBuckets(self, request, context):
        with _handler_span("rpc.transfer_buckets", context):
            return self._transfer_buckets(request, context)

    def _transfer_buckets(self, request, context):
        # Ownership handoff (cluster/handoff.py): restore a shipped
        # window of bucket rows into the local engine.  Raw JSON in,
        # empty response out.
        try:
            self.instance.receive_transfer(bytes(request))
        except (ValueError, KeyError, IndexError, TypeError) as e:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"malformed bucket transfer: {e}",
            )
        return b""

    def ReplicateKeys(self, request, context):
        with _handler_span("rpc.replicate_keys", context):
            return self._replicate_keys(request, context)

    def _replicate_keys(self, request, context):
        # Hot-key replication (cluster/replication.py): install/revoke
        # replica credit leases.  Raw JSON in, raw JSON out (the
        # response carries superseded leases' credit accounting for
        # the owner's reconciliation).
        try:
            return self.instance.receive_replication(bytes(request))
        except (ValueError, KeyError, IndexError, TypeError) as e:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"malformed replication message: {e}",
            )

    def ObsSnapshot(self, request, context):
        with _handler_span("rpc.obs_snapshot", context):
            # Fleet rollup scrape (obs/fleet.py): this node's metric
            # families as raw JSON.  The request body is empty by
            # contract; a node without the obs plane answers its
            # disabled shape so the collector can count it.
            return self.instance.obs_snapshot_raw()
