"""The `pb.gubernator.V1/GetRateLimits` wire format, as the benchmark
writes and reads it.

Requests are encoded by hand into bytes before the window, so that the
timed call sends pre-encoded payloads through identity serialisers.
Responses are kept as raw bytes and decoded after the window with
protobuf's own parser over a descriptor built here (field numbers from
upstream's `gubernator.proto`); nothing of the program is imported.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

METHOD = "/pb.gubernator.V1/GetRateLimits"


def varint(n: int) -> bytes:
    """Protobuf varint of a non-negative int."""
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field(tag: int, n: int) -> bytes:
    """A varint field, left out when it holds proto3's default 0."""
    return bytes([tag]) + varint(n) if n else b""


def item_suffix(hits: int, limit: int, duration: int, algorithm: int,
                behavior: int, burst: int) -> bytes:
    """Fields 3..8 of one RateLimitReq: everything after the two strings."""
    return (
        _field(0x18, hits) + _field(0x20, limit) + _field(0x28, duration)
        + _field(0x30, algorithm) + _field(0x38, behavior)
        + _field(0x40, burst)
    )


def name_prefix(name: str) -> bytes:
    raw = name.encode()
    return b"\x0a" + varint(len(raw)) + raw


def encode_item(prefix: bytes, unique_key: bytes, suffix: bytes) -> bytes:
    """One `requests` entry of GetRateLimitsReq (field 1, a submessage)."""
    body = prefix + b"\x12" + varint(len(unique_key)) + unique_key + suffix
    return b"\x0a" + varint(len(body)) + body


def encode_request(items: Sequence[Tuple[bytes, bytes, bytes]]) -> bytes:
    """GetRateLimitsReq bytes from (name prefix, unique_key, suffix) rows."""
    return b"".join(encode_item(p, k, s) for p, k, s in items)


_CLASSES = None


def _classes():
    """(GetRateLimitsReq, GetRateLimitsResp) over a private descriptor
    pool, so that they cannot collide with the program's generated
    module in one process."""
    global _CLASSES
    if _CLASSES is not None:
        return _CLASSES
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_gubernator.proto", package="benchwire", syntax="proto3"
    )

    def message(name, fields):
        msg = fd.message_type.add(name=name)
        for num, (fname, ftype) in enumerate(fields, start=1):
            msg.field.add(name=fname, number=num, type=ftype,
                          label=F.LABEL_OPTIONAL)

    message("RateLimitReq", (
        ("name", F.TYPE_STRING), ("unique_key", F.TYPE_STRING),
        ("hits", F.TYPE_INT64), ("limit", F.TYPE_INT64),
        ("duration", F.TYPE_INT64), ("algorithm", F.TYPE_INT32),
        ("behavior", F.TYPE_INT32), ("burst", F.TYPE_INT64),
    ))
    # field 6 of the response, the metadata map, is left unknown: the
    # parser skips it.
    message("RateLimitResp", (
        ("status", F.TYPE_INT32), ("limit", F.TYPE_INT64),
        ("remaining", F.TYPE_INT64), ("reset_time", F.TYPE_INT64),
        ("error", F.TYPE_STRING),
    ))
    for outer, field, inner in (
        ("GetRateLimitsReq", "requests", "RateLimitReq"),
        ("GetRateLimitsResp", "responses", "RateLimitResp"),
    ):
        fd.message_type.add(name=outer).field.add(
            name=field, number=1, type=F.TYPE_MESSAGE,
            label=F.LABEL_REPEATED, type_name=f".benchwire.{inner}",
        )
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    _CLASSES = tuple(
        message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"benchwire.{n}")
        )
        for n in ("GetRateLimitsReq", "GetRateLimitsResp")
    )
    return _CLASSES


def decode_request(raw: bytes) -> List:
    """The `requests` of one GetRateLimitsReq."""
    return _classes()[0].FromString(raw).requests


def decode_response(raw: bytes) -> List:
    """The `responses` of one GetRateLimitsResp: objects with .status,
    .limit, .remaining, .reset_time, .error."""
    return _classes()[1].FromString(raw).responses


def encode_response(rows: Sequence[Tuple[int, int, int, int]]) -> bytes:
    """GetRateLimitsResp bytes from (status, limit, remaining,
    reset_time) rows."""
    out = []
    for status, limit, remaining, reset_time in rows:
        body = (
            _field(0x08, status) + _field(0x10, limit)
            + _field(0x18, remaining) + _field(0x20, reset_time)
        )
        out.append(b"\x0a" + varint(len(body)) + body)
    return b"".join(out)
