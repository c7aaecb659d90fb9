"""The benchmark's own copy of the plain reference.

One bucket's update as a pure function, `(state, input, now) ->
(state', output)`: upstream gubernator's `algorithms.go` token and
leaky buckets, quirks included (sticky OVER_LIMIT status, rejection
without consuming, leak applied only when a whole token leaked,
remaining held as 32.32 fixed point).  Copied from
`gubernator_tpu/models/spec.py` as PR 21 left it, so that a later
change to the program cannot move the yardstick; it imports nothing
of the program.  `tests/benchmark/test_traffic.py` checks that the
two files still agree on a seeded stream.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple



class Algorithm:
    TOKEN_BUCKET = 0
    LEAKY_BUCKET = 1


class Behavior:
    BATCHING = 0
    DURATION_IS_GREGORIAN = 4
    RESET_REMAINING = 8


class Status:
    UNDER_LIMIT = 0
    OVER_LIMIT = 1

# int64 truncation helper: Go's int64(float64) truncates toward zero.
def _trunc(x: float) -> int:
    return int(x)


def quantize_remf(x: float) -> float:
    """Quantize a leaky remaining to the kernel's 32.32 fixed point.

    The device persists `remaining_f` as (int32 whole, uint32 2^-32
    fraction) — see ops/bucket_kernel.py `split_remf` — so the spec
    quantizes identically to stay bit-equal with the kernel.  All
    arithmetic here is exact in float64 (power-of-two scalings)."""
    import math

    w = math.floor(x)
    wc = min(max(w, -(2.0**31)), 2.0**31 - 1)
    return wc + math.floor((x - w) * 2.0**32) / 2.0**32


@dataclass
class SlotState:
    """One key's bucket state — the SoA row (reference: store.go:29-43).

    `t0` is TokenBucketItem.CreatedAt for token buckets and
    LeakyBucketItem.UpdatedAt for leaky buckets.  `expire_at` is the
    cache item TTL (reference: cache.go:30-42 CacheItem.ExpireAt).
    """

    algorithm: int = Algorithm.TOKEN_BUCKET
    limit: int = 0
    remaining: int = 0  # token-bucket remaining (int64)
    remaining_f: float = 0.0  # leaky-bucket remaining (float64)
    duration: int = 0
    t0: int = 0
    expire_at: int = 0
    burst: int = 0
    status: int = Status.UNDER_LIMIT
    invalid_at: int = 0  # store-driven invalidation (reference: cache.go:37-41)


@dataclass
class SpecInput:
    """Per-request fields after host-side Gregorian precompute."""

    hits: int = 0
    limit: int = 0
    duration: int = 0
    burst: int = 0
    algorithm: int = Algorithm.TOKEN_BUCKET
    behavior: int = Behavior.BATCHING
    greg_duration: int = 0  # gregorian_duration(now, duration) when flag set
    greg_expire: int = 0  # gregorian_expiration(now, duration) when flag set


@dataclass
class SpecOutput:
    status: int = Status.UNDER_LIMIT
    limit: int = 0
    remaining: int = 0
    reset_time: int = 0


def _is_live(state: Optional[SlotState], now: int) -> bool:
    """Cache-hit check (reference: lrucache.go:112-138).

    An item is a miss once `expire_at < now` (strict) or once a non-zero
    `invalid_at < now`.
    """
    if state is None:
        return False
    if state.invalid_at != 0 and state.invalid_at < now:
        return False
    if state.expire_at < now:
        return False
    return True


def apply_spec(
    state: Optional[SlotState], inp: SpecInput, now: int
) -> Tuple[Optional[SlotState], SpecOutput]:
    """Apply one request to one slot. Returns (new_state, response).

    new_state None means the slot was removed (RESET_REMAINING on a live
    token bucket, reference: algorithms.go:83-97).
    """
    live = _is_live(state, now)
    if live and state.algorithm != inp.algorithm:
        # Client switched algorithms: remove + recreate
        # (reference: algorithms.go:104-117,333-345).
        live = False
    greg = bool(inp.behavior & Behavior.DURATION_IS_GREGORIAN)
    reset_flag = bool(inp.behavior & Behavior.RESET_REMAINING)

    if inp.algorithm == Algorithm.TOKEN_BUCKET:
        if live:
            return _token_existing(state, inp, now, greg, reset_flag)
        return _token_new(inp, now, greg)
    else:
        if live:
            return _leaky_existing(state, inp, now, greg, reset_flag)
        return _leaky_new(inp, now, greg)


# ---------------------------------------------------------------- token


def _token_existing(
    s: SlotState, r: SpecInput, now: int, greg: bool, reset_flag: bool
) -> Tuple[Optional[SlotState], SpecOutput]:
    """reference: algorithms.go:79-208"""
    if reset_flag:
        # Remove the item entirely (reference: algorithms.go:83-97).
        return None, SpecOutput(Status.UNDER_LIMIT, r.limit, r.limit, 0)

    # Limit change folds the delta into remaining (algorithms.go:120-129).
    rem0 = s.remaining
    if s.limit != r.limit:
        rem0 = max(s.remaining + (r.limit - s.limit), 0)
    limit = r.limit

    created = s.t0
    expire = s.expire_at
    rem_store = rem0

    # Response snapshot taken *before* any renewal (algorithms.go:131-136).
    resp_rem = rem0
    resp_status = s.status
    status_store = s.status

    duration = s.duration
    if s.duration != r.duration:
        # Duration change (algorithms.go:138-162).
        new_expire = r.greg_expire if greg else created + r.duration
        if new_expire <= now:
            # Renew the bucket.
            new_expire = now + r.duration
            created = now
            rem_store = limit
        expire = new_expire
        duration = r.duration

    out = SpecOutput(resp_status, limit, resp_rem, expire)

    if r.hits == 0:
        # Status query only (algorithms.go:173-176).
        pass
    elif resp_rem == 0 and r.hits > 0:
        # Already at the limit (checks the response snapshot;
        # algorithms.go:179-185).
        out = SpecOutput(Status.OVER_LIMIT, limit, resp_rem, expire)
        status_store = Status.OVER_LIMIT
    elif rem_store == r.hits:
        # Hits take the exact remainder (algorithms.go:188-193).
        rem_store = 0
        out = SpecOutput(resp_status, limit, 0, expire)
    elif r.hits > rem_store:
        # Over the limit: reject WITHOUT consuming (algorithms.go:195-202).
        out = SpecOutput(Status.OVER_LIMIT, limit, resp_rem, expire)
    else:
        rem_store = rem_store - r.hits
        out = SpecOutput(resp_status, limit, rem_store, expire)

    new_state = replace(
        s,
        limit=limit,
        remaining=rem_store,
        duration=duration,
        t0=created,
        expire_at=expire,
        status=status_store,
        invalid_at=0,
    )
    return new_state, out


def _token_new(
    r: SpecInput, now: int, greg: bool
) -> Tuple[SlotState, SpecOutput]:
    """reference: algorithms.go:215-272"""
    expire = r.greg_expire if greg else now + r.duration
    remaining = r.limit - r.hits
    status = Status.UNDER_LIMIT
    if r.hits > r.limit:
        # Over on creation: don't consume (algorithms.go:255-261);
        # stored status stays UNDER_LIMIT (zero value of t.Status).
        status = Status.OVER_LIMIT
        remaining = r.limit

    state = SlotState(
        algorithm=Algorithm.TOKEN_BUCKET,
        limit=r.limit,
        remaining=remaining,
        duration=r.duration,
        t0=now,
        expire_at=expire,
        status=Status.UNDER_LIMIT,
    )
    return state, SpecOutput(status, r.limit, remaining, expire)


# ---------------------------------------------------------------- leaky


def _leaky_existing(
    s: SlotState, r: SpecInput, now: int, greg: bool, reset_flag: bool
) -> Tuple[SlotState, SpecOutput]:
    """reference: algorithms.go:329-448"""
    burst = r.burst if r.burst != 0 else r.limit  # algorithms.go:285-287

    rem = s.remaining_f
    if reset_flag:
        rem = float(burst)  # algorithms.go:347-349

    s_burst = s.burst
    if s_burst != burst:
        # algorithms.go:352-357
        if burst > _trunc(rem):
            rem = float(burst)
        s_burst = burst

    limit = r.limit
    duration = r.duration
    if limit > 0:
        rate = float(duration) / float(limit)
    else:
        rate = float("inf")

    eff_duration = duration
    if greg:
        # algorithms.go:365-381
        rate = float(r.greg_duration) / float(limit) if limit > 0 else float("inf")
        eff_duration = r.greg_expire - now

    expire = s.expire_at
    if r.hits != 0:
        expire = now + eff_duration  # algorithms.go:383-385 UpdateExpiration

    # Leak (algorithms.go:387-398).  rate==0 (duration 0) divides by zero
    # in Go too: elapsed/0.0 = +Inf, which refills the bucket to burst.
    # A negative rate (negative duration) divides normally: negative
    # leak, which never applies.
    elapsed = now - s.t0
    if rate != 0:
        leak = float(elapsed) / rate
    else:
        leak = float("inf") if elapsed > 0 else 0.0
    t0 = s.t0
    if leak == float("inf"):
        rem = float(s_burst)
        t0 = now
    elif _trunc(leak) > 0:
        rem += leak
        t0 = now
    if _trunc(rem) > s_burst:
        rem = float(s_burst)

    rem_i = _trunc(rem)
    rate_i = _trunc(rate) if rate != float("inf") else 0
    reset = now + (limit - rem_i) * rate_i
    out = SpecOutput(Status.UNDER_LIMIT, limit, rem_i, reset)

    if rem_i == 0 and r.hits > 0:
        # algorithms.go:416-421 — no mutation of remaining.
        out = SpecOutput(Status.OVER_LIMIT, limit, rem_i, reset)
    elif rem_i == r.hits:
        # algorithms.go:423-429 (also reached for hits==0, rem==0).
        rem -= float(r.hits)
        out = SpecOutput(Status.UNDER_LIMIT, limit, 0, now + limit * rate_i)
    elif r.hits > rem_i:
        # algorithms.go:431-437 — reject without consuming.
        out = SpecOutput(Status.OVER_LIMIT, limit, rem_i, reset)
    elif r.hits == 0:
        pass  # algorithms.go:439-442
    else:
        rem -= float(r.hits)
        out_rem = _trunc(rem)
        out = SpecOutput(
            Status.UNDER_LIMIT, limit, out_rem, now + (limit - out_rem) * rate_i
        )

    new_state = replace(
        s,
        algorithm=Algorithm.LEAKY_BUCKET,
        limit=limit,
        duration=duration,  # raw request duration (algorithms.go:360)
        remaining_f=quantize_remf(rem),
        t0=t0,
        expire_at=expire,
        burst=s_burst,
        invalid_at=0,
    )
    return new_state, out


def _leaky_new(
    r: SpecInput, now: int, greg: bool
) -> Tuple[SlotState, SpecOutput]:
    """reference: algorithms.go:454-516"""
    burst = r.burst if r.burst != 0 else r.limit
    duration = r.duration
    if greg:
        duration = r.greg_expire - now  # algorithms.go:464-473
        rate = float(r.greg_duration) / float(r.limit) if r.limit > 0 else float("inf")
    else:
        rate = float(duration) / float(r.limit) if r.limit > 0 else float("inf")

    remaining = burst - r.hits
    rate_i = _trunc(rate) if rate != float("inf") else 0
    status = Status.UNDER_LIMIT
    rem_f = float(remaining)
    resp_rem = remaining
    if r.hits > burst:
        # algorithms.go:492-498
        status = Status.OVER_LIMIT
        resp_rem = 0
        rem_f = 0.0
    reset = now + (r.limit - resp_rem) * rate_i

    state = SlotState(
        algorithm=Algorithm.LEAKY_BUCKET,
        limit=r.limit,
        remaining_f=quantize_remf(rem_f),
        duration=duration,
        t0=now,
        expire_at=now + duration,
        burst=burst,
        status=Status.UNDER_LIMIT,
    )
    return state, SpecOutput(status, r.limit, resp_rem, reset)
