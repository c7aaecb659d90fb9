"""Differential fuzz: the vectorized kernel must match the scalar spec.

`gubernator_tpu.models.spec.apply_spec` is the hand-checked transcription
of reference algorithms.go; the engine runs the same stream through the
device kernel.  Every response field must match exactly on every step.
"""

from __future__ import annotations

import random

from gubernator_tpu import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.clock import Clock
from gubernator_tpu.core.engine import DecisionEngine
from gubernator_tpu.gregorian import (
    GregorianError,
    gregorian_duration,
    gregorian_expiration,
)
from gubernator_tpu.models.spec import SlotState, SpecInput, apply_spec


class SpecShadow:
    """Scalar shadow state: key → SlotState, applied in arrival order."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.buckets: dict[str, SlotState] = {}

    def apply(self, reqs: list[RateLimitReq]):
        from gubernator_tpu.gregorian import dt_from_ms

        now = self.clock.now_ms()
        now_dt = dt_from_ms(now)
        outs = []
        for r in reqs:
            greg_dur = greg_exp = 0
            if int(r.behavior) & Behavior.DURATION_IS_GREGORIAN:
                try:
                    greg_dur = gregorian_duration(now_dt, r.duration)
                    greg_exp = gregorian_expiration(now_dt, r.duration)
                except GregorianError:
                    outs.append(None)  # engine returns an error response
                    continue
            inp = SpecInput(
                hits=r.hits,
                limit=r.limit,
                duration=r.duration,
                burst=r.burst,
                algorithm=int(r.algorithm),
                behavior=int(r.behavior),
                greg_duration=greg_dur,
                greg_expire=greg_exp,
            )
            key = r.hash_key()
            state, out = apply_spec(self.buckets.get(key), inp, now)
            if state is None:
                self.buckets.pop(key, None)
            else:
                self.buckets[key] = state
            outs.append(out)
        return outs


def _random_req(rng: random.Random, keys: list[str]) -> RateLimitReq:
    behavior = 0
    if rng.random() < 0.15:
        behavior |= Behavior.RESET_REMAINING
    duration = rng.choice([0, 1, 5, 100, 1000, 9000, 30000])
    if rng.random() < 0.2:
        behavior |= Behavior.DURATION_IS_GREGORIAN
        duration = rng.choice([0, 1, 2, 3, 4, 5])
    return RateLimitReq(
        name="fuzz",
        unique_key=rng.choice(keys),
        hits=rng.choice([-3, -1, 0, 1, 1, 1, 2, 5, 10, 100]),
        limit=rng.choice([0, 1, 2, 5, 10, 100]),
        duration=duration,
        algorithm=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
        behavior=behavior,
        burst=rng.choice([0, 0, 0, 5, 20]),
    )


def test_kernel_matches_spec_fuzz():
    rng = random.Random(1234)
    clock = Clock().freeze()
    engine = DecisionEngine(capacity=256, clock=clock)
    shadow = SpecShadow(clock)
    keys = [f"k{i}" for i in range(12)]

    for step in range(300):
        batch = [_random_req(rng, keys) for _ in range(rng.randint(1, 8))]
        got = engine.get_rate_limits(batch)
        want = shadow.apply(batch)
        for i, (g, w) in enumerate(zip(got, want)):
            ctx = f"step={step} i={i} req={batch[i]}"
            if w is None:
                assert g.error != "", ctx
                continue
            assert g.error == "", ctx
            assert int(g.status) == int(w.status), ctx
            assert g.limit == w.limit, ctx
            assert g.remaining == w.remaining, ctx
            assert g.reset_time == w.reset_time, ctx
        clock.advance(ms=rng.choice([0, 0, 1, 3, 7, 100, 1000, 40000]))


def test_kernel_matches_spec_single_key_long_stream():
    """Long sequential stream on one key — exercises state carry-over."""
    rng = random.Random(99)
    clock = Clock().freeze()
    engine = DecisionEngine(capacity=16, clock=clock)
    shadow = SpecShadow(clock)

    for step in range(400):
        batch = [_random_req(rng, ["solo"])]
        got = engine.get_rate_limits(batch)
        want = shadow.apply(batch)
        g, w = got[0], want[0]
        ctx = f"step={step} req={batch[0]}"
        if w is None:
            assert g.error != "", ctx
            continue
        assert int(g.status) == int(w.status), ctx
        assert g.remaining == w.remaining, ctx
        assert g.reset_time == w.reset_time, ctx
        clock.advance(ms=rng.choice([0, 1, 2, 500, 1500, 61000]))


def test_rate_and_truncation_helpers_match_the_reference():
    """`rate_int` (exact integer division) and `trunc_i64` (truncate
    in float first) exist because an accelerator's float64 is not IEEE
    double; on every backend they must equal Go's int64(float64(..))
    — the spec's `_trunc` — including the quotient a v5e got wrong
    (7 per 30 days) and negative operands (toward zero, not floor)."""
    import jax.numpy as jnp

    from gubernator_tpu.ops.bucket_kernel import rate_int, trunc_i64

    D = [2_592_000_000, 31_536_000_000, 1000, -5, 5, 0, 9000]
    L = [7, 7, 3, 2, 2, 5, 7]
    got = rate_int(jnp.asarray(D), jnp.asarray(L), jnp.asarray(True))
    assert got.tolist() == [int(d / l) for d, l in zip(D, L)]
    assert rate_int(jnp.asarray(D), jnp.asarray(L), jnp.asarray(False)).sum() == 0
    v = [3.9999999997671694, -3.9999999997671694, 1000.999999, 0.0, 5.0]
    assert trunc_i64(jnp.asarray(v)).tolist() == [int(x) for x in v]
