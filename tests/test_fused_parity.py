"""Step parity: the served step programs — `fused_step` and the mesh's
`local_packed_fused` — must be BIT-EQUAL to the scalar spec
(models/spec.py), driven directly in the packed layout, and so must
the engine and the ledger-fronted serve partition over it — token and
leaky buckets, duration-change renewal, and expiry boundaries included
(the test_ledger.py harness shape).

Also pins the ISSUE 10 acceptance invariant directly: a steady-state
decision batch runs as a SINGLE device dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gubernator_tpu.clock import Clock
from gubernator_tpu.core.engine import DecisionEngine, PackedKeys
from gubernator_tpu.models.spec import SlotState, SpecInput, apply_spec
from gubernator_tpu.ops import bucket_kernel as bk
from gubernator_tpu.parallel.mesh import make_mesh
from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine
from gubernator_tpu.types import Behavior

CAPACITY = 512


class StepShadow:
    """Drives a served step program directly: key → slot interning on
    the host, packed rounds through `step(state, pin)` — the exact
    serving layout, minus the engine plumbing.  `lead` is the mesh
    programs' leading shard axis on what the host packs (pin[None],
    pout[0]); the state is the flat column either way."""

    def __init__(self, step, lead: bool, width: int = 64):
        self.step = step
        self.lead = lead
        self.capacity = CAPACITY
        self.width = width
        self.state = bk.make_state(CAPACITY)
        self.slots: dict[bytes, int] = {}

    def _slot(self, key: bytes) -> int:
        s = self.slots.get(key)
        if s is None:
            s = len(self.slots)
            assert s < self.capacity
            self.slots[key] = s
        return s

    def apply(self, rows, now_ms: int):
        """rows: [(key, algo, behavior, hits, limit, duration, burst)]
        with unique keys (callers split duplicate keys into rounds).
        Returns [(status, limit, remaining, reset)] in row order."""
        m = len(rows)
        slot = np.asarray([self._slot(r[0]) for r in rows], np.int32)
        order = np.argsort(slot, kind="stable")
        cols = [np.asarray([r[j] for r in rows], np.int64) for j in range(1, 7)]
        buf = bk.pack_batch_host(
            self.width,
            now_ms,
            self.capacity,
            np.ascontiguousarray(slot[order]),
            *(c[order] for c in cols),
            np.zeros(m, np.int64),
            np.zeros(m, np.int64),
        )
        pin = jnp.asarray(buf[None] if self.lead else buf)
        self.state, pout = self.step(self.state, pin)
        pout = np.asarray(pout[0] if self.lead else pout)
        st, rem, rst = bk.unpack_out_host(pout, m)
        inv = np.empty(m, np.int64)
        inv[order] = np.arange(m)
        limits = cols[3]
        return [
            (int(st[inv[i]]), int(limits[i]), int(rem[inv[i]]), int(rst[inv[i]]))
            for i in range(m)
        ]


class SpecShadow:
    def __init__(self):
        self.states: dict[bytes, SlotState] = {}

    def apply(self, rows, now_ms: int):
        out = []
        for key, algo, behavior, hits, limit, duration, burst in rows:
            inp = SpecInput(
                hits=int(hits), limit=int(limit), duration=int(duration),
                burst=int(burst), algorithm=int(algo), behavior=int(behavior),
            )
            state, resp = apply_spec(self.states.get(key), inp, now_ms)
            if state is None:
                self.states.pop(key, None)
            else:
                self.states[key] = state
            out.append(
                (int(resp.status), int(resp.limit), int(resp.remaining),
                 int(resp.reset_time))
            )
        return out


def _rand_rows(rng, keys, n):
    rows = []
    for _ in range(n):
        key = rng.choice(keys)
        algo = int(rng.choice([0, 1]))
        behavior = 0
        if rng.random() < 0.1:
            behavior |= int(Behavior.RESET_REMAINING)
        rows.append(
            (
                key,
                algo,
                behavior,
                int(rng.choice([-2, 0, 1, 1, 1, 2, 5, 11])),
                int(rng.choice([0, 1, 3, 10, 50])),
                int(rng.choice([1, 40, 200, 1000])),
                int(rng.choice([0, 0, 0, 5, 20])),
            )
        )
    # Unique keys per kernel round (the engine's rounds invariant).
    seen, uniq = set(), []
    for r in rows:
        if r[0] in seen:
            continue
        seen.add(r[0])
        uniq.append(r)
    return uniq


@pytest.fixture(scope="module")
def mesh_packed_fused():
    """The mesh tier's packed step (`local_packed_fused` under
    shard_map) as a one-device mesh builds it."""
    engine = ShardedDecisionEngine(
        shard_capacity=CAPACITY, mesh=make_mesh(jax.devices()[:1])
    )
    return engine._packed_fused


@pytest.fixture(params=["fused_step", "mesh_packed_fused"])
def shadow(request):
    if request.param == "fused_step":
        return StepShadow(bk.fused_step, lead=False)
    return StepShadow(request.getfixturevalue(request.param), lead=True)


def test_step_bit_equal_to_spec_fuzz(shadow):
    """Token + leaky fuzz across advancing time: every response field
    of the step program equals the scalar spec, including expiry
    boundaries crossed by the clock advances."""
    rng = np.random.default_rng(11)
    oracle = SpecShadow()
    keys = [b"fz_%d" % i for i in range(24)]
    now = 1_000_000
    for step in range(120):
        now += int(rng.integers(0, 120))  # crosses 40/200/1000ms expiries
        rows = _rand_rows(rng, keys, int(rng.integers(1, 16)))
        got = shadow.apply(rows, now)
        want = oracle.apply(rows, now)
        assert got == want, f"step {step} now={now}: {rows}"


def test_step_duration_change_renewal_boundary(shadow):
    """The duration-change renewal quirk (stored remaining becomes
    limit, response reports the pre-renewal snapshot — spec docstring)
    must hold bit-for-bit through the step program, on both sides of
    the `new_expire <= now` boundary."""
    oracle = SpecShadow()
    now = 50_000
    key = b"renew"
    for rows, dt in [
        ([(key, 0, 0, 3, 10, 100, 0)], 0),     # create, expire=now+100
        ([(key, 0, 0, 1, 10, 100, 0)], 40),    # consume inside window
        ([(key, 0, 0, 1, 10, 70, 0)], 0),      # dur change, not renewed
        ([(key, 0, 0, 1, 10, 100, 0)], 65),    # back; still live
        ([(key, 0, 0, 1, 10, 30, 0)], 0),      # dur change → renewal
        ([(key, 0, 0, 0, 10, 30, 0)], 0),      # query the renewed bucket
    ]:
        now += dt
        assert shadow.apply(rows, now) == oracle.apply(rows, now), (
            rows, now,
        )


def test_step_expiry_boundary_exact(shadow):
    """`expire_at < now` is a strict miss; equality still serves the
    item (lrucache.go semantics) — pinned at the exact millisecond."""
    oracle = SpecShadow()
    key = b"edge"
    base = 10_000
    assert shadow.apply([(key, 0, 0, 2, 5, 100, 0)], base) == oracle.apply(
        [(key, 0, 0, 2, 5, 100, 0)], base
    )
    for now in (base + 100, base + 101):  # at expiry, one past it
        rows = [(key, 0, 0, 1, 5, 100, 0)]
        assert shadow.apply(rows, now) == oracle.apply(rows, now), now


def test_step_leaky_fractional_leak_parity(shadow):
    """Leaky buckets accrue fractional leak by leaving t0 untouched
    (the TestLeakyBucketDivBug quirk) — the 32.32 fixed-point path
    through the step must track the spec's quantization exactly."""
    oracle = SpecShadow()
    key = b"leak"
    now = 77_000
    rows = [(key, 1, 0, 3, 7, 700, 0)]
    assert shadow.apply(rows, now) == oracle.apply(rows, now)
    for dt in (30, 30, 30, 110, 1, 49, 1000):
        now += dt
        rows = [(key, 1, 0, 1, 7, 700, 0)]
        assert shadow.apply(rows, now) == oracle.apply(rows, now), now


def _ledger_harness(clock):
    from gubernator_tpu.core.ledger import DecisionLedger
    from gubernator_tpu.hashing import fnv1a_64

    class _Dec:
        __slots__ = (
            "n", "key_buf", "key_offsets", "algo", "behavior", "hits",
            "limit", "duration", "burst", "fnv1a",
        )

    def make_dec(rows):
        d = _Dec()
        keys = [r[0] for r in rows]
        d.n = len(rows)
        d.key_buf = np.frombuffer(
            b"".join(keys) or b"\0", dtype=np.uint8
        )
        off = np.zeros(len(rows) + 1, dtype=np.int64)
        np.cumsum([len(k) for k in keys], out=off[1:])
        d.key_offsets = off
        for j, name in enumerate(
            ("algo", "behavior", "hits", "limit", "duration", "burst")
        ):
            setattr(
                d, name,
                np.asarray([r[j + 1] for r in rows],
                           np.int32 if j < 2 else np.int64),
            )
        d.fnv1a = np.asarray([fnv1a_64(k) for k in keys], np.uint64)
        return d

    engine = DecisionEngine(capacity=2048, clock=clock)
    ledger = DecisionLedger(engine, settle_interval=0, lease_size=4)

    def serve(rows):
        now = clock.now_ms()
        plan = ledger.plan(make_dec(rows), now)
        if plan.full:
            st, lim, rem, rst = plan.dense_cols()
        else:
            lane = plan.build_engine_lane()
            st, lim, rem, rst = engine.apply_columnar(
                PackedKeys(lane.key_buf, lane.key_offsets, lane.n),
                lane.algo, lane.behavior, lane.hits, lane.limit,
                lane.duration, lane.burst, now_ms=now,
            )
            plan.learn(st, lim, rem, rst)
            st, _lim, rem, rst = plan.merge_outputs(st, rem, rst)
        return st, rem, rst

    return engine, ledger, serve


@pytest.mark.parametrize("seed", [3, 19])
def test_engine_vs_spec_vs_ledger_three_way(seed, monkeypatch):
    """The three-tier pin: the engine's step, the host ledger's
    answers through that engine, and the scalar spec all agree row
    for row — token AND leaky, across duration changes and
    expiries."""
    monkeypatch.setenv("GUBER_PUMP", "0")
    rng = np.random.default_rng(seed)
    clock = Clock().freeze()
    engine, ledger, serve = _ledger_harness(clock)
    oracle = SpecShadow()
    keys = [b"led_%d" % i for i in range(10)]
    try:
        for step in range(60):
            clock.advance(ms=int(rng.integers(0, 60)))
            rows = []
            for _ in range(int(rng.integers(1, 8))):
                key = keys[int(rng.integers(0, len(keys)))]
                algo = int(key[-1] % 2)  # algo is a property of the key
                rows.append(
                    (
                        key, algo, 0,
                        int(rng.choice([0, 1, 1, 2, 4])),
                        int(rng.choice([2, 5, 9])),
                        int(rng.choice([40, 90, 400])),
                        0,
                    )
                )
            st, rem, rst = serve(rows)
            now = clock.now_ms()
            want = oracle.apply(rows, now)
            for i, (es, _el, er, et) in enumerate(want):
                got = (int(st[i]), int(rem[i]), int(rst[i]))
                assert got == (es, er, et), (
                    f"seed {seed} step {step} row {i} {rows[i]}: "
                    f"ledger+engine={got} spec={(es, er, et)}"
                )
    finally:
        ledger.close()


@pytest.mark.parametrize("seed", [7])
def test_paged_vs_spec_vs_ledger_three_way(seed, monkeypatch):
    """The three-way harness with the PAGED plane underneath
    (GUBER_PAGED, core/paging.py): ledger-fronted answers through a
    paged engine squeezed to 64 resident rows under a
    2048-slot key space still match the scalar spec row for row —
    eviction→spill→refill roundtrips land mid-fuzz (asserted via the
    fault counters), so residency is exercised, not incidental."""
    monkeypatch.setenv("GUBER_PUMP", "0")
    monkeypatch.setenv("GUBER_PAGED", "1")
    monkeypatch.setenv("GUBER_PAGE_SIZE", "16")
    monkeypatch.setenv("GUBER_PAGED_RESIDENT", "4")
    rng = np.random.default_rng(seed)
    clock = Clock().freeze()
    engine, ledger, serve = _ledger_harness(clock)
    assert engine.paging is not None
    assert engine.capacity == 64 and engine.logical_capacity == 2048
    oracle = SpecShadow()
    # 7x more keys than resident rows: cold keys keep faulting pages.
    keys = [b"pgl_%d" % i for i in range(420)]
    try:
        for step in range(60):
            clock.advance(ms=int(rng.integers(0, 60)))
            rows = []
            for _ in range(int(rng.integers(1, 8))):
                key = keys[int(rng.integers(0, len(keys)))]
                algo = int(key[-1] % 2)
                rows.append(
                    (
                        key, algo, 0,
                        int(rng.choice([0, 1, 1, 2, 4])),
                        int(rng.choice([2, 5, 9])),
                        int(rng.choice([40, 90, 400])),
                        0,
                    )
                )
            st, rem, rst = serve(rows)
            now = clock.now_ms()
            want = oracle.apply(rows, now)
            for i, (es, _el, er, et) in enumerate(want):
                got = (int(st[i]), int(rem[i]), int(rst[i]))
                assert got == (es, er, et), (
                    f"seed {seed} step {step} row {i} {rows[i]}: "
                    f"ledger+paged={got} spec={(es, er, et)}"
                )
        assert engine.paging.faults > 0 and engine.paging.spills > 0
    finally:
        ledger.close()


def test_fused_steady_state_is_single_dispatch(monkeypatch):
    """ISSUE 10 acceptance: in steady state one batch = ONE device
    dispatch (unique keys, no evictions)."""
    monkeypatch.setenv("GUBER_PUMP", "0")
    clock = Clock().freeze()
    engine = DecisionEngine(capacity=4096, clock=clock)

    def batch(engine, start, n=100):
        return engine.apply_columnar(
            [b"sd_%d" % i for i in range(start, start + n)],
            np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 10, np.int64),
            np.full(n, 60_000, np.int64), np.zeros(n, np.int64),
        )

    batch(engine, 0)  # first contact interns + compiles
    before = engine.dispatches_total
    batch(engine, 0)  # steady state: same keys, no evictions
    assert engine.dispatches_total - before == 1
    before = engine.dispatches_total
    batch(engine, 200)  # new keys, capacity ample: still one dispatch
    assert engine.dispatches_total - before == 1


def test_engine_serves_wire_shapes_equal_to_spec(monkeypatch):
    """The ordinary columnar and dataclass paths at 150 lanes (two pad
    widths past the 64 floor, token and leaky mixed: the packed
    program, not the uniform one) answer as the scalar spec does
    (integration: packers, rounds, readback all route through the
    step)."""
    from gubernator_tpu.types import RateLimitReq

    monkeypatch.setenv("GUBER_PUMP", "0")
    clock = Clock().freeze()
    columnar = DecisionEngine(capacity=1024, clock=clock)
    dataclass = DecisionEngine(capacity=1024, clock=clock)
    oracle = SpecShadow()
    n = 150
    algo = [i % 2 for i in range(n)]
    cols = dict(
        algo=np.asarray(algo, np.int32),
        behavior=np.zeros(n, np.int32),
        hits=np.ones(n, np.int64),
        limit=np.full(n, 7, np.int64),
        duration=np.full(n, 2_000, np.int64),
        burst=np.zeros(n, np.int64),
    )
    for step in range(4):
        clock.advance(ms=700)
        now = clock.now_ms()
        keys = [b"w_%d!%d" % (i % 90, i) for i in range(n)]
        want = oracle.apply(
            [(k, algo[i], 0, 1, 7, 2_000, 0) for i, k in enumerate(keys)],
            now,
        )
        st, lim, rem, rst = columnar.apply_columnar(keys, now_ms=now, **cols)
        got = list(zip(st.tolist(), lim.tolist(), rem.tolist(), rst.tolist()))
        assert got == want, f"columnar, step {step}"
        resps = dataclass.get_rate_limits(
            [
                RateLimitReq(
                    name="w", unique_key=k.decode(), hits=1, limit=7,
                    duration=2_000, algorithm=algo[i],
                )
                for i, k in enumerate(keys)
            ],
            now_ms=now,
        )
        # The dataclass path keys by name + "_" + unique_key: other
        # buckets than the columnar engine's, the same sequence.
        got = [
            (int(r.status), r.limit, r.remaining, r.reset_time)
            for r in resps
        ]
        assert got == want, f"dataclass, step {step}"
