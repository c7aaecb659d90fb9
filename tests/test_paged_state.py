"""Paged device bucket state (GUBER_PAGED, core/paging.py): the page
table + LRU host spill plane must be INVISIBLE to decisions.

The pins:
- dense vs paged fuzz: a paged engine squeezed to a fraction of its
  key space resident answers bit-equal to a dense engine AND the
  scalar spec (models/spec.py), across token/leaky, pad widths, and
  TTL expiries — while actually faulting (the harness asserts the
  fault counters moved, so the parity is not vacuous);
- eviction→spill→refill roundtrips are bit-exact at exact TTL/reset
  boundaries, including the leaky 32.32 fixed-point remaining;
- restore is page-aware: a bulk load of a key space far larger than
  the resident frames writes cold pages host-side and faults NOTHING
  (the core/engine.py bulk-load small fix);
- oversized batches segment by unique-key working set instead of
  blowing the frame budget;
- the host-side TTL sweep frees cold expired slots without faulting
  their pages back in.
"""

from __future__ import annotations

import numpy as np
import pytest

from gubernator_tpu.clock import Clock
from gubernator_tpu.core.engine import DecisionEngine
from gubernator_tpu.models.spec import SlotState, SpecInput, apply_spec
from gubernator_tpu.types import RateLimitReq, Status


def _paged_env(monkeypatch, page_size=16, resident=4):
    monkeypatch.setenv("GUBER_PUMP", "0")
    monkeypatch.setenv("GUBER_PAGED", "1")
    monkeypatch.setenv("GUBER_PAGE_SIZE", str(page_size))
    monkeypatch.setenv("GUBER_PAGED_RESIDENT", str(resident))


def _dense_env(monkeypatch):
    monkeypatch.setenv("GUBER_PUMP", "0")
    monkeypatch.delenv("GUBER_PAGED", raising=False)


class _SpecOracle:
    def __init__(self):
        self.states: dict[bytes, SlotState] = {}

    def apply(self, rows, now_ms):
        out = []
        for key, algo, behavior, hits, limit, duration, burst in rows:
            inp = SpecInput(
                hits=int(hits), limit=int(limit), duration=int(duration),
                burst=int(burst), algorithm=int(algo),
                behavior=int(behavior),
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


def _columnar(engine, rows, now_ms):
    n = len(rows)
    res = engine.apply_columnar(
        [r[0] for r in rows],
        np.asarray([r[1] for r in rows], np.int32),
        np.asarray([r[2] for r in rows], np.int32),
        np.asarray([r[3] for r in rows], np.int64),
        np.asarray([r[4] for r in rows], np.int64),
        np.asarray([r[5] for r in rows], np.int64),
        np.asarray([r[6] for r in rows], np.int64),
        now_ms=now_ms,
    )
    st, lim, rem, rst = res
    return [
        (int(st[i]), int(lim[i]), int(rem[i]), int(rst[i]))
        for i in range(n)
    ]


@pytest.mark.parametrize("seed", [5, 23])
def test_dense_vs_paged_vs_spec_fuzz(seed, monkeypatch):
    """Token + leaky fuzz over a key space ~6x the resident rows:
    paged == dense == spec on every response field, across advancing
    time (TTL expiries crossed) and pad widths — and the paged arm
    really pages (fault/spill counters move)."""
    rng = np.random.default_rng(seed)
    clock = Clock().freeze()
    _paged_env(monkeypatch)
    paged = DecisionEngine(capacity=1024, clock=clock)
    _dense_env(monkeypatch)
    dense = DecisionEngine(capacity=1024, clock=clock)
    assert paged.paging is not None and dense.paging is None
    assert paged.capacity == 64 and paged.logical_capacity == 1024
    oracle = _SpecOracle()

    keys = [b"pz_%d" % i for i in range(380)]
    for step in range(50):
        clock.advance(ms=int(rng.integers(0, 120)))
        now = clock.now_ms()
        nrows = int(rng.integers(1, 24))
        rows = []
        for _ in range(nrows):
            key = keys[int(rng.integers(0, len(keys)))]
            rows.append(
                (
                    key,
                    int(key[-1] % 2),  # algo is a property of the key
                    0,
                    int(rng.choice([-1, 0, 1, 1, 2, 5])),
                    int(rng.choice([1, 3, 10, 50])),
                    int(rng.choice([40, 200, 1000])),
                    int(rng.choice([0, 0, 5])),
                )
            )
        got_p = _columnar(paged, rows, now)
        got_d = _columnar(dense, rows, now)
        want = oracle.apply(rows, now)
        assert got_p == want, f"paged vs spec, step {step}: {rows}"
        assert got_d == want, f"dense vs spec, step {step}: {rows}"
    # The parity must not be vacuous: the key space (380) is ~6x the
    # resident rows (64), so the paged arm must have faulted.
    assert paged.paging.faults > 0
    assert paged.paging.spills > 0
    assert paged.paging.refills == paged.paging.faults


def test_spill_refill_roundtrip_exact_ttl_boundary(monkeypatch):
    """Evict→spill→refill must preserve the bucket bit-exactly across
    the residency roundtrip: re-hit at expire_at (equality serves) and
    at expire_at+1 (strict miss → fresh bucket), matching the spec on
    both sides of the boundary.  Leaky included — the 32.32 fractional
    words survive the raw-word spill."""
    clock = Clock().freeze()
    _paged_env(monkeypatch, page_size=16, resident=2)
    eng = DecisionEngine(capacity=512, clock=clock)
    oracle = _SpecOracle()
    now = clock.now_ms()

    tok = [(b"tok", 0, 0, 3, 10, 5_000, 0)]
    lky = [(b"lky", 1, 0, 3, 7, 700, 0)]
    assert _columnar(eng, tok, now) == oracle.apply(tok, now)
    clock.advance(ms=33)  # leaky fractional leak accrues mid-window
    now = clock.now_ms()
    assert _columnar(eng, lky, now) == oracle.apply(lky, now)

    # Flush both pages out through cold traffic (2 resident frames,
    # 16-row pages: 3 pages of strangers evict everything).
    before = eng.paging.spills
    for i in range(60):
        rows = [(b"cold_%d" % i, 0, 0, 1, 5, 60_000, 0)]
        now = clock.now_ms()
        assert _columnar(eng, rows, now) == oracle.apply(rows, now)
    assert eng.paging.spills > before
    assert not eng.paging.is_resident(0)  # the first page went cold

    # Refill at an exact boundary: leaky first (the fractional-words
    # pin), then the token bucket at expire_at and one past it.
    clock.advance(ms=44)
    now = clock.now_ms()
    lrows = [(b"lky", 1, 0, 1, 7, 700, 0)]
    assert _columnar(eng, lrows, now) == oracle.apply(lrows, now)

    exp = oracle.states[b"tok"].expire_at
    clock.advance(ms=exp - clock.now_ms())
    now = clock.now_ms()
    trows = [(b"tok", 0, 0, 1, 10, 5_000, 0)]
    assert _columnar(eng, trows, now) == oracle.apply(trows, now)
    clock.advance(ms=1)
    now = clock.now_ms()
    assert _columnar(eng, trows, now) == oracle.apply(trows, now)


def test_dataclass_path_pages_and_matches_dense(monkeypatch):
    """The dataclass serve path (get_rate_limits) through a paged
    engine answers exactly like a dense engine over a key space well
    past the resident rows."""
    clock = Clock().freeze()
    _paged_env(monkeypatch)
    paged = DecisionEngine(capacity=1024, clock=clock)
    _dense_env(monkeypatch)
    dense = DecisionEngine(capacity=1024, clock=clock)

    def reqs(lo, hi):
        return [
            RateLimitReq(
                name="dp", unique_key=str(i), hits=1, limit=4,
                duration=30_000,
            )
            for i in range(lo, hi)
        ]

    for _round in range(3):
        for lo in range(0, 300, 50):
            clock.advance(ms=7)
            now = clock.now_ms()
            rp = paged.get_rate_limits(reqs(lo, lo + 50), now_ms=now)
            rd = dense.get_rate_limits(reqs(lo, lo + 50), now_ms=now)
            for a, b in zip(rp, rd):
                assert (a.status, a.limit, a.remaining, a.reset_time) == (
                    b.status, b.limit, b.remaining, b.reset_time,
                )
    assert paged.paging.faults > 0


def test_oversized_batch_segments_by_working_set(monkeypatch):
    """One batch with more unique keys than the device can hold
    resident splits into sequential segments — answers stay exact and
    arrival-ordered (duplicate keys count their earlier segments)."""
    clock = Clock().freeze()
    _paged_env(monkeypatch, page_size=16, resident=2)  # 32 device rows
    eng = DecisionEngine(capacity=2048, clock=clock)
    oracle = _SpecOracle()
    now = clock.now_ms()

    # 200 unique keys + a straggler duplicate of key 0 at the end:
    # its hit must see the segment-1 debit (sequential semantics
    # across the segment boundary).
    rows = [(b"seg_%d" % i, 0, 0, 1, 10, 60_000, 0) for i in range(200)]
    rows.append((b"seg_0", 0, 0, 1, 10, 60_000, 0))
    assert _columnar(eng, rows, now) == oracle.apply(rows, now)

    # Same shape through the dataclass path.
    reqs = [
        RateLimitReq(
            name="seg2", unique_key=str(i % 150), hits=1, limit=9,
            duration=60_000,
        )
        for i in range(160)
    ]
    got = eng.get_rate_limits(reqs, now_ms=now)
    rows2 = [
        (b"r2_%d" % (i % 150), 0, 0, 1, 9, 60_000, 0) for i in range(160)
    ]
    want = oracle.apply(rows2, now)
    for g, (ws, _wl, wr, wt) in zip(got, want):
        assert (int(g.status), g.remaining, g.reset_time) == (ws, wr, wt)


def test_restore_is_page_aware_no_fault_storm(monkeypatch):
    """Bulk restore (engine.load) of a key space ≫ resident frames
    writes cold pages straight into the host store: ZERO page faults
    during the load, and the restored buckets answer exactly after a
    (counted) fault on first traffic.  The export side roundtrips the
    same rows, cold pages included."""
    clock = Clock().freeze()
    _paged_env(monkeypatch)
    src = DecisionEngine(capacity=1024, clock=clock)
    now = clock.now_ms()

    # Populate 300 keys with distinct consumption, then snapshot.
    rows = [
        (b"rst_%d" % i, i % 2, 0, 1 + i % 3, 10, 600_000, 0)
        for i in range(300)
    ]
    _columnar(src, rows, now)
    items = list(src.export_items())
    assert len(items) == 300

    class _Loader:
        def load(self):
            return iter(items)

        def save(self, it):
            raise AssertionError("unused")

    dst = DecisionEngine(capacity=1024, clock=clock)
    assert dst.load(_Loader()) == 300
    assert dst.paging.faults == 0, (
        "page-aware restore must not fault the key space through the "
        "resident frames"
    )

    # Restored state is exact: a fresh export matches the source's,
    # and a query (hits=0) on a cold restored key reports the restored
    # remaining after one counted fault.
    src_by_key = {
        it.key: it.value.remaining for it in items if it.value is not None
    }
    probe = [(b"rst_7", 1, 0, 0, 10, 600_000, 0),
             (b"rst_8", 0, 0, 0, 10, 600_000, 0)]
    got = _columnar(dst, probe, clock.now_ms())
    assert got[1][2] == src_by_key["rst_8"]
    assert dst.paging.faults >= 1

    out = {it.key for it in dst.export_items()}
    assert out == set(src_by_key)


def test_host_sweep_frees_cold_pages_without_faults(monkeypatch):
    """TTL sweep: expired buckets on NON-resident pages free from the
    host words alone — slots return to the intern table, fault count
    stays flat."""
    clock = Clock().freeze()
    _paged_env(monkeypatch, page_size=16, resident=2)
    eng = DecisionEngine(capacity=512, clock=clock)
    now = clock.now_ms()
    rows = [(b"sw_%d" % i, 0, 0, 1, 5, 1_000, 0) for i in range(96)]
    assert len(_columnar(eng, rows, now)) == 96
    assert len(eng.paging.nonresident_used_pages()) > 0

    faults_before = eng.paging.faults
    clock.advance(ms=60_000)
    freed = eng.sweep(now_ms=clock.now_ms())
    assert freed == 96
    assert eng.paging.faults == faults_before
    assert list(eng.export_items()) == []


def test_resident_only_traffic_never_faults(monkeypatch):
    """The A/B contract the bench leans on: a working set inside the
    resident frames pays zero faults after first contact — the paged
    plane is pure overhead-free indexing for resident traffic."""
    clock = Clock().freeze()
    _paged_env(monkeypatch, page_size=16, resident=4)  # 64 rows
    eng = DecisionEngine(capacity=1024, clock=clock)
    rows = [(b"hot_%d" % i, 0, 0, 1, 1000, 600_000, 0) for i in range(48)]
    _columnar(eng, rows, clock.now_ms())
    base = eng.paging.faults
    for _ in range(10):
        clock.advance(ms=5)
        _columnar(eng, rows, clock.now_ms())
    assert eng.paging.faults == base


def test_paged_knob_defaults_and_validation(monkeypatch):
    """GUBER_PAGE_SIZE rejects non-pow2/<16 by falling back to the
    default; GUBER_PAGED_RESIDENT=0 keeps every page resident (paged
    indexing, no spill possible)."""
    from gubernator_tpu.config import env_page_size, env_paged_resident

    monkeypatch.setenv("GUBER_PAGE_SIZE", "48")
    assert env_page_size() == 512
    monkeypatch.setenv("GUBER_PAGE_SIZE", "8")
    assert env_page_size() == 512
    monkeypatch.setenv("GUBER_PAGE_SIZE", "64")
    assert env_page_size() == 64
    monkeypatch.setenv("GUBER_PAGED_RESIDENT", "-3")
    assert env_paged_resident() == 0

    clock = Clock().freeze()
    _paged_env(monkeypatch, page_size=16, resident=0)
    eng = DecisionEngine(capacity=256, clock=clock)
    assert eng.capacity == eng.logical_capacity == 256
    rows = [(b"all_%d" % i, 0, 0, 1, 5, 60_000, 0) for i in range(200)]
    _columnar(eng, rows, clock.now_ms())
    assert eng.paging.faults == 0 and eng.paging.spills == 0


def test_paged_metrics_exported(monkeypatch):
    """The gubernator_paged_* family rides the engine collector when
    (and only when) the plane exists; device.page_fault joins the
    stage timers through the service wiring."""
    from gubernator_tpu.core.paging import PagePlane

    plane = PagePlane(1024, 16, 4)
    assert plane.frames == 4
    assert plane.device_capacity == 64
    assert plane.num_pages == 64
    # The counters the metric family reads exist and start at zero.
    assert (plane.faults, plane.spills, plane.refills) == (0, 0, 0)
    assert plane.refill_wait.count == 0
    # Metric names stay in lockstep with utils/metrics.py literals.
    import inspect

    from gubernator_tpu.utils import metrics as m

    src = inspect.getsource(m)
    for name in (
        "gubernator_paged_pages_resident",
        "gubernator_paged_faults",
        "gubernator_paged_spills",
        "gubernator_paged_refill_wait",
    ):
        assert name in src, name
