"""The mesh engine's state is twelve flat columns of n_shards × cap
rows, shard s owning rows [s·cap, (s+1)·cap) — the layout in which a
chip's view under `shard_map` is the column the one-chip step programs
take (parallel/sharded_engine.py, module docstring).  The layout is an
arrangement of the same rows: every program that reads or writes the
state has to touch exactly the (shard, slot) rows it touched when the
state was [n_shards, cap], and the answers are the dense engine's.

Shard 0's last row and shard 1's first row are in every case: they are
neighbours in the flat column, the boundary a flat index could cross.
Both modes (one `shard_map` program per device; one vmapped program on
one device, with its flat twins) hold the same state."""

import numpy as np
import jax
import pytest

from gubernator_tpu.core.engine import DecisionEngine
from gubernator_tpu.ops.bucket_kernel import (
    pack_state_host,
    unpack_state_host,
)
from gubernator_tpu.parallel.mesh import keys_sharding, make_mesh
from gubernator_tpu.parallel.sharded_engine import ShardedDecisionEngine
from gubernator_tpu.store import (
    CacheItem,
    LeakyBucketItem,
    MemoryLoader,
    TokenBucketItem,
)
from gubernator_tpu.types import Algorithm, RateLimitReq

N_SH = 4
CAP = 16
MODES = ["mesh", "single_program"]


@pytest.fixture(params=MODES)
def mode(request):
    return request.param


def build(mode, clock, cap=CAP, **kw):
    return ShardedDecisionEngine(
        shard_capacity=cap,
        mesh=make_mesh(jax.devices()[:N_SH]),
        clock=clock,
        single_program=mode == "single_program",
        **kw,
    )


def keys_by_shard(engine, per_shard, prefix="k"):
    """`per_shard` key names for every shard, by rejection sampling."""
    out = [[] for _ in range(engine.n_shards)]
    i = 0
    while any(len(ks) < per_shard for ks in out):
        key = f"{prefix}{i}_x"
        sh = engine.shard_of(key)
        if len(out[sh]) < per_shard:
            out[sh].append(key)
        i += 1
    return out


def item_for(key, j, now):
    """A bucket partly spent, token or leaky by `j`, every field of
    its own value so that a row written to the wrong place shows."""
    if j % 3 == 2:
        words = (7 + j % 5, 1 << (j % 31))
        return CacheItem(
            key=key,
            value=LeakyBucketItem(
                limit=50 + j,
                duration=60_000 + j,
                remaining=words[0] + words[1] * 2.0**-32,
                updated_at=now - 10 - j,
                burst=60 + j,
                remaining_words=words,
            ),
            expire_at=now + 60_000 + j,
            algorithm=int(Algorithm.LEAKY_BUCKET),
            invalid_at=0,
        )
    return CacheItem(
        key=key,
        value=TokenBucketItem(
            status=0,
            limit=20 + j,
            duration=30_000 + j,
            remaining=5 + j % 11,
            created_at=now - 20 - j,
        ),
        expire_at=now + 30_000 + j,
        algorithm=int(Algorithm.TOKEN_BUCKET),
        invalid_at=0,
    )


def full_table(engine, now, prefix="k"):
    """Items that fill every row of every shard, and where each
    landed: {key: (shard, slot)}."""
    items = [
        item_for(key, sh * 100 + j, now)
        for sh, ks in enumerate(keys_by_shard(engine, CAP, prefix))
        for j, key in enumerate(ks)
    ]
    assert engine.load(MemoryLoader(items)) == N_SH * CAP
    where = {
        engine.tables[sh].key_for_slot(slot): (sh, slot)
        for sh in range(N_SH)
        for slot in range(CAP)
    }
    assert set(where) == {it.key for it in items}
    return items, where


def host_words(engine) -> dict:
    """The packed state on the host, every column [n_shards, cap]."""
    return {
        f: np.array(col)
        for f, col in engine._host_state()._asdict().items()
    }


def assert_flat_layout(engine, mode):
    n = engine.n_shards * engine.shard_capacity
    devices = list(engine.mesh.devices.flat)
    for name, col in engine._state._asdict().items():
        assert col.shape == (n,), name
        if mode == "single_program":
            assert col.sharding.device_set == {devices[0]}, name
            continue
        assert col.sharding.is_equivalent_to(
            keys_sharding(engine.mesh), 1
        ), name
        cap = engine.shard_capacity
        for s in col.addressable_shards:
            sh = devices.index(s.device)
            assert s.index == (slice(sh * cap, (sh + 1) * cap),), name


def by_key(items):
    return {it.key: it for it in items}


def test_every_program_leaves_flat_columns_on_the_keys_axis(
    mode, frozen_clock
):
    """At construction, and out of every program that returns state:
    a step (packed and collapsed), a restore, an eviction clear, a
    sweep, a bulk load."""
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    assert_flat_layout(engine, mode)
    reqs = [
        RateLimitReq(name="lay", unique_key=f"{i}", hits=1, limit=9,
                     duration=1_000)
        for i in range(40)
    ]
    engine.get_rate_limits(reqs, now_ms=now)  # packed step
    assert_flat_layout(engine, mode)
    engine.get_rate_limits(reqs[:3] * 5, now_ms=now)  # collapsed step
    assert_flat_layout(engine, mode)
    engine._apply_shard_clears([[CAP - 1], [0], [], []])
    assert_flat_layout(engine, mode)
    engine._apply_shard_restores(
        [[(CAP - 1, item_for("a", 1, now))], [(0, item_for("b", 2, now))],
         [], []]
    )
    assert_flat_layout(engine, mode)
    assert engine.sweep(now_ms=now + 5_000) > 0
    assert_flat_layout(engine, mode)
    engine.load(MemoryLoader([item_for("c_x", 3, now)]))
    assert_flat_layout(engine, mode)


def test_loader_round_trip_is_the_dense_engines_field_for_field(
    mode, frozen_clock
):
    """load → serve → export_items → load: the items of an engine
    whose state has no shard axis at all, field for field — on a full
    table, so that both rows at the shard 0 / shard 1 boundary hold a
    bucket of their own."""
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    items, where = full_table(engine, now)
    assert {(0, CAP - 1), (1, 0)} <= set(where.values())
    dense = DecisionEngine(capacity=N_SH * CAP, clock=frozen_clock)
    assert dense.load(MemoryLoader(items)) == len(items)
    assert by_key(engine.export_items()) == by_key(items)

    rng = np.random.default_rng(31)
    for step in range(3):
        frozen_clock.advance(ms=700)
        picked = rng.choice(len(items), size=90)
        reqs = []
        for j in picked:
            it = items[j]
            name, _, unique = it.key.rpartition("_")
            reqs.append(
                RateLimitReq(
                    name=name,
                    unique_key=unique,
                    hits=int(j % 3),
                    limit=it.value.limit,
                    duration=it.value.duration,
                    algorithm=Algorithm(it.algorithm),
                    burst=getattr(it.value, "burst", 0),
                )
            )
        assert {r.hash_key() for r in reqs} <= set(where)
        assert engine.get_rate_limits(reqs) == dense.get_rate_limits(reqs)
    served = by_key(engine.export_items())
    assert served == by_key(dense.export_items())
    assert served != by_key(items)

    other = "mesh" if mode == "single_program" else "single_program"
    again = build(other, frozen_clock)
    assert again.load(MemoryLoader(list(served.values()))) == len(items)
    assert by_key(again.export_items()) == served


def test_an_eviction_clear_clears_bit_0_of_its_rows_alone(mode, frozen_clock):
    engine = build(mode, frozen_clock)
    full_table(engine, frozen_clock.now_ms())
    want = host_words(engine)
    assert (want["meta"] & 1).all()
    clears = [[CAP - 1, 2], [0], [], [5]]
    for sh, slots in enumerate(clears):
        want["meta"][sh, slots] &= ~1
    engine._apply_shard_clears(clears)
    got = host_words(engine)
    for f in want:
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def test_a_store_restore_writes_its_rows_alone(mode, frozen_clock):
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    full_table(engine, now)
    restores = [
        [(CAP - 1, item_for("r0", 41, now))],
        [(0, item_for("r1", 44, now)), (7, item_for("r2", 45, now))],
        [],
        [(3, item_for("r3", 47, now))],
    ]
    # what writing the same rows of the [n_shards, cap] host view gives
    logical = {
        k: np.array(v)
        for k, v in unpack_state_host(engine._host_state()).items()
    }
    for sh, rows in enumerate(restores):
        for slot, it in rows:
            v = it.value
            logical["occupied"][sh, slot] = True
            logical["algo"][sh, slot] = it.algorithm
            logical["limit"][sh, slot] = v.limit
            logical["duration"][sh, slot] = v.duration
            logical["expire"][sh, slot] = it.expire_at
            logical["invalid"][sh, slot] = it.invalid_at
            leaky = isinstance(v, LeakyBucketItem)
            logical["status"][sh, slot] = 0 if leaky else v.status
            logical["remaining"][sh, slot] = 0 if leaky else v.remaining
            hi, lo = v.remaining_words if leaky else (0, 0)
            logical["remf_hi"][sh, slot] = hi
            logical["remf_lo"][sh, slot] = lo
            logical["t0"][sh, slot] = v.updated_at if leaky else v.created_at
            logical["burst"][sh, slot] = v.burst if leaky else 0
    want = pack_state_host(logical)
    engine._apply_shard_restores(restores)
    got = host_words(engine)
    for f in want:
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


@pytest.mark.parametrize("window", [CAP, CAP // 2, 6])
def test_a_windowed_sweep_frees_the_expired_rows_of_every_shard_alone(
    mode, window, frozen_clock
):
    """Windows start shard-local (a window of 6 over 16 rows clamps
    its tail and overlaps), the freed slots go back to the shard's own
    table, and nothing but bit 0 of the expired rows changes."""
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    engine.SWEEP_WINDOW = window
    items, where = full_table(engine, now)
    # token items expire at now + 30 s + j, leaky at now + 60 s + j
    then = now + 45_000
    expired = {it.key for it in items if it.expire_at < then}
    rows = {where[k] for k in expired}
    assert {(0, CAP - 1), (1, 0)} <= rows and len(rows) < len(items)
    want = host_words(engine)
    for sh, slot in rows:
        want["meta"][sh, slot] &= ~1

    assert engine.sweep(now_ms=then) == len(expired)
    got = host_words(engine)
    for f in want:
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
    assert engine.cache_size() == len(items) - len(expired)
    for key, (sh, slot) in where.items():
        kept = None if key in expired else key
        assert engine.tables[sh].key_for_slot(slot) == kept
    assert engine.sweep(now_ms=then) == 0


def test_an_incremental_sweep_covers_every_shard_window_by_window(
    mode, frozen_clock
):
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    engine.SWEEP_WINDOW = CAP // 4
    items, _ = full_table(engine, now)
    freed = [
        engine.sweep(now_ms=now + 120_000, max_windows=1) for _ in range(4)
    ]
    assert freed == [N_SH * CAP // 4] * 4 and engine.cache_size() == 0


def zipf_batches(rng, n_batches, n_items, n_keys):
    """Seeded Zipf batches, fields a function of the key (so duplicate
    segments collapse), one batch in three with hits that vary inside
    a key (so it serves by rounds)."""
    weights = 1.0 / np.arange(1, n_keys + 1) ** 0.99
    weights /= weights.sum()
    for b in range(n_batches):
        ids = rng.choice(n_keys, size=n_items, p=weights)
        hits = (
            rng.integers(0, 3, n_items) if b % 3 == 2
            else np.ones(n_items, dtype=np.int64)
        )
        yield (
            [f"z{i}_key".encode() for i in ids],
            (ids % 2).astype(np.int32),
            np.zeros(n_items, dtype=np.int32),
            hits.astype(np.int64),
            np.asarray([10, 100, 1_000])[ids % 3].astype(np.int64),
            np.asarray([1_000, 60_000])[ids % 2].astype(np.int64),
            np.asarray([10, 100, 1_000])[ids % 3].astype(np.int64),
        )


def test_mesh_single_program_and_dense_agree_on_a_zipf_stream(frozen_clock):
    engines = {
        "mesh": build("mesh", frozen_clock, cap=256),
        "single_program": build("single_program", frozen_clock, cap=256),
        "dense": DecisionEngine(capacity=1024, clock=frozen_clock),
    }
    before = {k: e.dispatches_total for k, e in engines.items()}
    rng = np.random.default_rng(20261004)
    for cols in zipf_batches(rng, n_batches=6, n_items=700, n_keys=300):
        frozen_clock.advance(ms=400)
        got = {k: e.apply_columnar(*cols) for k, e in engines.items()}
        for k in ("mesh", "single_program"):
            for a, b in zip(got[k], got["dense"]):
                np.testing.assert_array_equal(a, b, err_msg=k)
    for k, e in engines.items():
        assert e.dispatches_total > before[k]
    exported = {k: by_key(e.export_items()) for k, e in engines.items()}
    assert exported["mesh"] == exported["dense"]
    assert exported["single_program"] == exported["dense"]


def test_a_padding_lane_of_one_shard_is_no_row_of_the_next(mode, frozen_clock):
    """A shard's padding slots are `cap + lane`: in the flat column
    those are the next shard's first rows.  One key on shard 0 pads 63
    lanes; nothing of shard 1 may move."""
    now = frozen_clock.now_ms()
    engine = build(mode, frozen_clock)
    _, where = full_table(engine, now)
    key = next(k for k, (sh, _) in where.items() if sh == 0)
    before = host_words(engine)
    name, _, unique = key.rpartition("_")
    for reqs in (
        [RateLimitReq(name=name, unique_key=unique, hits=1, limit=30,
                      duration=30_000)],
        [RateLimitReq(name=name, unique_key=unique, hits=1, limit=30,
                      duration=30_000)] * 3,
    ):
        engine.get_rate_limits(reqs, now_ms=now + 1)
        n = len(reqs)
        engine.apply_columnar(
            [key.encode()] * n, np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, np.int64), np.full(n, 30, np.int64),
            np.full(n, 30_000, np.int64), np.zeros(n, np.int64),
            now_ms=now + 2,
        )
    after = host_words(engine)
    sh, slot = where[key]
    for f in before:
        changed = np.argwhere(after[f] != before[f])
        assert all(tuple(c) == (sh, slot) for c in changed), (f, changed)
    assert (after["rem_lo"] != before["rem_lo"]).any()
