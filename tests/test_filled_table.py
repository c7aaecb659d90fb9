"""A table that is full: restored through `engine.load` (columnar and
per item), then asked for keys it does not hold, so that every miss
evicts the least recently used bucket.  The oracle is the plain
reference (`models/lru_reference.py`: an OrderedDict over `spec.py`),
which shares nothing with `InternTable`, the native table or the
engine.  Small and seeded; both intern tables."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gubernator_tpu.checkpoint import NpzFileLoader
from gubernator_tpu.core.engine import DecisionEngine, _pad_size
from gubernator_tpu.core.interning import InternTable
from gubernator_tpu.core.native import NativeInternTable
from gubernator_tpu.models.lru_reference import LRUReference
from gubernator_tpu.models.spec import SlotState, SpecInput
from gubernator_tpu.ops import bucket_kernel as bk
from gubernator_tpu.store import (
    CacheItem,
    ItemColumns,
    LeakyBucketItem,
    MemoryLoader,
    TokenBucketItem,
)
from gubernator_tpu.types import Algorithm, RateLimitReq
from gubernator_tpu.utils import tracing

from test_state_access import FORMS, fresh, scatter_forms

NOW = 1_700_000_000_000
TOKEN, LEAKY = int(Algorithm.TOKEN_BUCKET), int(Algorithm.LEAKY_BUCKET)
LIMITS, DURATIONS = (10, 100, 1000), (60_000, 3_600_000)
TABLES = {"python": InternTable, "native": NativeInternTable}


def config_of(i: int):
    """A key's limit configuration, a pure function of its number."""
    algo = i % 2
    limit = LIMITS[i % 3]
    return algo, limit, DURATIONS[(i // 2) % 2], limit if algo == LEAKY else 0


def filler(i: int) -> CacheItem:
    """Row i of a snapshot: partly spent, every seventh one expired."""
    algo, limit, duration, burst = config_of(i)
    expire = NOW - 1 if i % 7 == 0 else NOW + duration
    if algo == TOKEN:
        value = TokenBucketItem(
            status=0, limit=limit, duration=duration,
            remaining=limit - i % (limit + 1), created_at=NOW - i % 5000,
        )
    else:
        words = (limit - 1 - i % limit, (i * 2654435761) & 0xFFFFFFFF)
        value = LeakyBucketItem(
            limit=limit, duration=duration, updated_at=NOW - i % 5000,
            burst=burst, remaining=words[0] + words[1] * 2.0**-32,
            remaining_words=words,
        )
    return CacheItem(
        key=f"fill_f{i}", value=value, expire_at=expire, algorithm=algo
    )


def slot_state(item: CacheItem) -> SlotState:
    v = item.value
    if isinstance(v, TokenBucketItem):
        return SlotState(
            algorithm=TOKEN, limit=v.limit, remaining=v.remaining,
            duration=v.duration, t0=v.created_at, expire_at=item.expire_at,
            status=v.status,
        )
    return SlotState(
        algorithm=LEAKY, limit=v.limit, duration=v.duration,
        remaining_f=v.remaining_words[0] + v.remaining_words[1] * 2.0**-32,
        t0=v.updated_at, expire_at=item.expire_at, burst=v.burst,
    )


class ColumnLoader:
    """A Loader that has columns only, in chunks of uneven length."""

    def __init__(self, items, chunk=700):
        self.items, self.chunk = items, chunk

    def load(self):
        raise AssertionError("the engine walked load() item by item")

    def load_columns(self):
        for lo in range(0, len(self.items), self.chunk):
            yield ItemColumns.from_items(self.items[lo : lo + self.chunk])


def engine_with(table: str, capacity: int, clock) -> DecisionEngine:
    engine = DecisionEngine(capacity=capacity, clock=clock)
    engine.table = TABLES[table](capacity)
    return engine


def lru_order(engine) -> list:
    """Every key the table holds, least recently used first: what new
    keys evict, one by one."""
    cap = engine.table.capacity
    key_of = [engine.table.key_for_slot(s) for s in range(cap)]
    order = []
    for i in range(cap):  # the free slots go first, then the oldest key's
        evicted = []
        engine.table.intern(f"drain_{i}", NOW, evicted)
        order.extend(key_of[s] for s in evicted)
    return order


def exported(engine) -> list:
    return sorted((it.key, repr(it)) for it in engine.export_items())


# -- (i) a full table under a mix with misses, against the reference ----


def zipf_batches(rng, capacity: int, batches: int, width: int):
    """Traffic ids from a bounded power law over four tables' worth of
    ids — most items hit, the tail misses — and one item in ten on a
    restored key."""
    ids = np.arange(1, 4 * capacity + 1)
    p = ids ** -0.99
    p /= p.sum()
    for _ in range(batches):
        drawn = rng.choice(ids, size=width, p=p)
        restored = rng.random(width) < 0.1
        yield [
            (f"fill_f{i % capacity}", i % capacity) if r else (f"mix_t{i}", i)
            for i, r in zip(drawn.tolist(), restored.tolist())
        ]


@pytest.mark.parametrize("path", ["apply_columnar", "get_rate_limits"])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_full_table_under_misses_equals_the_reference(
    table, path, frozen_clock
):
    frozen_clock.freeze_at(NOW * 1_000_000)
    capacity = 512
    items = [filler(i) for i in range(capacity)]
    engine = engine_with(table, capacity, frozen_clock)
    assert engine.load(ColumnLoader(items)) == capacity
    assert engine.cache_size() == capacity
    ref = LRUReference(capacity)
    ref.load([(it.key, slot_state(it)) for it in items], NOW)

    cleared = []
    apply_clears = engine._apply_clears
    engine._apply_clears = lambda slots: (
        cleared.extend(slots.tolist()), apply_clears(slots))[1]

    rng = np.random.default_rng(28)
    for b, batch in enumerate(zipf_batches(rng, capacity, 12, 200)):
        now = NOW + 7 * b
        hits = rng.integers(0, 3, size=len(batch)).tolist()
        cfgs = [config_of(i) for _key, i in batch]
        key_of = [engine.table.key_for_slot(s) for s in range(capacity)]
        del cleared[:]
        gone = len(ref.evicted)
        want = [
            ref.get_rate_limit(
                key, SpecInput(hits=h, limit=limit, duration=dur,
                               burst=burst, algorithm=algo), now)
            for (key, _i), h, (algo, limit, dur, burst) in zip(batch, hits, cfgs)
        ]
        algo, limit, dur, burst = (np.asarray(c) for c in zip(*cfgs))
        if path == "apply_columnar":
            status, o_limit, remaining, reset = engine.apply_columnar(
                [key.encode() for key, _i in batch],
                algo.astype(np.int32), np.zeros(len(batch), np.int32),
                np.asarray(hits, np.int64), limit.astype(np.int64),
                dur.astype(np.int64), burst.astype(np.int64), now_ms=now,
            )
            got = list(zip(status.tolist(), o_limit.tolist(),
                           remaining.tolist(), reset.tolist()))
        else:
            got = [
                (int(r.status), r.limit, r.remaining, r.reset_time)
                for r in engine.get_rate_limits([
                    RateLimitReq(
                        name=key.split("_", 1)[0],
                        unique_key=key.split("_", 1)[1], hits=h,
                        limit=int(li), duration=int(d), burst=int(bu),
                        algorithm=Algorithm(int(a)),
                    )
                    for (key, _i), h, a, li, d, bu in zip(
                        batch, hits, algo, limit, dur, burst)
                ], now_ms=now)
            ]
        assert got == [
            (int(w.status), w.limit, w.remaining, w.reset_time) for w in want
        ], f"batch {b}"
        # the keys evicted, in the order they went
        assert [key_of[s] for s in cleared] == ref.evicted[gone:], f"batch {b}"
        assert engine.cache_size() == capacity
    assert engine.table.evictions == ref.evictions > 400
    assert engine.table.unexpired_evictions == ref.unexpired_evictions
    assert 0 < ref.unexpired_evictions < ref.evictions  # both kinds occurred
    assert lru_order(engine) == list(ref.buckets)


# -- (ii) the columnar load is the per-item load -------------------------


def stream(capacity: int, rows: int) -> list:
    """A snapshot longer than the table, some keys in it twice (the
    second time in another state)."""
    items = [filler(i) for i in range(rows)]
    for i in range(5, rows, 11):
        again = filler(i - 5)
        again.value.limit += 1
        again.expire_at += 1
        items[i] = again
    return items


@pytest.mark.parametrize("capacity,rows,chunk", [
    (512, 512, 700),     # exactly full, one chunk
    (512, 1400, 333),    # overflow: evictions inside and across chunks
    (4096, 2600, 1024),  # two pieces of the scatter's width and a half
])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_columnar_load_equals_per_item_load(
    table, capacity, rows, chunk, frozen_clock
):
    frozen_clock.freeze_at(NOW * 1_000_000)
    items = stream(capacity, rows)
    by_column = engine_with(table, capacity, frozen_clock)
    by_item = engine_with(table, capacity, frozen_clock)
    assert by_column.load_width == (1024 if capacity == 512 else 4096)
    assert by_column.load(ColumnLoader(items, chunk)) == rows
    assert by_item.load(MemoryLoader(items)) == rows
    assert by_column.rows_loaded_total == by_item.rows_loaded_total == rows
    rows_c, rows_i = exported(by_column), exported(by_item)
    assert rows_c == rows_i  # bit for bit, the leaky 32.32 words included
    assert len(rows_c) == min(capacity, len({it.key for it in items}))
    for attr in ("evictions", "unexpired_evictions"):
        assert getattr(by_column.table, attr) == getattr(by_item.table, attr)
    # one stage a piece of the scatter's width, no piece over a chunk's end
    assert by_column.stages["engine.load"].count == sum(
        -(-min(chunk, rows - lo) // by_column.load_width)
        for lo in range(0, rows, chunk)
    )
    # the reference's Load, row by row
    ref = LRUReference(capacity)
    ref.load([(it.key, slot_state(it)) for it in items], NOW)
    assert by_column.table.evictions == ref.evictions
    assert by_column.table.unexpired_evictions == ref.unexpired_evictions
    assert lru_order(by_column) == lru_order(by_item) == list(ref.buckets)


@pytest.mark.parametrize("table", sorted(TABLES))
def test_a_piece_as_wide_as_the_scatter_goes_up_unpadded(table, frozen_clock):
    frozen_clock.freeze_at(NOW * 1_000_000)
    items = [filler(i) for i in range(1024)]  # each key once: 1,024 slots
    by_column = engine_with(table, 1024, frozen_clock)
    by_item = engine_with(table, 1024, frozen_clock)
    assert by_column.load_width == 1024
    assert by_column.load(ColumnLoader(items, 1024)) == 1024
    by_item.load(MemoryLoader(items))
    assert exported(by_column) == exported(by_item)
    assert lru_order(by_column) == [it.key for it in items]


def test_npz_loader_hands_its_columns_over(tmp_path, frozen_clock):
    items = stream(512, 300) + [CacheItem(key="", value=None)]
    loader = NpzFileLoader(str(tmp_path / "snapshot.npz"))
    loader.save(iter(items))
    (cols,) = loader.load_columns()
    assert len(cols) == 300 and cols.keys() == [it.key for it in items[:300]]
    assert [repr(it) for it in loader.load()] == [
        repr(it) for it in ItemColumns.from_items(items).items()
    ]
    engine = DecisionEngine(capacity=512, clock=frozen_clock)
    loader.load = None  # a columnar engine does not walk the items
    assert engine.load(loader) == 300
    again = DecisionEngine(capacity=512, clock=frozen_clock)
    again.load(MemoryLoader(items))
    assert exported(engine) == exported(again)


def test_a_row_without_a_key_restores_nothing(frozen_clock):
    cols = ItemColumns.from_items([filler(i) for i in range(4)])
    keys = b"fill_f0" b"" b"fill_f2" b"fill_f3"  # row 1 has none
    cols.key_buf = np.frombuffer(keys, dtype=np.uint8)
    cols.key_offsets = np.array([0, 7, 7, 14, 21], dtype=np.int64)
    engine = DecisionEngine(capacity=512, clock=frozen_clock)

    class Loader:
        def load_columns(self):
            return [cols]

    assert engine.load(Loader()) == engine.cache_size() == 3
    assert sorted(it.key for it in engine.export_items()) == [
        "fill_f0", "fill_f2", "fill_f3"]


# -- (iii) a restored bucket answers from its restored state -------------


@pytest.mark.parametrize("loader", [ColumnLoader, MemoryLoader])
@pytest.mark.parametrize("table", sorted(TABLES))
def test_restored_bucket_answers_from_its_state_and_an_evicted_one_starts_empty(
    table, loader, frozen_clock
):
    frozen_clock.freeze_at(NOW * 1_000_000)
    capacity = 512
    items = [filler(i) for i in range(capacity)]
    engine = engine_with(table, capacity, frozen_clock)
    engine.load(loader(items))
    # 108: token, limit 10, 10 - 108 % 11 = 1 left; 15: leaky, limit 10,
    # whole part 10 - 1 - 15 % 10 = 4; neither expired
    token, leaky = items[108], items[15]
    assert (token.value.remaining, leaky.value.remaining_words[0]) == (1, 4)

    def ask(i, hits, now=NOW + 3):
        algo, limit, duration, burst = config_of(i)
        return engine.get_rate_limits([RateLimitReq(
            name="fill", unique_key=f"f{i}", hits=hits, limit=limit,
            duration=duration, burst=burst, algorithm=Algorithm(algo),
        )], now_ms=now)[0]

    ref = LRUReference(capacity)
    ref.load([(it.key, slot_state(it)) for it in items], NOW)
    for i, hits in [(108, 0), (108, 1), (108, 1), (15, 0), (15, 1), (15, 1)]:
        algo, limit, duration, burst = config_of(i)
        want = ref.get_rate_limit(f"fill_f{i}", SpecInput(
            hits=hits, limit=limit, duration=duration, burst=burst,
            algorithm=algo), NOW + 3)
        got = ask(i, hits)
        assert (int(got.status), got.limit, got.remaining, got.reset_time) == (
            int(want.status), want.limit, want.remaining, want.reset_time
        ), (i, hits)
    # the token bucket had one left and the first hit took it: the next
    # is over the limit; its reset is still the restored expiry
    assert [ask(108, 0).remaining, int(ask(108, 1).status)] == [0, 1]
    assert ask(108, 0).reset_time == token.expire_at
    # row 2 (token, limit 1000, 998 left) answers from its state, is
    # evicted, and when it returns it starts from its limit
    assert ask(2, 0).remaining == 998 and engine.table.evictions == 0
    for i in range(capacity):  # 2 is now the newest: a table's worth evicts it
        ask(10_000 + i, 1)
    assert engine.table.evictions == capacity
    assert ask(2, 1).remaining == 1000 - 1


# -- (iv) engine.evict_clear is a leaf under the lock --------------------


def test_evict_clear_is_a_leaf_and_the_leaves_tile_the_lock_hold(frozen_clock):
    frozen_clock.freeze_at(NOW * 1_000_000)
    capacity, width = 4096, 500
    engine = DecisionEngine(capacity=capacity, clock=frozen_clock)
    engine.load(ColumnLoader([filler(i) for i in range(capacity)], 4096))
    cols = dict(
        algo=np.zeros(width, np.int32), behavior=np.zeros(width, np.int32),
        hits=np.ones(width, np.int64), limit=np.full(width, 100, np.int64),
        duration=np.full(width, 60_000, np.int64),
        burst=np.zeros(width, np.int64),
    )

    def batch(b):
        # 40 of one hot key (the collapsed step) and 460 new ones
        return [b"mix_hot"] * 40 + [
            b"mix_b%d_%d" % (b, i) for i in range(width - 40)]

    engine.apply_columnar(batch(0), now_ms=NOW, **cols)  # shapes warm
    before = {name: s.total for name, s in engine.stages.items()}
    clears = engine.stages["engine.evict_clear"].count
    tracer = tracing.InMemoryTracer()
    tracing.set_tracer(tracer)
    try:
        with tracing.span("rpc.get_rate_limits"):
            engine.apply_columnar(batch(1), now_ms=NOW + 1, **cols)
    finally:
        tracing.set_tracer(None)
    for b in range(2, 102):
        engine.apply_columnar(batch(b), now_ms=NOW + b, **cols)
    assert engine.stages["engine.evict_clear"].count - clears == 101
    assert engine.table.evictions >= 101 * (width - 40)
    spent = {name: s.total - before[name] for name, s in engine.stages.items()}
    leaves = ["engine.intern", "engine.pack", "device.h2d", "device.launch",
              "engine.set_expiry", "engine.evict_clear"]
    assert spent["engine.evict_clear"] > 0
    assert sum(spent[n] for n in leaves) >= 0.9 * spent["engine.lock_hold"]
    # in the RPC's tree: a child of the engine's span, with no child of
    # its own, and no stage of the tree open while it ran
    (clear,) = tracer.spans("engine.evict_clear")
    (columnar,) = tracer.spans("engine.columnar")
    tree = tracer.trace(clear.trace_id)
    assert clear.parent_span_id == columnar.span_id
    assert not [s for s in tree if s.parent_span_id == clear.span_id]
    for s in tree:
        if s.name in leaves and s is not clear:
            assert s.end_ns <= clear.start_ns or s.start_ns >= clear.end_ns, s.name


# -- (v) the scatter forms of the two programs a full table runs ---------


def slot_record(slots, width: int, cap: int, seed: int = 5) -> bk.SlotRecord:
    rng = np.random.default_rng(seed)
    slot = np.arange(cap, cap + width, dtype=np.int64).astype(np.int32)
    slot[: len(slots)] = sorted(slots)
    i64 = lambda hi: rng.integers(0, hi, size=width).astype(np.int64)  # noqa: E731
    return bk.SlotRecord(
        slot=jnp.asarray(slot),
        algo=jnp.asarray(rng.integers(0, 2, width).astype(np.int32)),
        status=jnp.asarray(rng.integers(0, 2, width).astype(np.int32)),
        limit=jnp.asarray(i64(1 << 40)), remaining=jnp.asarray(i64(1 << 40)),
        remf_hi=jnp.asarray(rng.integers(0, 1 << 20, width).astype(np.int32)),
        remf_lo=jnp.asarray(rng.integers(0, 1 << 32, width).astype(np.uint32)),
        duration=jnp.asarray(i64(1 << 40)), t0=jnp.asarray(i64(1 << 42)),
        expire_at=jnp.asarray(i64(1 << 42)), burst=jnp.asarray(i64(1 << 40)),
        invalid_at=jnp.asarray(i64(1 << 42)),
    )


@pytest.mark.parametrize("form", sorted(FORMS))
def test_load_slots_writes_its_rows_alone_in_either_form(form, monkeypatch):
    monkeypatch.setattr(bk, "_SCATTER_PASS_ROWS_PER_LANE", FORMS[form])
    cap, width, live = 1000, 64, [0, 1, 127, 128, 640, 999]
    state = bk.make_state(cap)
    rec = slot_record(live, width, cap)
    program = jax.jit(fresh(bk._load_slots_impl))
    assert scatter_forms(program, state, rec) == {form}
    got = bk.unpack_state_host(program(state, rec))
    n = len(live)
    assert np.flatnonzero(got["occupied"]).tolist() == live
    leaky = np.asarray(rec.algo)[:n] != 0
    for name, col in [
        ("algo", rec.algo), ("status", rec.status), ("limit", rec.limit),
        ("duration", rec.duration), ("t0", rec.t0),
        ("expire", rec.expire_at), ("burst", rec.burst),
        ("invalid", rec.invalid_at),
    ]:
        np.testing.assert_array_equal(got[name][live], np.asarray(col)[:n], name)
    np.testing.assert_array_equal(
        got["remaining"][live][~leaky], np.asarray(rec.remaining)[:n][~leaky])
    np.testing.assert_array_equal(
        got["remf_hi"][live][leaky], np.asarray(rec.remf_hi)[:n][leaky])
    np.testing.assert_array_equal(
        got["remf_lo"][live][leaky], np.asarray(rec.remf_lo)[:n][leaky])
    rest = np.setdiff1d(np.arange(cap), live)
    for col in got.values():
        assert not np.asarray(col)[rest].any()


@pytest.mark.parametrize("program,rows,lanes,form", [
    # the benchmark's filled node: a restore piece is a pass over each
    # column, an RPC's evictions (~400, padded to 512) a loop over lanes
    ("load", 100_000_000, 1 << 20, "pass"),
    ("clear", 100_000_000, 512, "loop"),
    ("clear", 100_000_000, 16, "loop"),
    # its CPU rehearsal and the tests above: the pass either way
    ("load", 20_000, 32_768, "pass"),
    ("clear", 20_000, 512, "pass"),
])
def test_scatter_form_at_the_shapes_a_full_table_runs(program, rows, lanes, form):
    """Lowered from shapes alone: nothing of this size is allocated."""
    def shape(dtype, n=rows):
        return jax.ShapeDtypeStruct((n,), dtype)

    if program == "load":
        state = bk.BucketState(*(shape(c.dtype) for c in bk.make_state(1)))
        rec = bk.SlotRecord(*(
            shape(np.asarray(c).dtype, lanes) for c in slot_record([], 4, 4)))
        assert scatter_forms(
            jax.jit(fresh(bk._load_slots_impl)), state, rec) == {form}
        # the width the engine picks for a table of these rows
        assert min(1 << 20, _pad_size(rows, floor=1024)) == lanes
    else:
        assert scatter_forms(
            jax.jit(fresh(bk._clear_occupied_impl)),
            shape(jnp.int32), shape(jnp.int32, lanes)) == {form}
