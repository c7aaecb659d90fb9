"""ReadbackCombiner: stacked device→host transfers (core/readback.py).

Correctness contract: every ticket's fetch() returns exactly the bytes
its own dispatch produced, no matter how tickets interleave across
threads, shapes, or group boundaries; RPC count drops when callers
pipeline.
"""

import threading

import jax.numpy as jnp
import numpy as np

from gubernator_tpu.core.readback import MAX_GROUP, ReadbackCombiner


def _dev(arr):
    return jnp.asarray(arr)


def test_single_ticket_roundtrip():
    rc = ReadbackCombiner()
    a = np.arange(10, dtype=np.int32).reshape(2, 5)
    t = rc.register(_dev(a))
    np.testing.assert_array_equal(t.fetch(), a)
    assert rc.transfers == 1
    # Second fetch is cached, no new transfer.
    np.testing.assert_array_equal(t.fetch(), a)
    assert rc.transfers == 1


def test_pipelined_tickets_share_one_transfer():
    rc = ReadbackCombiner()
    arrs = [
        (np.arange(20, dtype=np.int32) * (i + 1)).reshape(4, 5)
        for i in range(6)
    ]
    tickets = [rc.register(_dev(a)) for a in arrs]
    # First fetch leads: everything outstanding rides one stacked RPC.
    np.testing.assert_array_equal(tickets[0].fetch(), arrs[0])
    assert rc.transfers == 1
    for t, a in zip(tickets, arrs):
        np.testing.assert_array_equal(t.fetch(), a)
    assert rc.transfers == 1
    assert rc.stacked == 6


def test_mixed_shapes_group_separately():
    rc = ReadbackCombiner()
    small = [np.full((2, 4), i, dtype=np.int32) for i in range(3)]
    big = [np.full((2, 8), 10 + i, dtype=np.int32) for i in range(3)]
    ts = [rc.register(_dev(a)) for a in small]
    tb = [rc.register(_dev(a)) for a in big]
    for t, a in zip(ts + tb, small + big):
        np.testing.assert_array_equal(t.fetch(), a)
    # One stacked transfer per shape class.
    assert rc.transfers == 2


def test_more_than_max_group_still_exact():
    rc = ReadbackCombiner()
    n = MAX_GROUP + 5
    arrs = [np.full((1, 8), i, dtype=np.int32) for i in range(n)]
    tickets = [rc.register(_dev(a)) for a in arrs]
    for t, a in zip(tickets, arrs):
        np.testing.assert_array_equal(t.fetch(), a)
    assert rc.transfers >= 2  # capped groups


def test_threaded_fetch_no_lost_tickets():
    rc = ReadbackCombiner()
    n = 24
    arrs = [np.full((3, 4), i, dtype=np.int32) for i in range(n)]
    tickets = [rc.register(_dev(a)) for a in arrs]
    errs = []

    def fetch_one(i):
        try:
            np.testing.assert_array_equal(tickets[i].fetch(), arrs[i])
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [
        threading.Thread(target=fetch_one, args=(i,)) for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    assert all(not t.is_alive() for t in threads)
    # Far fewer transfers than tickets (leaders covered followers).
    assert rc.transfers < n


def test_overflow_drains_fire_and_forget():
    import time

    rc = ReadbackCombiner()
    arrs = [np.full((2, 2), i, dtype=np.int32) for i in range(4 * MAX_GROUP + 8)]
    tickets = [rc.register(_dev(a)) for a in arrs]
    # The drain runs on a DETACHED thread (register must never block
    # behind a transfer — it is called under the engine lock); wait
    # for it to cover some early tickets.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not any(
        t.host is not None for t in tickets[:MAX_GROUP]
    ):
        time.sleep(0.01)
    assert any(t.host is not None for t in tickets[:MAX_GROUP])
    # And every ticket still fetches its own bytes.
    for t, a in zip(tickets, arrs):
        np.testing.assert_array_equal(t.fetch(), a)


class _Held:
    """A device array whose transfer blocks until released: a step
    that is still running."""

    def __init__(self, arr):
        self.arr, self.shape, self.dtype = arr, arr.shape, arr.dtype
        self.released = threading.Event()
        self.reads = 0

    def __array__(self, dtype=None, copy=None):
        self.reads += 1
        assert self.released.wait(timeout=10), "read waited for a later step"
        return self.arr


def test_window_scoped_read_claims_its_own_tickets_only():
    """`start_own` takes exactly the tickets it is given: a same-shape
    ticket registered later — the next window's, its step still
    running — stays queued and is neither stacked nor waited for, where
    a leader's `fetch` would have claimed both; another thread's
    `fetch` on what is left still works; the counters add up."""
    rc = ReadbackCombiner()
    a = np.arange(12, dtype=np.int32).reshape(3, 4)
    b = a + 100  # another shape class of the same window
    mine = [rc.register(_dev(a)), rc.register(_dev(np.arange(5, dtype=np.int32)))]
    later = _Held(b - 100 + 7)  # same shape as `a`, not yet computed
    theirs = rc.register(later)
    started = rc.start_own(mine)
    assert rc._queue == [theirs]
    rc.land(started)
    np.testing.assert_array_equal(mine[0].fetch(), a)
    np.testing.assert_array_equal(mine[1].fetch(), np.arange(5, dtype=np.int32))
    assert later.reads == 0 and theirs.host is None
    assert (rc.registered, rc.transfers, rc.stacked) == (3, 2, 0)

    got = []
    other = threading.Thread(target=lambda: got.append(theirs.fetch()))
    other.start()
    later.released.set()
    other.join(timeout=10)
    np.testing.assert_array_equal(got[0], later.arr)
    assert (rc.registered, rc.transfers) == (3, 3) and rc._queue == []


def test_window_scoped_read_stacks_its_same_shape_chunks():
    """Two chunks of one width (a window of 8,000 rows is two 4,096-lane
    pieces) ride one stacked transfer, as a leader's would — and a
    ticket a leader has already claimed is left to that leader."""
    rc = ReadbackCombiner()
    arrs = [np.full((2, 8), i, dtype=np.int32) for i in range(3)]
    t0, t1, t2 = (rc.register(_dev(a)) for a in arrs)
    np.testing.assert_array_equal(t2.fetch(), arrs[2])  # a leader took all three
    assert rc.transfers == 1 and rc.stacked == 3
    started = rc.start_own([t0, t1])  # nothing of theirs is queued any more
    assert started == ([], [])
    rc.land(started)
    t3, t4 = rc.register(_dev(arrs[0])), rc.register(_dev(arrs[1]))
    rc.land(rc.start_own([t3, t4]))
    np.testing.assert_array_equal(t3.fetch(), arrs[0])
    np.testing.assert_array_equal(t4.fetch(), arrs[1])
    assert (rc.registered, rc.transfers, rc.stacked) == (5, 2, 5)


def test_pending_reads_back_its_own_window_not_the_next():
    """Two batches launched back to back on one engine, as the front's
    serve thread launches them: the first one's `get()` is served by
    the copies `start_readback` started and leaves the second one's
    tickets alone, whatever their shape."""
    from gubernator_tpu.core.engine import DecisionEngine

    engine = DecisionEngine(capacity=4096)
    n = 64
    cols = (np.zeros(n, np.int32), np.zeros(n, np.int32), np.ones(n, np.int64),
            np.full(n, 10, np.int64), np.full(n, 60_000, np.int64), np.zeros(n, np.int64))
    keys = [b"own_k%d" % (i % 40) for i in range(n)]  # duplicates: collapsed, readback tickets
    first = engine.apply_columnar(keys, *cols, want_async=True).start_readback()
    assert engine.readback._queue == []  # claimed, copying
    second = engine.apply_columnar(keys, *cols, want_async=True).start_readback()
    transfers = engine.readback.transfers
    status, limit, remaining, _ = first.get()
    assert engine.readback.transfers == transfers  # nothing new was started
    assert (status == 0).all() and (limit == 10).all()
    assert sorted(remaining[:40].tolist()) == [9] * 40
    assert sorted(second.get()[2].tolist())[0] == 6  # ran after, on the first's state
    distinct = [b"own_d%d" % i for i in range(n)]  # no duplicates: pump rounds
    third = engine.apply_columnar(distinct, *cols, want_async=True)
    ticket = third._pieces[0][0]
    if engine._pump is not None:
        assert ticket.group is None  # queued, not launched
        third.start_readback()
        assert ticket.group is not None  # "submitted" means launched
    assert (third.start_readback().get()[2] == 9).all()
    engine.close()
