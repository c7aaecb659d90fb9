"""Native columnar feeder plane tests (core/native/columnar_feeder.cpp).

The load-bearing guarantee is PARITY: the columns the C conn threads
pack straight from wire bytes must be bit-equal to what the Python
columnar line (net/wire_codec.decode_reqs) produces for the same
payload — key bytes, offsets, every value lane, and both FNV hashes.
Plus the ring's operational contract: overflow backpressure declines
(never blocks, never drops), teardown drains then closes (no
use-after-free, no stranded RPCs), and the retry-hint metadata rides
natively answered OVER_LIMIT items.
"""

import os
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.net import h2_fast, wire_codec
from gubernator_tpu.net.pb import gubernator_pb2 as pb

pytestmark = pytest.mark.skipif(
    h2_fast.load() is None, reason="native h2 server unavailable"
)


def _payload(items):
    return pb.GetRateLimitsReq(
        requests=[pb.RateLimitReq(**kw) for kw in items]
    ).SerializeToString()


def _capture_feeder(**kw):
    """A feeder whose window handler snapshots the packed columns."""
    from gubernator_tpu.core.native_plane import NativeColumnarFeeder

    captured = []

    def handler(slot, n_rows, n_rpcs, key_bytes):
        captured.append(
            {
                "key_buf": slot.key_buf[:key_bytes].copy(),
                "key_offsets": slot.key_offsets[: n_rows + 1].copy(),
                "algo": slot.algo[:n_rows].copy(),
                "behavior": slot.behavior[:n_rows].copy(),
                "hits": slot.hits[:n_rows].copy(),
                "limit": slot.limit[:n_rows].copy(),
                "duration": slot.duration[:n_rows].copy(),
                "burst": slot.burst[:n_rows].copy(),
                "fnv1": slot.fnv1[:n_rows].copy(),
                "fnv1a": slot.fnv1a[:n_rows].copy(),
                "name_lens": slot.name_lens[:n_rows].copy(),
                "rpc_row": slot.rpc_row[:n_rpcs].copy(),
                "rpc_items": slot.rpc_items[:n_rpcs].copy(),
            }
        )
        slot.rpc_status[:n_rpcs] = 0
        return 0

    feeder = NativeColumnarFeeder(window_handler=handler, **kw)
    return feeder, captured


def _fuzz_items(rng, n):
    """Random request rows across algorithms, value widths (32-bit
    boundaries, int64 extremes, negative hits = settle rows), and
    key shapes (incl. '_' in names — name_lens must still split)."""
    items = []
    for _ in range(n):
        name = rng.choice(
            ["r", "rate_limit", "x" * 60, "a_b_c", "Ω≈ç"]
        ) + str(rng.integers(0, 99))
        key = rng.choice(["k", "user_1234", "z" * 120]) + str(
            rng.integers(0, 999)
        )
        items.append(
            dict(
                name=name,
                unique_key=key,
                hits=int(
                    rng.choice(
                        [0, 1, -1, 7, 2**31 - 1, 2**31, -(2**40), 2**62]
                    )
                ),
                limit=int(rng.choice([1, 100, 2**32 + 5, 2**62])),
                duration=int(rng.choice([1000, 60_000, 2**40])),
                algorithm=int(rng.choice([0, 1])),
                behavior=int(rng.choice([0, 2, 8, 32])),  # non-disqualifying
                burst=int(rng.choice([0, 5, 2**33])),
            )
        )
    return items


def test_pack_parity_fuzz():
    """C-packed columns bit-equal to the Python columnar decode across
    wire widths/algorithms — single-RPC windows."""
    feeder, captured = _capture_feeder(n_slots=2, max_rows=2048)
    rng = np.random.default_rng(7)
    try:
        payloads = []
        for round_ in range(20):
            body = _payload(_fuzz_items(rng, int(rng.integers(1, 40))))
            payloads.append(body)
            rc = feeder.pack(body)
            assert rc > 0
            feeder.flush()
        assert len(captured) == len(payloads)
        for body, got in zip(payloads, captured):
            dec = wire_codec.decode_reqs(body, 2048, 0)
            assert dec is not None
            assert got["key_offsets"][0] == 0
            np.testing.assert_array_equal(got["key_buf"], dec.key_buf)
            np.testing.assert_array_equal(
                got["key_offsets"], dec.key_offsets
            )
            for lane in (
                "algo", "behavior", "hits", "limit", "duration", "burst",
                "fnv1", "fnv1a",
            ):
                np.testing.assert_array_equal(
                    got[lane], getattr(dec, lane), err_msg=lane
                )
            np.testing.assert_array_equal(got["name_lens"], dec.name_len)
    finally:
        feeder.close()


def test_pack_parity_multi_rpc_window():
    """Several RPCs packed into ONE window: per-RPC ranges (rpc_row /
    rpc_items) recover each body's own decode exactly, and the joint
    offsets column stays gap-free."""
    feeder, captured = _capture_feeder(
        n_slots=2, max_rows=2048, window_s=0.5
    )
    rng = np.random.default_rng(11)
    try:
        bodies = [
            _payload(_fuzz_items(rng, int(rng.integers(1, 12))))
            for _ in range(6)
        ]
        for b in bodies:
            assert feeder.pack(b) > 0
        feeder.flush()
        assert len(captured) == 1
        got = captured[0]
        assert len(got["rpc_row"]) == len(bodies)
        # Ranges are contiguous and ordered (claims are sequential).
        assert got["rpc_row"][0] == 0
        np.testing.assert_array_equal(
            got["rpc_row"][1:],
            (got["rpc_row"] + got["rpc_items"])[:-1],
        )
        for r, body in enumerate(bodies):
            dec = wire_codec.decode_reqs(body, 2048, 0)
            row0 = int(got["rpc_row"][r])
            k = int(got["rpc_items"][r])
            assert k == dec.n
            off0 = int(got["key_offsets"][row0])
            np.testing.assert_array_equal(
                got["key_offsets"][row0 : row0 + k + 1] - off0,
                dec.key_offsets,
            )
            np.testing.assert_array_equal(
                got["key_buf"][off0 : int(got["key_offsets"][row0 + k])],
                dec.key_buf,
            )
            for lane in ("hits", "limit", "duration", "fnv1a"):
                np.testing.assert_array_equal(
                    got[lane][row0 : row0 + k], getattr(dec, lane),
                    err_msg=lane,
                )
    finally:
        feeder.close()


def test_pack_declines_disqualified_and_malformed():
    from gubernator_tpu.service import COLUMNAR_DISQUALIFIERS
    from gubernator_tpu.types import Behavior

    feeder, captured = _capture_feeder(
        disqualify_mask=COLUMNAR_DISQUALIFIERS
    )
    try:
        body = _payload(
            [
                dict(
                    name="g", unique_key="k", hits=1, limit=5,
                    duration=1000, behavior=int(Behavior.GLOBAL),
                )
            ]
        )
        assert feeder.pack(body) == -1  # disqualified → byte path
        assert feeder.pack(b"\xff\xff\xff") == -1  # malformed
        assert feeder.stats()["feeder_declined"] == 2
        assert not captured
    finally:
        feeder.close()


def test_oversized_claim_declines_without_sealing():
    """An RPC whose key bytes can never fit even an EMPTY window must
    decline to the byte path (-1) WITHOUT sealing the open window —
    sealing would force-flush co-producers' group-commit windows on
    every oversized arrival."""
    feeder, captured = _capture_feeder(
        n_slots=2, max_rows=2048, key_cap=1, window_s=0.5,
    )  # key_cap clamps to the 64 KiB floor
    try:
        small = _payload(
            [dict(name="sm", unique_key="k1xyz", hits=1, limit=9,
                  duration=1000)]
        )
        big = _payload(
            [
                dict(name="big", unique_key="k" * 80 + str(i), hits=1,
                     limit=9, duration=1000)
                for i in range(1000)
            ]
        )  # ~80 KB of key bytes > the 64 KiB window floor
        assert feeder.pack(small) == 1
        before = feeder.stats()
        assert feeder.pack(big, max_items=1000) == -1
        after = feeder.stats()
        assert after["feeder_declined"] == before["feeder_declined"] + 1
        assert after["feeder_ring_full"] == before["feeder_ring_full"]
        # The open window kept its claim open: more rows still join it.
        assert feeder.pack(small) == 1
        feeder.flush()
        assert len(captured) == 1 and len(captured[0]["algo"]) == 2
    finally:
        feeder.close()


def test_max_rpcs_clamp_reflected_in_views():
    """The C side clamps max_rpcs to its cursor field width; the
    Python views must map the CLAMPED capacity, not the raw argument
    (an oversized view would let whole-array writes run past the C
    allocation)."""
    feeder, _ = _capture_feeder(n_slots=2, max_rows=64, max_rpcs=100_000)
    try:
        assert feeder.max_rpcs == 8191  # kRpcsMask
        assert len(feeder.slots[0].rpc_status) == 8191
        assert feeder.stats()["feeder_max_rpcs"] == 8191
    finally:
        feeder.close()


def test_ring_overflow_backpressure_and_recovery():
    """A blocked serve thread + tiny ring ⇒ cf_pack returns the
    backpressure decline (never blocks, never drops); once the serve
    thread drains, packing works again."""
    from gubernator_tpu.core.native_plane import NativeColumnarFeeder

    release = threading.Event()
    served = []

    def handler(slot, n_rows, n_rpcs, key_bytes):
        release.wait(timeout=10)
        served.append(n_rows)
        slot.rpc_status[:n_rpcs] = 0
        return 0

    feeder = NativeColumnarFeeder(
        n_slots=2, max_rows=64, max_rpcs=16, flush_rows=8,
        window_s=0.001, window_handler=handler,
    )
    try:
        body = _payload(
            [
                dict(name="bp", unique_key=f"k{i}xyz", hits=1, limit=9,
                     duration=1000)
                for i in range(8)
            ]
        )
        # Window A seals at flush_rows=8 and blocks in the handler;
        # window B fills and seals; with n_slots=2 there is nowhere to
        # rotate → backpressure.
        deadline = time.monotonic() + 10
        rc = feeder.pack(body)
        while rc > 0 and time.monotonic() < deadline:
            rc = feeder.pack(body)
        assert rc == -2
        assert feeder.stats()["feeder_ring_full"] >= 1
        release.set()
        feeder.flush()
        assert sum(served) == feeder.stats()["feeder_served_rows"]
        # Recovered: the ring accepts claims again.
        deadline = time.monotonic() + 10
        rc = feeder.pack(body)
        while rc == -2 and time.monotonic() < deadline:
            time.sleep(0.005)
            rc = feeder.pack(body)
        assert rc > 0
        feeder.flush()
    finally:
        feeder.close()


def test_teardown_drains_claimed_windows():
    """close() with claimed-but-unserved windows must drain (stats
    account every packed row) and free without crash — the
    drain-then-close contract."""
    from gubernator_tpu.core.native_plane import NativeColumnarFeeder

    hold = threading.Event()

    def handler(slot, n_rows, n_rpcs, key_bytes):
        hold.wait(timeout=3)
        slot.rpc_status[:n_rpcs] = 0
        return 0

    feeder = NativeColumnarFeeder(
        n_slots=3, max_rows=64, flush_rows=8, window_s=0.001,
        window_handler=handler,
    )
    body = _payload(
        [dict(name="td", unique_key=f"x{i}abc", hits=1, limit=9,
              duration=1000) for i in range(8)]
    )
    packed = 0
    for _ in range(3):
        rc = feeder.pack(body)
        if rc > 0:
            packed += rc
    hold.set()
    feeder.close()  # stop drains remaining windows, then frees
    assert packed > 0


def test_flush_observes_late_claims_row_conservation():
    """Regression for the PR-12 teardown flake: a cf_pack claim
    landing AFTER cf_flush's seal scan (or a window sealed by a flush
    just after the serve loop's rotation passed it) could leave one
    RPC packed-but-unserved past the flush's bounded wait.  The fix
    repeats the seal scan inside the wait loop, re-kicks the serve
    thread each iteration, and makes the serve loop sweep sealed
    non-open windows — so at quiesce every packed row is served.
    Producers hammer packs while other threads hammer flushes; the
    final flush must account for every row."""
    from gubernator_tpu.core.native_plane import NativeColumnarFeeder

    served = [0]
    lock = threading.Lock()

    def handler(slot, n_rows, n_rpcs, key_bytes):
        with lock:
            served[0] += n_rows
        slot.out_status[:n_rows] = 0
        slot.out_limit[:n_rows] = 9
        slot.out_remaining[:n_rows] = 8
        slot.out_reset[:n_rows] = 0
        slot.rpc_status[:n_rpcs] = 0
        return 0

    # Small windows + a tiny group-commit so seals, rotations, and
    # flushes interleave densely.
    feeder = NativeColumnarFeeder(
        n_slots=3, max_rows=64, flush_rows=8, window_s=0.0005,
        window_handler=handler,
    )
    try:
        body = _payload(
            [dict(name="fl", unique_key=f"y{i}abc", hits=1, limit=9,
                  duration=1000) for i in range(4)]
        )
        n_packers, reps = 4, 150
        packed = [0] * n_packers
        stop = threading.Event()

        def packer(t):
            for _ in range(reps):
                rc = feeder.pack(body)
                if rc > 0:
                    packed[t] += rc

        def flusher():
            while not stop.is_set():
                feeder.flush()

        ts = [
            threading.Thread(target=packer, args=(t,))
            for t in range(n_packers)
        ]
        fs = [threading.Thread(target=flusher) for _ in range(2)]
        for t in ts + fs:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        for t in fs:
            t.join()
        # The teardown contract: after the final flush with no
        # producers in flight, NOTHING may remain packed-but-unserved.
        feeder.flush()
        st = feeder.stats()
        total = sum(packed)
        assert total > 0
        assert st["feeder_rows"] == total
        assert st["feeder_served_rows"] == total, (st, total)
        assert served[0] == total
    finally:
        feeder.close()


# -- the serve thread's depth of two (submit / complete) -----------------


def _body8(tag, n=8):
    return _payload(
        [dict(name="ov", unique_key=f"{tag}{i}xyz", hits=1, limit=9,
              duration=1000) for i in range(n)]
    )


class _Pair:
    """A stub handler pair: submit leaves every window IN_FLIGHT (or
    raises / answers at once where told to), complete writes the
    verdict lanes; both keep a log of (entry, rows' first hash, time).
    `gate`, where set, holds the FIRST submit inside Python until the
    test has packed the next window's rows."""

    def __init__(self, gate=None, submit_raises=(), complete_raises=()):
        self.log, self.gate = [], gate
        self.entered = threading.Event()
        self.submit_raises = set(submit_raises)
        self.complete_raises = set(complete_raises)
        self.served_rows = 0

    def _nth(self, entry):
        return sum(1 for e in self.log if e[0] == entry)

    def submit(self, slot, n_rows, n_rpcs, key_bytes):
        from gubernator_tpu.core.native_plane import IN_FLIGHT

        nth = self._nth("submit")
        self.log.append(("submit", int(slot.fnv1a[0]), time.monotonic()))
        if nth == 0 and self.gate is not None:
            self.entered.set()
            self.gate.wait(timeout=10)
        if nth in self.submit_raises:
            raise RuntimeError("submit stub")
        slot.pending = n_rows
        return IN_FLIGHT

    def complete(self, slot, n_rows, n_rpcs, key_bytes):
        nth = self._nth("complete")
        self.log.append(("complete", int(slot.fnv1a[0]), time.monotonic()))
        assert slot.pending == n_rows  # this window's own, nobody else's
        slot.pending = None
        if nth in self.complete_raises:
            raise RuntimeError("complete stub")
        self.served_rows += n_rows
        slot.out_status[:n_rows] = 0
        slot.out_limit[:n_rows] = 9
        slot.out_remaining[:n_rows] = 8
        slot.out_reset[:n_rows] = 0
        slot.rpc_status[:n_rpcs] = 0
        return 0

    def feeder(self, **kw):
        from gubernator_tpu.core.native_plane import NativeColumnarFeeder

        kw = dict(dict(n_slots=4, max_rows=64, flush_rows=8, window_s=0.5), **kw)
        return NativeColumnarFeeder(
            window_handler=self.submit, window_complete=self.complete, **kw)

    def entries(self):
        first = {}
        for _entry, key, _t in self.log:
            first.setdefault(key, len(first) + 1)
        return [(entry, first[key]) for entry, key, _t in self.log]


def test_a_window_worth_a_dispatch_is_submitted_before_the_read():
    """Window 2 holds an eighth of flush_rows when window 1's submit
    returns: the serve thread submits 2 without its group-commit wait
    (its intern, pack and launch run while 1's step runs), only then
    completes and scatters 1, and counts the overlap; 2, with nothing
    waiting behind it, is completed at once."""
    pair = _Pair(gate=threading.Event())
    feeder = pair.feeder(flush_rows=32, window_s=5.0)
    try:
        for tag in "abcd":
            assert feeder.pack(_body8(tag)) == 8  # the fourth seals window 1
        assert pair.entered.wait(timeout=10)
        assert feeder.pack(_body8("e", 4)) == 4  # 4 of 32 rows: unsealed
        pair.gate.set()
        deadline = time.monotonic() + 5.0
        while len(pair.log) < 4 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert pair.entries() == [
            ("submit", 1), ("submit", 2), ("complete", 1), ("complete", 2)]
        st = feeder.stats()
        assert st["feeder_windows_overlapped"] == 1
        assert st["feeder_windows"] == 2
        assert st["feeder_served_rows"] == st["feeder_rows"] == 36
    finally:
        feeder.close()


def _ring():
    import ctypes

    lib = h2_fast.load()
    return lib, ctypes.c_void_p(lib.evr_create(1024))


def _drain(lib, ring, kind):
    import ctypes

    out = np.zeros(4 * 1024, dtype=np.int64)
    n = lib.evr_drain(ring, out.ctypes.data_as(ctypes.c_void_p), 1024)
    rec = out[: 4 * n].reshape(n, 4)
    return rec[rec[:, 0] == kind]


def test_every_submitted_window_records_its_wait_and_the_serve_cpu():
    """One feeder_window_wait event (kind 13) a submitted window, items
    its RPCs, the time the serve thread sat in its waits before taking
    it; the serve thread's CPU in its Python entries grows with the
    windows it enters."""
    lib, ring = _ring()
    pair = _Pair()
    feeder = pair.feeder(flush_rows=64, window_s=0.5)
    feeder.attach_ring(ring)
    try:
        cpu = [feeder.stats()["feeder_serve_cpu_ns"]]
        for n, tag in enumerate("abc", start=1):
            for i in range(n):
                assert feeder.pack(_body8(f"{tag}{i}")) == 8
            time.sleep(0.02)  # the serve thread waits in the window
            feeder.flush()
            st = feeder.stats()
            assert st["feeder_windows"] == n
            cpu.append(st["feeder_serve_cpu_ns"])
        assert cpu[0] < cpu[1] < cpu[2] < cpu[3], cpu
        waits = _drain(lib, ring, 13)
        assert waits[:, 3].tolist() == [1, 2, 3]  # RPCs a window
        assert (waits[:, 2] >= 10_000_000).all(), waits  # >= 10 ms blocked
    finally:
        feeder.close()
        lib.evr_free(ring)


def test_a_window_taken_with_another_in_flight_waited_for_nothing():
    """Window 2 is submitted while window 1 is in flight: neither of
    the serve thread's waits ran before it, so its event reads 0."""
    lib, ring = _ring()
    pair = _Pair(gate=threading.Event())
    feeder = pair.feeder(flush_rows=32, window_s=5.0)
    feeder.attach_ring(ring)
    try:
        for tag in "abcd":
            assert feeder.pack(_body8(tag)) == 8  # the fourth seals window 1
        assert pair.entered.wait(timeout=10)
        assert feeder.pack(_body8("e", 4)) == 4
        pair.gate.set()
        deadline = time.monotonic() + 5.0
        while feeder.stats()["feeder_windows"] < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert feeder.stats()["feeder_windows_overlapped"] == 1
        waits = _drain(lib, ring, 13)
        assert waits[:, 3].tolist() == [4, 1]
        assert waits[0, 2] > 0 and waits[1, 2] == 0
    finally:
        feeder.close()
        lib.evr_free(ring)


def test_a_thin_window_keeps_its_group_commit_wait():
    """Window 2 holds less than an eighth of flush_rows when window 1's
    submit returns — a herd's straggler: submitted now it would split
    what the group-commit wait gathers into one dispatch, so 1 is
    completed at once and 2 is left to the idle path (here: a flush)."""
    pair = _Pair(gate=threading.Event())
    feeder = pair.feeder(flush_rows=32, window_s=5.0)
    try:
        for tag in "abcd":
            assert feeder.pack(_body8(tag)) == 8
        assert pair.entered.wait(timeout=10)
        assert feeder.pack(_body8("e", 3)) == 3  # 3 of 32 rows: thin
        pair.gate.set()
        deadline = time.monotonic() + 5.0
        while len(pair.log) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.05)
        assert pair.entries() == [("submit", 1), ("complete", 1)]
        feeder.flush()
        assert pair.entries() == [
            ("submit", 1), ("complete", 1), ("submit", 2), ("complete", 2)]
        st = feeder.stats()
        assert st["feeder_windows_overlapped"] == 0
        assert st["feeder_served_rows"] == st["feeder_rows"] == 35
    finally:
        feeder.close()


def test_nothing_waiting_is_answered_at_once():
    """No other window holds rows: complete follows submit with no
    sleep on the condvar or the window timer (0.5 s here) — an answer
    the device has finished is never held for arrivals."""
    pair = _Pair()
    feeder = pair.feeder()
    try:
        for tag in "abc":
            assert feeder.pack(_body8(tag)) == 8
            feeder.flush()
        assert pair.entries() == [
            ("submit", 1), ("complete", 1), ("submit", 2), ("complete", 2),
            ("submit", 3), ("complete", 3)]
        for sub, com in zip(pair.log[0::2], pair.log[1::2]):
            assert com[2] - sub[2] < 0.25  # half the window timer
        assert feeder.stats()["feeder_windows_overlapped"] == 0
    finally:
        feeder.close()


def test_flush_with_a_window_in_flight_conserves_rows():
    """Packers and flushers race the overlapped loop: at quiesce every
    packed row has been submitted once and completed once, in ring
    order, and nothing is left sealed."""
    pair = _Pair()
    feeder = pair.feeder(window_s=0.0005)
    try:
        body = _body8("f")
        packed = [0] * 4
        stop = threading.Event()

        def packer(t):
            for _ in range(150):
                rc = feeder.pack(body)
                if rc > 0:
                    packed[t] += rc

        def flusher():
            while not stop.is_set():
                feeder.flush()

        ts = [threading.Thread(target=packer, args=(t,)) for t in range(4)]
        fs = [threading.Thread(target=flusher) for _ in range(2)]
        for t in ts + fs:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        for t in fs:
            t.join()
        feeder.flush()
        st, total = feeder.stats(), sum(packed)
        assert total > 0 and st["feeder_rows"] == total
        assert st["feeder_served_rows"] == total == pair.served_rows, st
        assert st["feeder_open_rows"] == 0
        submits = [e for e in pair.log if e[0] == "submit"]
        assert len(submits) == st["feeder_windows"] == len(pair.log) / 2
        assert st["feeder_windows_overlapped"] < len(submits)
        # at most one window in flight: a submit is followed by its own
        # complete, or by one more submit and then the older complete
        depth = 0
        for entry, _key, _t in pair.log:
            depth += 1 if entry == "submit" else -1
            assert 0 <= depth <= 2
    finally:
        feeder.close()


def test_stop_completes_the_window_in_flight_first():
    """stop() while window 1 is in flight and window 2 holds rows: 1
    is completed (its step has run; its callers are owed the answers),
    2 is drained without entering Python, and every slot is released."""
    pair = _Pair(gate=threading.Event())
    feeder = pair.feeder()
    try:
        assert feeder.pack(_body8("a")) == 8
        assert pair.entered.wait(timeout=10)
        assert feeder.pack(_body8("b")) == 8
        stopper = threading.Thread(target=feeder.stop)
        stopper.start()
        time.sleep(0.05)  # cf_stop has set `closing` and waits to join
        pair.gate.set()
        stopper.join(timeout=10)
        assert not stopper.is_alive()
        assert pair.entries() == [("submit", 1), ("complete", 1)]
        st = feeder.stats()
        assert st["feeder_windows"] == 2  # one completed, one drained
        assert st["feeder_served_rows"] == 8 and st["feeder_rows"] == 16
        assert st["feeder_drained_rows"] == 8 and st["feeder_open_rows"] == 0
        assert all(s.pending is None for s in feeder.slots)
    finally:
        feeder.close()


def test_an_entry_that_raises_fails_its_own_window_only():
    """The second window's submit raises, the fourth's complete raises:
    each fails its window (INTERNAL for its RPCs) and the loop serves
    the next one as if nothing had happened."""
    from gubernator_tpu.utils.metrics import swallowed_counts

    before = swallowed_counts()
    pair = _Pair(submit_raises={1}, complete_raises={2})
    feeder = pair.feeder()
    try:
        for tag in "abcde":
            assert feeder.pack(_body8(tag)) == 8
            feeder.flush()
        assert pair.entries() == [
            ("submit", 1), ("complete", 1), ("submit", 2),
            ("submit", 3), ("complete", 3), ("submit", 4), ("complete", 4),
            ("submit", 5), ("complete", 5)]
        st, after = feeder.stats(), swallowed_counts()
        assert st["feeder_windows"] == 5 and pair.served_rows == 24
        assert after.get("feeder.window", 0) - before.get("feeder.window", 0) == 1
        assert after.get("feeder.complete", 0) - before.get("feeder.complete", 0) == 1
    finally:
        feeder.close()


def test_a_failed_window_answers_internal_and_the_front_serves_on():
    """Through the front: a serve that raises at submit, then one whose
    pending raises at complete, each answer INTERNAL for that RPC
    alone; the RPCs before and after are answered as ever."""
    import grpc

    d = _spawn_fast_daemon(ledger=False)
    try:
        ch, call = _fast_call(d)
        payload = _payload(
            [dict(name="boom", unique_key="k1end", hits=1, limit=9,
                  duration=60_000)]
        )
        real = d.instance.serve_decoded_local

        class Boom:
            def get(self):
                raise RuntimeError("complete stub")

        def raises(dec, want_async=False):
            raise RuntimeError("submit stub")

        def pending_raises(dec, want_async=False):
            real(dec, want_async).get()  # the hit is applied all the same
            return Boom()

        assert pb.GetRateLimitsResp.FromString(call(payload)).responses[0].remaining == 8
        for stub in (raises, pending_raises):
            d.instance.serve_decoded_local = stub
            with pytest.raises(grpc.RpcError) as err:
                call(payload)
            assert err.value.code() == grpc.StatusCode.INTERNAL
        d.instance.serve_decoded_local = real
        assert pb.GetRateLimitsResp.FromString(call(payload)).responses[0].remaining == 6
        st = d.h2_fast.stats()
        assert st["errors"] == 2 and st["feeder_window_errors"] == 2
        ch.close()
    finally:
        d.close()


def test_concurrent_pack_parity():
    """Many Python threads pack concurrently; every packed row must
    appear exactly once across the captured windows (claim/commit
    protocol: no losses, no duplicates, offsets gap-free)."""
    feeder, captured = _capture_feeder(
        n_slots=4, max_rows=4096, window_s=0.002
    )
    try:
        n_threads, reps = 8, 50
        body = _payload(
            [dict(name="cc", unique_key=f"u{i}qrs", hits=1, limit=9,
                  duration=1000) for i in range(5)]
        )
        dec = wire_codec.decode_reqs(body, 64, 0)
        ok = [0] * n_threads

        def worker(t):
            for _ in range(reps):
                rc = feeder.pack(body)
                if rc > 0:
                    ok[t] += rc

        ts = [
            threading.Thread(target=worker, args=(t,))
            for t in range(n_threads)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        feeder.flush()
        total = sum(ok)
        assert total > 0
        got_rows = sum(len(c["algo"]) for c in captured)
        assert got_rows == total
        klen = int(dec.key_offsets[-1])
        for c in captured:
            # Offsets stay cumulative and gap-free across interleaved
            # claims, and every row's key slice is one of the body's.
            lens = np.diff(c["key_offsets"])
            assert c["key_offsets"][0] == 0
            assert int(c["key_offsets"][-1]) == len(c["key_buf"])
            assert (lens > 0).all()
            n = len(c["algo"])
            assert n % dec.n == 0  # whole RPCs only
            for r0 in range(0, n, dec.n):
                o0 = int(c["key_offsets"][r0])
                np.testing.assert_array_equal(
                    c["key_buf"][o0 : o0 + klen], dec.key_buf
                )
    finally:
        feeder.close()


def test_encode_resps_hint_parity_and_metadata():
    """The hint encoder is wire_encode_resps plus ONLY the metadata
    entry on OVER items: parse both and compare field-by-field."""
    from gubernator_tpu.types import Status

    status = np.array(
        [int(Status.UNDER_LIMIT), int(Status.OVER_LIMIT)], dtype=np.int32
    )
    limit = np.array([10, 10], dtype=np.int64)
    remaining = np.array([3, 0], dtype=np.int64)
    reset = np.array([50_000, 60_000], dtype=np.int64)
    plain = pb.GetRateLimitsResp.FromString(
        wire_codec.encode_resps(status, limit, remaining, reset)
    )
    hinted = pb.GetRateLimitsResp.FromString(
        wire_codec.encode_resps_hint(
            status, limit, remaining, reset,
            int(Status.OVER_LIMIT), 45_000,
        )
    )
    for a, b in zip(plain.responses, hinted.responses):
        assert (a.status, a.limit, a.remaining, a.reset_time) == (
            b.status, b.limit, b.remaining, b.reset_time
        )
    assert not dict(hinted.responses[0].metadata)  # UNDER: no hint
    assert dict(hinted.responses[1].metadata) == {
        "retry_after_ms": "15000"
    }
    # Stale reset clamps at zero, never negative.
    again = pb.GetRateLimitsResp.FromString(
        wire_codec.encode_resps_hint(
            status, limit, remaining, reset,
            int(Status.OVER_LIMIT), 99_000,
        )
    )
    assert dict(again.responses[1].metadata) == {"retry_after_ms": "0"}


def _spawn_fast_daemon(**over):
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon

    conf = DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=4096,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        h2_fast_address="127.0.0.1:0",
        h2_fast_window=0.001,
        **over,
    )
    return spawn_daemon(conf)


def _fast_call(daemon):
    import grpc

    from gubernator_tpu.net.grpc_service import V1_SERVICE

    ch = grpc.insecure_channel(daemon.h2_fast_address)
    return ch, ch.unary_unary(
        f"/{V1_SERVICE}/GetRateLimits",
        request_serializer=lambda r: r,
        response_deserializer=lambda r: r,
    )


def test_feeder_e2e_through_front():
    """Fall-through RPCs (ledger off ⇒ every RPC falls through) ride
    the feeder ring end-to-end: answers match the engine contract,
    OVER_LIMIT carries the retry hint, and the byte window path stays
    idle (windows == 0)."""
    d = _spawn_fast_daemon(ledger=False)
    try:
        ch, call = _fast_call(d)
        payload = _payload(
            [
                dict(name="fe2e", unique_key=f"k{i}end", hits=1, limit=2,
                     duration=60_000)
                for i in range(3)
            ]
        )
        for _ in range(3):
            raw = call(payload)
        resp = pb.GetRateLimitsResp.FromString(raw)
        sts = [r.status for r in resp.responses]
        assert sts == [1, 1, 1]  # limit 2, third round: all OVER
        for r in resp.responses:
            hint = int(dict(r.metadata)["retry_after_ms"])
            # reset-derived and in the ENGINE clock domain: a fresh
            # 60 s bucket's reset is near-full, so the hint must be a
            # sane wait, not a clock-offset artifact.
            assert 50_000 < hint <= 60_000, hint
        st = d.h2_fast.stats()
        assert st["feeder_front_rpcs"] == 3
        assert st["feeder_windows"] >= 1
        assert st["windows"] == 0  # byte window path never entered
        assert st["errors"] == 0
        ch.close()
    finally:
        d.close()


def test_feeder_front_declines_global_to_byte_path():
    """A GLOBAL-behavior RPC must NOT enter the feeder (C-side
    disqualify) — it falls to the byte window path and answers
    UNIMPLEMENTED exactly like the pre-feeder front."""
    import grpc

    from gubernator_tpu.types import Behavior

    d = _spawn_fast_daemon(ledger=False)
    try:
        ch, call = _fast_call(d)
        payload = _payload(
            [
                dict(name="g", unique_key="k1end", hits=1, limit=5,
                     duration=60_000, behavior=int(Behavior.GLOBAL))
            ]
        )
        with pytest.raises(grpc.RpcError) as err:
            call(payload)
        assert err.value.code() == grpc.StatusCode.UNIMPLEMENTED
        st = d.h2_fast.stats()
        assert st["feeder_front_rpcs"] == 0
        assert st["feeder_declined"] >= 1
        assert st["windows"] >= 1  # byte path handled it
        ch.close()
    finally:
        d.close()


def test_feeder_disabled_restores_byte_path(monkeypatch):
    monkeypatch.setenv("GUBER_NATIVE_FEEDER", "0")
    d = _spawn_fast_daemon(ledger=False)
    try:
        assert d.h2_fast.feeder is None
        ch, call = _fast_call(d)
        payload = _payload(
            [dict(name="off", unique_key="k1end", hits=1, limit=5,
                  duration=60_000)]
        )
        raw = call(payload)
        resp = pb.GetRateLimitsResp.FromString(raw)
        assert resp.responses[0].remaining == 4
        st = d.h2_fast.stats()
        assert st["windows"] >= 1
        assert "feeder_rpcs" not in st
        ch.close()
    finally:
        d.close()


def test_retry_hints_disabled(monkeypatch):
    monkeypatch.setenv("GUBER_RETRY_HINTS", "0")
    d = _spawn_fast_daemon(ledger=False)
    try:
        ch, call = _fast_call(d)
        payload = _payload(
            [dict(name="noh", unique_key="k1end", hits=1, limit=1,
                  duration=60_000)]
        )
        call(payload)
        resp = pb.GetRateLimitsResp.FromString(call(payload))
        assert resp.responses[0].status == 1  # OVER
        assert not dict(resp.responses[0].metadata)
        ch.close()
    finally:
        d.close()


def test_feeder_stats_in_front_stats():
    d = _spawn_fast_daemon(ledger=False)
    try:
        st = d.h2_fast.stats()
        for k in (
            "feeder_rpcs", "feeder_rows", "feeder_windows",
            "feeder_ring_full", "feeder_declined",
        ):
            assert k in st
    finally:
        d.close()
