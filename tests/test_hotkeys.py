"""Hot-key sketch: space-saving invariants + the windowed rate decay,
on both tiers of the table, and the two tiers held to the same answers.

The decay tests drive an injected clock, pinning the demotion
contract the replication plane depends on (cluster/replication.py): a
key hot an hour ago must read ~0 in `top_rates()` even though its
cumulative count still ranks it in `top()`.

The native table (core/native/hotkeys.cpp) is what serves; the Python
one is its reference.  The differential test compares every read of
the two after every batch of one seeded stream; the threaded test and
the served-path test run the native one where requests do.
"""

import json
import logging
import sys
import threading
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.utils import hotkeys
from gubernator_tpu.utils.hotkeys import SpaceSaving

TIERS = [pytest.param(False, id="python"), pytest.param(True, id="native")]


def table(native, **kw) -> SpaceSaving:
    """A sketch on the named tier; the native case skips where the
    library cannot be built (decided here, not at import: collection
    builds nothing)."""
    if native and hotkeys.load() is None:
        pytest.skip("native hot-key table unavailable (no compiler, or "
                    "GUBERNATOR_TPU_NATIVE=0)")
    return SpaceSaving(native=native, **kw)


class _Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.mark.parametrize("native", TIERS)
def test_space_saving_counts_and_error_bounds(native):
    ss = table(native, capacity=4)
    for i in range(8):
        ss.offer(f"k{i}".encode(), i + 1)
    top = ss.top(4)
    assert len(top) == 4
    # Every reported count over-estimates by at most its error bound.
    for _key, count, err in top:
        assert count >= 1
        assert err <= count
    assert ss.stats()["tracked"] == 4
    assert ss.stats()["tier"] == ("native" if native else "python")


@pytest.mark.parametrize("native", TIERS)
def test_rate_reflects_current_window_only(native):
    clk = _Clock()
    ss = table(native, capacity=16, window_s=1.0, now=clk)
    ss.offer(b"hot", 500)
    assert ss.rate(b"hot") == 500.0
    # Next window: the previous window's mass decays with the elapsed
    # fraction of the new one.
    clk.t = 1.5
    assert 0 < ss.rate(b"hot") <= 500.0
    # Two windows later: a key nobody offers reads 0, cumulative count
    # untouched.
    clk.t = 3.0
    assert ss.rate(b"hot") == 0.0
    assert ss.top(1)[0][:2] == (b"hot", 500)


@pytest.mark.parametrize("native", TIERS)
def test_top_rates_tracks_a_moving_zipf_hot_set(native):
    """Rotate the hot set across three windows; top_rates must follow
    the CURRENT hot keys while top() stays dominated by history."""
    clk = _Clock()
    rng = np.random.default_rng(3)
    ss = table(native, capacity=64, window_s=1.0, now=clk)
    phases = [b"alpha", b"beta", b"gamma"]
    for p, hot in enumerate(phases):
        clk.t = p * 2.0  # two windows apart: the old hot set decays out
        # Zipf-ish: the phase's hot key takes ~90% of offers.
        for _ in range(200):
            if rng.random() < 0.9:
                ss.offer(hot, 5)
            else:
                ss.offer(b"cold%d" % rng.integers(0, 20), 1)
        rates = ss.top_rates(3)
        assert rates[0][0] == hot, (p, rates)
        # Earlier phases' hot keys must have decayed out of the rate
        # ranking entirely.
        for earlier in phases[:p]:
            assert all(k != earlier or r < 1.0 for k, r, _l, _d in rates)
    # Cumulative top() still remembers phase 0's mass.
    assert b"alpha" in [k for k, _c, _e in ss.top(5)]


@pytest.mark.parametrize("native", TIERS)
def test_rate_params_carry_last_limit_duration(native):
    clk = _Clock()
    ss = table(native, capacity=8, window_s=1.0, now=clk)
    ss.offer_many_params([(b"k", 10, 1000, 60_000)])
    (key, rate, limit, duration), = ss.top_rates(1)
    assert (key, limit, duration) == (b"k", 1000, 60_000)
    assert rate == 10.0
    # A params-less offer must not clobber the stored params.
    ss.offer(b"k", 3)
    (_k, _r, limit, duration), = ss.top_rates(1)
    assert (limit, duration) == (1000, 60_000)


@pytest.mark.parametrize("native", TIERS)
def test_offer_columns_masks_ineligible_params(native):
    """offer_columns with a masked limit column (the service stamps 0
    for rows the lease algebra can't cover) must keep those keys'
    params at 0 so the promotion plane skips them."""
    clk = _Clock()
    ss = table(native, capacity=8, window_s=1.0, now=clk)
    keys = [b"aaa", b"bbb"]
    buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
    offs = np.array([0, 3, 6], dtype=np.int64)
    ss.offer_columns(
        buf, offs, np.array([4, 4]),
        hashes=np.array([11, 22], dtype=np.uint64),
        limit=np.array([100, 0]), duration=np.array([60_000, 60_000]),
    )
    by_key = {k: (lim, dur) for k, _r, lim, dur in ss.top_rates(4)}
    assert by_key[b"aaa"] == (100, 60_000)
    # limit 0 is the "never promotable" stamp the replication plane
    # keys off; duration alone is inert.
    assert by_key[b"bbb"][0] == 0


@pytest.mark.parametrize("native", TIERS)
def test_eviction_resets_window_counters(native):
    """A newcomer that evicts a counter inherits the cumulative error
    bound but NOT the old key's rate — rates carry no inherited
    error."""
    clk = _Clock()
    ss = table(native, capacity=2, window_s=1.0, now=clk)
    ss.offer(b"a", 10)
    ss.offer(b"b", 20)
    ss.offer(b"c", 1)  # evicts the min (a): inherits count 10
    top = {k: (c, e) for k, c, e in ss.top(2)}
    assert top[b"c"] == (11, 10)
    assert ss.rate(b"c") == 1.0  # window counter started fresh


# -- the two tiers, one set of answers ----------------------------------


def _fnv1a(key: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in key:
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def _columns(keys):
    buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
    offs = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offs[1:])
    hashes = np.array([_fnv1a(k) for k in keys], dtype=np.uint64)
    return buf, offs, hashes


def test_native_table_answers_as_the_python_one():
    """One seeded stream into both tiers: 24,000 offers in 60 batches,
    Zipf-like over 50,000 keys against K = 48 (so most offers evict,
    and with hits of 0..2 the counts at the bottom tie constantly —
    the victim is then chosen by key bytes), keys of unequal length
    (one a prefix of another), the clock stepped over window
    boundaries and once backwards, `idx` subsets, `hits = 0` rows,
    batches with and without hashes and params, and the single-key and
    row entry points between them.  Every read is compared after every
    batch."""
    K = 48
    rng = np.random.default_rng(20260)
    clk = _Clock(100.0)
    py = table(False, capacity=K, window_s=2.0, now=clk)
    nat = table(True, capacity=K, window_s=2.0, now=clk)
    assert (py.tier, nat.tier) == ("python", "native")
    both = (py, nat)
    probe = [b"z_1", b"z_2", b"z_10", b"z_1\x00", b"never-offered"]
    offered = 0
    for b in range(60):
        clk.t += float(rng.choice([0.0, 0.3, 1.1, 2.0, 4.5, -0.7]))
        n = 400
        ranks = rng.zipf(1.15, n) % 50_000
        keys = [b"z_%d" % r for r in ranks]
        # a key that another is a prefix of, and one with a NUL in it
        keys[0], keys[1] = b"z_1", b"z_1\x00"
        buf, offs, hashes = _columns(keys)
        hits = rng.integers(0, 3, n)
        limit = np.where(rng.random(n) < 0.5, rng.integers(1, 1000, n), 0)
        duration = rng.integers(1, 100_000, n)
        kind = b % 4
        idx = np.flatnonzero(rng.random(n) < 0.6)
        for ss in both:
            if kind == 0:
                ss.offer_columns(buf, offs, hits, hashes=hashes,
                                 limit=limit, duration=duration)
            elif kind == 1:
                ss.offer_columns(buf, offs, hits, idx=idx, hashes=hashes,
                                 limit=limit, duration=duration)
            elif kind == 2:
                ss.offer_columns(buf, offs, hits, hashes=hashes)
            else:
                ss.offer_columns(buf, offs, hits)  # per row, in order
            ss.offer(keys[5], 7)
            ss.offer_many([(keys[6], 2), (keys[7], 1)])
            ss.offer_many_params([
                (keys[8], 3, 500, 60_000), (keys[9], 1, 0, 1_000),
            ])
        offered += (len(idx) if kind == 1 else n)
        for read in ("top", "top_rates"):
            assert getattr(nat, read)(K) == getattr(py, read)(K), (b, read)
        assert nat.top(5) == py.top(5)
        for key in probe + keys[:20]:
            assert nat.rate(key) == py.rate(key), (b, key)
        want = dict(py.stats(), tier="native")
        assert nat.stats() == want, b
    assert offered >= 20_000
    assert py.stats()["tracked"] == K and py.stats()["offered"] > offered


def test_native_table_follows_a_capacity_change():
    """`capacity` is mutable on the class: growing admits more keys,
    shrinking evicts nothing by itself — both tiers alike."""
    tables = [table(n, capacity=4) for n in (False, True)]
    for ss in tables:
        for i in range(6):
            ss.offer(b"a%d" % i, i + 1)
        ss.capacity = 8
        for i in range(6):
            ss.offer(b"b%d" % i, 1)
        ss.capacity = 2
        ss.offer(b"c", 1)
        assert ss.capacity == 2
    assert tables[1].top(16) == tables[0].top(16)
    assert tables[1].stats()["tracked"] == tables[0].stats()["tracked"] == 8


@pytest.mark.parametrize("native", TIERS)
def test_eight_threads_lose_no_offer(native):
    """8 threads offer at once — a shared hot set through the batch
    entry, each thread's own keys through the single one.  The total
    is exact and, below capacity, every key is there with its exact
    count."""
    import sys

    ss = table(native, capacity=4096)
    shared = [b"shared-%d" % i for i in range(64)]
    buf, offs, hashes = _columns(shared)
    rounds, own = 40, 25
    errors = []

    def work(tid):
        try:
            for r in range(rounds):
                ss.offer_columns(buf, offs, np.full(64, 2), hashes=hashes)
                for j in range(own):
                    ss.offer(b"own-%d-%d" % (tid, j), 1 + (r & 1))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    per_own = sum(1 + (r & 1) for r in range(rounds))
    stats = ss.stats()
    assert stats["offered"] == 8 * rounds * 64 * 2 + 8 * own * per_own
    assert stats["tracked"] == 64 + 8 * own
    got = {k: (c, e) for k, c, e in ss.top(4096)}
    assert all(got[k] == (8 * rounds * 2, 0) for k in shared)
    assert all(
        got[b"own-%d-%d" % (t, j)] == (per_own, 0)
        for t in range(8) for j in range(own)
    )


# -- where requests meet it ---------------------------------------------


def test_served_path_offers_to_the_native_table():
    """A 1,000-item RPC through serve_wire_bytes on a CPU instance
    lands in the native table: one `service.hotkeys` observation, the
    keys in /debug/hotkeys beside the tier, the tier in /metrics."""
    from gubernator_tpu.config import DaemonConfig
    from gubernator_tpu.daemon import spawn_daemon
    from gubernator_tpu.net import wire_codec
    from gubernator_tpu.net.pb import gubernator_pb2 as pb

    if wire_codec.load() is None or hotkeys.load() is None:
        pytest.skip("native codec or hot-key table unavailable")
    d = spawn_daemon(DaemonConfig(
        grpc_listen_address="127.0.0.1:0",
        http_listen_address="127.0.0.1:0",
        cache_size=4096,
        peer_discovery_type="none",
        device_count=1,
        sweep_interval=0.0,
        ledger=False,
    ))
    try:
        inst = d.instance
        assert inst.hotkeys.stats()["tier"] == "native"
        keys = [f"k{i}" for i in range(960)] + ["hot"] * 40
        raw = pb.GetRateLimitsReq(requests=[
            pb.RateLimitReq(name="served", unique_key=k, hits=1, limit=1000,
                            duration=60_000)
            for k in keys
        ]).SerializeToString()
        before = inst.stage_timers["service.hotkeys"].count
        out = inst.serve_wire_bytes(raw)
        assert out is not None
        assert len(pb.GetRateLimitsResp.FromString(out).responses) == 1000
        assert inst.stage_timers["service.hotkeys"].count - before == 1
        stats = inst.hotkeys.stats()
        assert (stats["offered"], stats["tracked"]) == (1000, 961)
        (key, rate, limit, duration), = inst.hotkeys.top_rates(1)
        assert (key, limit, duration) == (b"served_hot", 1000, 60_000)
        hot = json.load(urllib.request.urlopen(
            f"http://{d.http_address}/debug/hotkeys", timeout=10
        ))
        assert hot["tier"] == "native" and hot["offered"] == 1000
        assert hot["top"][0] == {"key": "served_hot", "count": 40, "err": 0}
        body = urllib.request.urlopen(
            f"http://{d.http_address}/metrics", timeout=10
        ).read().decode()
        assert "gubernator_hotkeys_native 1.0" in body
    finally:
        d.close()


def test_python_tier_serves_and_says_so_without_the_library(
    monkeypatch, caplog
):
    """GUBERNATOR_TPU_NATIVE=0 (or no compiler): the Python table
    serves, and from_env says so in one warning."""
    monkeypatch.setenv("GUBERNATOR_TPU_NATIVE", "0")
    monkeypatch.setattr(hotkeys, "_lib", None)
    with caplog.at_level(logging.INFO, logger="gubernator_tpu"):
        ss = hotkeys.from_env()
    assert ss.stats()["tier"] == "python"
    ss.offer(b"k", 2)
    assert ss.top(1) == [(b"k", 2, 0)]
    said = [r for r in caplog.records if "hot-key sketch" in r.getMessage()]
    assert [r.levelno for r in said] == [logging.WARNING]
    assert "Python table serves" in said[0].getMessage()


# -- the native table under ThreadSanitizer ------------------------------

# Runs PRELOADED (tests/test_h2_server_san.py's pattern; numpy only,
# no jax: TSan instruments every malloc).  8 threads through the batch
# and the single entry while a ninth reads; the totals must be exact.
_SAN_SRC = r"""
import threading
import numpy as np

from gubernator_tpu.utils.hotkeys import SpaceSaving

ss = SpaceSaving(capacity=64, native=True)
assert ss.tier == "native"
# 96 rows a batch: past hotkeys._RELEASE_ROWS, so the walk runs with
# the interpreter lock released — the case there is to race.
keys = [b"shared-%d" % i for i in range(96)]
buf = np.frombuffer(b"".join(keys), dtype=np.uint8)
offs = np.zeros(len(keys) + 1, dtype=np.int64)
np.cumsum([len(k) for k in keys], out=offs[1:])
ROUNDS, OWN = 200, 40   # 8 x 40 own keys >> capacity: evictions race
stop = threading.Event()

def work(tid):
    for r in range(ROUNDS):
        ss.offer_columns(buf, offs, np.ones(len(keys), dtype=np.int64))
        for j in range(OWN):
            ss.offer(b"own-%d-%d" % (tid, j), 1)

def read():
    while not stop.is_set():
        ss.top(8); ss.top_rates(8); ss.rate(keys[0]); ss.stats()

reader = threading.Thread(target=read)
reader.start()
threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
for t in threads: t.start()
for t in threads: t.join()
stop.set(); reader.join()
st = ss.stats()
assert st["offered"] == 8 * ROUNDS * (len(keys) + OWN), st
assert st["tracked"] == 64, st
print("hotkeys san stress ok offered=%d" % st["offered"], flush=True)
"""


@pytest.mark.slow
def test_native_table_threaded_offers_under_tsan(monkeypatch):
    """TSan over the native table's mutex: batch offers, single offers
    and readers from nine threads.  `GUBER_NATIVE_SAN=1 pytest -m slow
    tests/test_hotkeys.py`."""
    import os
    import subprocess
    from pathlib import Path

    from gubernator_tpu.core.native_build import (
        ensure_built, sanitizer_preload,
    )

    if os.environ.get("GUBER_NATIVE_SAN", "") in ("", "0"):
        pytest.skip("set GUBER_NATIVE_SAN=1 to run the TSan stress")
    preload = sanitizer_preload("thread")
    if preload is None:
        pytest.skip("libtsan not available from this toolchain")
    # Build the instrumented .so here (compiling needs no preload); the
    # subprocess then dlopens the cached artifact.
    monkeypatch.setenv("GUBER_NATIVE_SAN", "thread")
    if ensure_built("hotkeys") is None:
        pytest.skip("sanitized hotkeys build failed (no g++?)")
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    supp = repo / "tests" / "tsan_suppressions.txt"
    proc = subprocess.run(
        [sys.executable, "-c", _SAN_SRC],
        cwd=repo,
        env=dict(
            env,
            LD_PRELOAD=preload,
            TSAN_OPTIONS=(
                "halt_on_error=1 exitcode=66 report_thread_leaks=0 "
                f"report_mutex_bugs=0 detect_deadlocks=0 suppressions={supp}"
            ),
            PYTHONMALLOC="malloc",
            GUBERNATOR_TPU_X64="0",
            GUBERNATOR_TPU_COMPILE_CACHE="0",
        ),
        capture_output=True, text=True, timeout=300,
    )
    assert "ThreadSanitizer" not in proc.stderr, (
        "TSan report from hotkeys:\n" + proc.stderr[-4000:]
    )
    assert proc.returncode == 0, (
        f"hotkeys san stress failed rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-1000:]}\nstderr: {proc.stderr[-3000:]}"
    )
    assert "hotkeys san stress ok" in proc.stdout
