"""ThreadSanitizer (and, for the reactor plane's hand-off,
AddressSanitizer) stress of the native h2 server (guberlint's native
runtime companion, STATIC_ANALYSIS.md).

Builds core/native/h2_server.cpp with GUBER_NATIVE_SAN=thread|address
(separate cache tags, -fsanitize=... -O1 -g) and hammers it from
concurrent gRPC clients in a SUBPROCESS with the sanitizer runtime
LD_PRELOADed — a sanitizer runtime cannot initialize inside an
already-running uninstrumented python, so in-process loading is not an
option.  Any data race (TSan) or bad access (ASan) inside the
instrumented .so fails the subprocess (halt_on_error=1, exitcode=66).

Marked slow: TSan startup + the hammer take tens of seconds; run it
with `GUBER_NATIVE_SAN=1 pytest -m slow tests/test_h2_server_san.py`
or via the scheduled soak, not tier-1.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from gubernator_tpu.core.native_build import ensure_built, sanitizer_preload

REPO = Path(__file__).resolve().parents[1]

# Runs PRELOADED (TSan): the instrumented server + a flat columnar
# callback.  It prints its port, then blocks on stdin until the parent
# closes it — the server process must NEVER fork once its C threads
# run (fork from a TSan'd multithreaded process deadlocks), so the
# unpreloaded pytest parent is the one that spawns the client hammer.
_SERVER_SRC = r"""
import ctypes, os, sys, time
import numpy as np

from gubernator_tpu.net import h2_fast

lib = h2_fast.load()
assert lib is not None, "sanitized h2_server build unavailable"

def window(buf, length, counts_ptr, lens_ptr, n_rpcs, total, out_ptr,
           status_ptr):
    n = int(total); nr = int(n_rpcs)
    if nr > 0 and status_ptr:
        np.ctypeslib.as_array(
            ctypes.cast(status_ptr, ctypes.POINTER(ctypes.c_int64)),
            shape=(nr,),
        )[:] = 0
    if n > 0 and out_ptr:
        cols = np.ctypeslib.as_array(
            ctypes.cast(out_ptr, ctypes.POINTER(ctypes.c_int64)),
            shape=(4 * n,),
        )
        cols[:n] = 0          # status UNDER_LIMIT
        cols[n:2 * n] = 100   # limit
        cols[2 * n:3 * n] = 99  # remaining
        cols[3 * n:] = 0      # reset
    return 0

cb = h2_fast._CALLBACK(window)
# SAN_EVENT_FRONT=1: the epoll reactor plane (2 reactors racing the
# dispatch/feeder threads through the shared Conn write side);
# otherwise the thread-per-conn plane with 2 listener lanes.
event = int(os.environ.get("SAN_EVENT_FRONT", "0"))
handle = lib.h2s_start(0, 500, 16384, 4096, 2, event, 2, 0, cb)
assert handle, "h2 server failed to bind"

# Columnar feeder attached: the hammer's fall-through RPCs now run
# the REAL integrated path — conn threads cf_pack into the ring, the
# feeder serve thread enters this columnar handler pair, and the
# scatter rides h2s_feeder_respond back through the connections — all
# under TSan.  Windows are tiny (flush_rows=8) so seal/rotate churns,
# and every submit leaves its window in flight, so the serve thread
# submits window k+1 before it completes and scatters window k
# whenever rows are waiting: the tokens of two windows are live at
# once, and every one of them is answered or released.
from gubernator_tpu.core import native_plane

def feeder_submit(slot, n_rows, n_rpcs, key_bytes):
    time.sleep(0.001)  # an intern + pack's worth: the next window fills
    slot.pending = n_rows
    return native_plane.IN_FLIGHT

def feeder_complete(slot, n_rows, n_rpcs, key_bytes):
    assert slot.pending == n_rows
    slot.pending = None
    slot.out_status[:n_rows] = 0
    slot.out_limit[:n_rows] = 100
    slot.out_remaining[:n_rows] = 99
    slot.out_reset[:n_rows] = 0
    slot.rpc_status[:n_rpcs] = 0
    return 0

feeder = native_plane.NativeColumnarFeeder(
    n_slots=3, max_rows=256, max_rpcs=64, flush_rows=8,
    window_s=0.0005, window_handler=feeder_submit,
    window_complete=feeder_complete,
)
lib.h2s_attach_feeder(handle, feeder.handle)

print("PORT", int(lib.h2s_port(handle)), flush=True)
sys.stdin.read()  # parent closes stdin when the hammer is done
# Stats BEFORE stop: h2s_stop frees the server (TSan caught this
# harness's original stats-after-stop as a heap-use-after-free).
# 16 slots: h2s_stats writes eleven now (conn-plane fields) — an
# 8-slot buffer here would be a 24-byte heap overflow.
stats = np.zeros(16, dtype=np.int64)
lib.h2s_stats(handle, stats.ctypes.data_as(ctypes.c_void_p))
# Teardown order contract (net/h2_fast.close): detach, drain-stop the
# feeder, stop the server, then free the ring.
lib.h2s_attach_feeder(handle, None)
feeder.stop()
fst = feeder.stats()
lib.h2s_stop(handle)
feeder.close()
assert stats[5] > 0, "hammer never exercised the feeder path"
if event:
    # The scatter handed its responses to the connections' reactors:
    # the posted lists and their kicks ran under the hammer and the
    # churn client's resets, and run on through this teardown.
    assert 0 < stats[13] <= stats[0], list(stats)
assert fst["feeder_windows_overlapped"] > 0, fst
assert fst["feeder_open_rows"] == 0, fst
if event:
    # The churn client packs on into the teardown, so the stop drains
    # claimed windows without entering Python: every packed row is
    # served or drained, none lost.
    assert (fst["feeder_served_rows"] + fst["feeder_drained_rows"]
            == fst["feeder_rows"]), fst
else:
    # No client runs into the teardown: every packed row was served.
    assert fst["feeder_served_rows"] == fst["feeder_rows"], fst
    assert fst["feeder_drained_rows"] == 0, fst
print("san stress ok rpcs=%d windows=%d feeder_rpcs=%d overlapped=%d"
      % (stats[0], stats[1], stats[5], fst["feeder_windows_overlapped"]),
      flush=True)
"""

_CLIENT_SRC = r"""
import sys, threading
import grpc
from gubernator_tpu.net.pb import gubernator_pb2 as pb

port = int(sys.argv[1])
payload = pb.GetRateLimitsReq(
    requests=[
        pb.RateLimitReq(name="san", unique_key=str(i), hits=1, limit=100,
                        duration=60000)
        for i in range(8)
    ]
).SerializeToString()

N_THREADS = 8
N_RPCS = 60
errs = []

def hammer(tid):
    try:
        ch = grpc.insecure_channel("127.0.0.1:%d" % port)
        stub = ch.unary_unary(
            "/pb.gubernator.V1/GetRateLimits",
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )
        for i in range(N_RPCS):
            resp = stub(payload, timeout=30)
            out = pb.GetRateLimitsResp.FromString(resp)
            assert len(out.responses) == 8, len(out.responses)
        ch.close()
    except Exception as e:
        errs.append("t%d: %r" % (tid, e))

threads = [threading.Thread(target=hammer, args=(t,)) for t in range(N_THREADS)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=120)
if errs:
    print("CLIENT ERRORS:", errs[:5], file=sys.stderr)
    sys.exit(1)
print("client ok: %d rpcs" % (N_THREADS * N_RPCS))
"""

# Runs unsanitized beside the hammer and on through the server's
# teardown, until killed: raw connections that send three RPCs and
# reset the socket (SO_LINGER 0) at once or after a first read — peers
# that die between a response's hand-off and its reactor's flush.
_CHURN_SRC = r"""
import socket, struct, sys, time
from gubernator_tpu.net.pb import gubernator_pb2 as pb

port = int(sys.argv[1])
body = pb.GetRateLimitsReq(requests=[pb.RateLimitReq(
    name="churn", unique_key="k", hits=1, limit=100, duration=60000)]
).SerializeToString()
def frame(ftype, flags, sid, payload=b""):
    return (struct.pack(">I", len(payload))[1:] + bytes([ftype, flags])
            + struct.pack(">I", sid) + payload)
wire = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n" + b"".join(
    frame(1, 4, sid) + frame(0, 1, sid, b"\x00" + struct.pack(">I", len(body)) + body)
    for sid in (1, 3, 5))
i = 0
while True:
    i += 1
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=1)
        s.sendall(wire)
        if i % 2:
            s.recv(64)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0))
        s.close()
    except OSError:
        time.sleep(0.005)  # the server is going or gone: keep on until killed
"""


@pytest.mark.slow
@pytest.mark.parametrize("event_front,san", [
    (0, "thread"), (1, "thread"), (1, "address")],
    ids=["threaded", "reactor", "reactor-asan"])
def test_h2_server_threaded_stress_under_tsan(event_front, san):
    if os.environ.get("GUBER_NATIVE_SAN", "") in ("", "0"):
        pytest.skip("set GUBER_NATIVE_SAN=1 to run the sanitizer stress")
    preload = sanitizer_preload(san)
    if preload is None:
        pytest.skip(f"the {san} sanitizer runtime is not available from this toolchain")
    # Build the instrumented .so in-process (compilation needs no
    # preload); the subprocess then dlopens the cached artifact.
    orig_san = os.environ.get("GUBER_NATIVE_SAN")
    env = dict(os.environ, GUBER_NATIVE_SAN=san)
    os.environ["GUBER_NATIVE_SAN"] = san
    try:
        so = ensure_built("h2_server")
    finally:
        if orig_san is None:
            os.environ.pop("GUBER_NATIVE_SAN", None)
        else:
            os.environ["GUBER_NATIVE_SAN"] = orig_san
    if so is None:
        pytest.skip("sanitized h2_server build failed (no g++?)")

    supp = REPO / "tests" / "tsan_suppressions.txt"
    server_env = dict(
        env,
        SAN_EVENT_FRONT=str(event_front),
        LD_PRELOAD=preload,
        TSAN_OPTIONS=(
            # Mutex-misuse reports are off: gcc-10's libtsan
            # false-positives "double lock" on pthread_cond_wait
            # re-acquisition (and on uninstrumented Eigen pools in
            # jaxlib).  Data-race detection — what this stress is
            # for — stays fully on.
            "halt_on_error=1 exitcode=66 report_thread_leaks=0 "
            f"report_mutex_bugs=0 detect_deadlocks=0 suppressions={supp}"
        ),
        # Leaks are python's own at exit; what ASan is here for is a
        # bad access: a posted conn or a kicked reactor used after free.
        ASAN_OPTIONS="halt_on_error=1 exitcode=66 detect_leaks=0",
        # Import gubernator_tpu without jax: TSan instruments every
        # malloc; the XLA runtime under TSan is noise we don't want.
        GUBERNATOR_TPU_X64="0",
        GUBERNATOR_TPU_COMPILE_CACHE="0",
    )
    plain_env = dict(env, GUBERNATOR_TPU_X64="0", GUBERNATOR_TPU_COMPILE_CACHE="0")
    server = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SRC],
        cwd=REPO,
        env=server_env,
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    churn = None
    try:
        port_line = server.stdout.readline()
        assert port_line.startswith("PORT "), (
            f"server failed to start: {port_line!r}\n"
            + server.stderr.read()[-4000:]
        )
        port = int(port_line.split()[1])
        if event_front:  # the hand-off exists on the reactor plane only
            churn = subprocess.Popen(
                [sys.executable, "-c", _CHURN_SRC, str(port)], cwd=REPO,
                env=plain_env, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
        client = subprocess.run(
            [sys.executable, "-c", _CLIENT_SRC, str(port)],
            cwd=REPO,
            env=plain_env,
            capture_output=True,
            text=True,
            timeout=240,
        )
        assert client.returncode == 0, (
            f"client hammer failed rc={client.returncode}\n"
            f"{client.stdout[-1000:]}\n{client.stderr[-2000:]}"
        )
        # The teardown runs while the churn client (reactor plane)
        # still connects, sends and resets.
        out, err = server.communicate(input="", timeout=120)
    except Exception:
        server.kill()
        raise
    finally:
        if churn is not None:
            churn.kill()
            churn.wait()
    assert "ThreadSanitizer" not in err and "AddressSanitizer" not in err, (
        "sanitizer report from h2_server:\n" + err[-4000:]
    )
    assert server.returncode == 0, (
        f"san server failed rc={server.returncode}\n"
        f"stdout: {out[-2000:]}\nstderr: {err[-4000:]}"
    )
    assert "san stress ok" in out


# Decision-plane stress, PRELOADED: concurrent dp_try_serve lanes race
# install/pull/probe churn on a shared hot key — the coherence
# protocol's exact concurrency shape (conn threads drain while the
# Python tier pulls/re-delegates).  Admissions are conserved: every
# pulled `consumed` count plus the post-pull admissions must equal the
# lanes' observed total.
_PLANE_SRC = r"""
import ctypes, sys, threading
import numpy as np

from gubernator_tpu.core import native_plane

plane = native_plane.NativeDecisionPlane(disqualify_mask=0)
key = b"san_hot"
NOW = 1_000_000
N_LANES = 6
ITERS = 2000

# A tiny hand-rolled GetRateLimitsReq: name="san", unique_key="hot",
# hits=1, limit=1<<40, duration=60000 (avoids importing protobuf into
# the TSan'd process).
def enc_field(tag, wt, payload):
    return bytes([(tag << 3) | wt]) + payload
def varint(v):
    out = b""
    while v >= 0x80:
        out += bytes([(v & 0x7F) | 0x80]); v >>= 7
    return out + bytes([v])
item = (enc_field(1, 2, varint(3) + b"san") + enc_field(2, 2, varint(3) + b"hot")
        + enc_field(3, 0, varint(1)) + enc_field(4, 0, varint(1 << 40))
        + enc_field(5, 0, varint(60000)))
body = enc_field(1, 2, varint(len(item)) + item)

admitted = [0] * N_LANES
def lane(t):
    for _ in range(ITERS):
        if plane.try_serve(body, max_items=1, now_ms=NOW) is not None:
            admitted[t] += 1

def churn():
    # The Python tier's pull/re-install cycle racing the lanes.
    consumed_total = 0
    for i in range(400):
        res = plane.pull(key)
        if res is not None:
            consumed_total += res[1]
        plane.install_lease(key, 1 << 40, 60000, NOW + 60000,
                            1 << 40, 1 << 30, 0, NOW + 60000)
    return consumed_total

plane.install_lease(key, 1 << 40, 60000, NOW + 60000, 1 << 40, 1 << 30, 0, NOW + 60000)
threads = [threading.Thread(target=lane, args=(t,)) for t in range(N_LANES)]
for t in threads: t.start()
pulled = churn()
for t in threads: t.join()
res = plane.pull(key)
final = res[1] if res is not None else 0
total = sum(admitted)
assert total == pulled + final, (total, pulled, final)
plane.close()
print("plane san stress ok admitted=%d" % total, flush=True)
"""


# Columnar feeder stress, PRELOADED: C bench threads (true
# multi-producer claim/commit against the lock-free window cursor)
# race the serve thread's seal/rotate/recycle AND a Python window
# callback pair — submit leaves every window in flight, complete
# writes the verdict lanes, so the serve thread keeps one window in
# flight while it submits the next — then mid-traffic flushes and a
# stop that lands on a window in flight.  Row conservation is
# asserted: every packed row is either served or drained, never lost
# or duplicated.
_FEEDER_SRC = r"""
import threading
import numpy as np

from gubernator_tpu.core import native_plane

def enc_field(tag, wt, payload):
    return bytes([(tag << 3) | wt]) + payload
def varint(v):
    out = b""
    while v >= 0x80:
        out += bytes([(v & 0x7F) | 0x80]); v >>= 7
    return out + bytes([v])
items = b""
for i in range(4):
    k = ("hot%dxyz" % i).encode()
    item = (enc_field(1, 2, varint(3) + b"san") + enc_field(2, 2, varint(len(k)) + k)
            + enc_field(3, 0, varint(1)) + enc_field(4, 0, varint(100))
            + enc_field(5, 0, varint(60000)))
    items += enc_field(1, 2, varint(len(item)) + item)
body = items

served = [0]
in_flight = [0]
hold = threading.Event()
hold.set()
def submit(slot, n_rows, n_rpcs, key_bytes):
    hold.wait(timeout=0.3)
    in_flight[0] += 1
    assert in_flight[0] <= 2, in_flight
    slot.pending = n_rows
    return native_plane.IN_FLIGHT

def complete(slot, n_rows, n_rpcs, key_bytes):
    assert slot.pending == n_rows
    slot.pending = None
    in_flight[0] -= 1
    served[0] += n_rows
    slot.out_status[:n_rows] = 0
    slot.out_limit[:n_rows] = 100
    slot.out_remaining[:n_rows] = 99
    slot.out_reset[:n_rows] = 0
    slot.rpc_status[:n_rpcs] = 0
    return 0

feeder = native_plane.NativeColumnarFeeder(
    n_slots=3, max_rows=256, max_rpcs=64, flush_rows=64,
    window_s=0.0005, window_handler=submit, window_complete=complete,
)
# Phase 1: C-threaded multi-producer hammer (true parallel claims).
packed = feeder.bench_pack(body, 4, 1500, 4)
feeder.flush()
# Phase 2: Python threads interleave packs with flushes.
py_packed = [0] * 4
def pylane(t):
    for i in range(300):
        rc = feeder.pack(body)
        if rc > 0:
            py_packed[t] += rc
        if i % 50 == 0:
            feeder.flush()
threads = [threading.Thread(target=pylane, args=(t,)) for t in range(4)]
for t in threads: t.start()
for t in threads: t.join()
feeder.flush()
st = feeder.stats()
total = packed + sum(py_packed)
assert st["feeder_rows"] == total, (st, total)
assert served[0] == st["feeder_served_rows"]
# served_rows excludes sink-mode/drain windows; everything packed must
# be accounted as served once callbacks were attached the whole run.
assert st["feeder_served_rows"] == total, (st, total)
assert st["feeder_windows_overlapped"] > 0, st
# Phase 3: stop lands on a window in flight.  The first submit is held
# inside Python (0.3 s) while a second window fills and this thread's
# cf_stop sets `closing`; it then leaves its window in flight — the
# drain completes that one (its rows count as served) and answers the
# other without entering Python.  (No helper thread: one created after
# the lanes exited frees their cached stacks' TLS inside uninstrumented
# glibc, which TSan reports as a race with their destructors.)
hold.clear()
for _ in range(16):
    assert feeder.pack(body) == 4   # 64 rows: window A seals, is submitted
deadline = 200
while feeder.stats()["feeder_open_rows"] and deadline:
    deadline -= 1; threading.Event().wait(0.005)   # the loop has rotated past A
assert feeder.pack(body) == 4       # window B holds rows
feeder.stop()
st = feeder.stats()
assert st["feeder_rows"] == total + 68, (st, total)
assert st["feeder_served_rows"] == total + 64 == served[0], (st, total, served)
assert in_flight[0] == 0 and st["feeder_open_rows"] == 0
feeder.close()
print("feeder san stress ok rows=%d" % total, flush=True)
"""


@pytest.mark.slow
def test_columnar_feeder_threaded_stress_under_tsan():
    """TSan over the feeder's lock-free claim/commit/seal/recycle
    protocol — C producer threads, the serve thread, and the Python
    callback racing on one ring."""
    if os.environ.get("GUBER_NATIVE_SAN", "") in ("", "0"):
        pytest.skip("set GUBER_NATIVE_SAN=1 to run the TSan stress")
    preload = sanitizer_preload("thread")
    if preload is None:
        pytest.skip("libtsan not available from this toolchain")
    orig_san = os.environ.get("GUBER_NATIVE_SAN")
    os.environ["GUBER_NATIVE_SAN"] = "thread"
    try:
        so = ensure_built("h2_server")
    finally:
        if orig_san is None:
            os.environ.pop("GUBER_NATIVE_SAN", None)
        else:
            os.environ["GUBER_NATIVE_SAN"] = orig_san
    if so is None:
        pytest.skip("sanitized h2_server build failed (no g++?)")
    supp = REPO / "tests" / "tsan_suppressions.txt"
    proc = subprocess.run(
        [sys.executable, "-c", _FEEDER_SRC],
        cwd=REPO,
        env=dict(
            os.environ,
            GUBER_NATIVE_SAN="thread",
            LD_PRELOAD=preload,
            TSAN_OPTIONS=(
                "halt_on_error=1 exitcode=66 report_thread_leaks=0 "
                f"report_mutex_bugs=0 detect_deadlocks=0 suppressions={supp}"
            ),
            PYTHONMALLOC="malloc",
            GUBERNATOR_TPU_X64="0",
            GUBERNATOR_TPU_COMPILE_CACHE="0",
        ),
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert "ThreadSanitizer" not in proc.stderr, (
        "TSan report from columnar feeder:\n" + proc.stderr[-4000:]
    )
    assert proc.returncode == 0, (
        f"feeder san stress failed rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-1000:]}\nstderr: {proc.stderr[-3000:]}"
    )
    assert "feeder san stress ok" in proc.stdout


@pytest.mark.slow
def test_decision_plane_threaded_stress_under_tsan():
    """TSan over the decision plane's install/probe/pull protocol —
    the exact lock shape the h2 connection threads and the ledger
    bridge exercise concurrently (round-8 harness, extended per the
    native-plane PR)."""
    if os.environ.get("GUBER_NATIVE_SAN", "") in ("", "0"):
        pytest.skip("set GUBER_NATIVE_SAN=1 to run the TSan stress")
    preload = sanitizer_preload("thread")
    if preload is None:
        pytest.skip("libtsan not available from this toolchain")
    orig_san = os.environ.get("GUBER_NATIVE_SAN")
    os.environ["GUBER_NATIVE_SAN"] = "thread"
    try:
        so = ensure_built("h2_server")
    finally:
        if orig_san is None:
            os.environ.pop("GUBER_NATIVE_SAN", None)
        else:
            os.environ["GUBER_NATIVE_SAN"] = orig_san
    if so is None:
        pytest.skip("sanitized h2_server build failed (no g++?)")
    supp = REPO / "tests" / "tsan_suppressions.txt"
    proc = subprocess.run(
        [sys.executable, "-c", _PLANE_SRC],
        cwd=REPO,
        env=dict(
            os.environ,
            GUBER_NATIVE_SAN="thread",
            LD_PRELOAD=preload,
            TSAN_OPTIONS=(
                "halt_on_error=1 exitcode=66 report_thread_leaks=0 "
                f"report_mutex_bugs=0 detect_deadlocks=0 suppressions={supp}"
            ),
            # pymalloc recycles the ctypes output buffers through its
            # own pools, invisible to TSan — a stale encode write then
            # pairs with a fresh buffer's memset in another thread as
            # a phantom race.  Raw malloc keeps the free/malloc
            # happens-before visible.
            PYTHONMALLOC="malloc",
            GUBERNATOR_TPU_X64="0",
            GUBERNATOR_TPU_COMPILE_CACHE="0",
        ),
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert "ThreadSanitizer" not in proc.stderr, (
        "TSan report from decision plane:\n" + proc.stderr[-4000:]
    )
    assert proc.returncode == 0, (
        f"plane san stress failed rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-1000:]}\nstderr: {proc.stderr[-3000:]}"
    )
    assert "plane san stress ok" in proc.stdout
