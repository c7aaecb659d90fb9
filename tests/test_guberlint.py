"""guberlint proves each pass catches its seeded bad fixture.

Each case writes a known-bad snippet, runs the pass directly, and
asserts the finding (and that the suppression escape hatch silences
it).  STATIC_ANALYSIS.md documents the grammar these fixtures pin.
"""

import json
import textwrap
from pathlib import Path

import pytest

from tools.guberlint import baseline as baseline_mod
from tools.guberlint import lockcheck, threadcheck, tracecheck
from tools.guberlint.common import Finding, SourceFile


def _src(tmp_path: Path, code: str, name: str = "fix.py") -> SourceFile:
    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    return SourceFile(p, name)


def _lock_findings(src):
    edges = set()
    out = lockcheck.check_file(src, edges)
    return out, edges


# ---------------------------------------------------------------- lock


LOCK_BAD = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0  # guberlint: guarded-by _lock

        def good(self):
            with self._lock:
                self._n += 1

        def bad(self):
            return self._n
"""


def test_lock_pass_catches_unguarded_access(tmp_path):
    findings, _ = _lock_findings(_src(tmp_path, LOCK_BAD))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "unguarded-access"
    assert f.scope == "Counter.bad"
    assert "self._n" in f.message


def test_lock_pass_suppression_escape_hatch(tmp_path):
    code = LOCK_BAD.replace(
        "return self._n",
        "return self._n  # guberlint: ok lock — racy read tolerated, metrics only",
    )
    findings, _ = _lock_findings(_src(tmp_path, code))
    assert findings == []


def test_lock_pass_suppression_requires_reason(tmp_path):
    code = LOCK_BAD.replace(
        "return self._n", "return self._n  # guberlint: ok lock"
    )
    src = _src(tmp_path, code)
    assert any(
        f.rule == "bad-suppression" for f in src.bad_suppressions
    ), "reasonless suppression must itself be a finding"


def test_lock_pass_holds_annotation_and_locked_convention(tmp_path):
    code = """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guberlint: guarded-by _lock

            def _bump_locked(self):
                self._n += 1

            def bump_held(self):  # guberlint: holds _lock
                self._n += 1
    """
    findings, _ = _lock_findings(_src(tmp_path, code))
    assert findings == []


def test_lock_pass_condition_alias(tmp_path):
    code = """
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []  # guberlint: guarded-by _lock

            def put(self, x):
                with self._cv:
                    self._items.append(x)
    """
    findings, _ = _lock_findings(_src(tmp_path, code))
    assert findings == [], "acquiring the condition acquires the wrapped lock"


def test_lock_pass_nested_def_resets_held_context(tmp_path):
    code = """
        import threading

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []  # guberlint: guarded-by _lock

            def kick(self, pool):
                with self._lock:
                    def later():
                        return self._items.pop()
                    pool.submit(later)
    """
    findings, _ = _lock_findings(_src(tmp_path, code))
    assert len(findings) == 1, "closure may run after the with exits"


def test_lock_order_inversion_detected(tmp_path):
    code = """
        import threading

        class AB:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self.x = 0  # guberlint: guarded-by _a_lock

            def fwd(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def rev(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """
    _, edges = _lock_findings(_src(tmp_path, code))
    cyc = lockcheck.order_findings(edges)
    assert len(cyc) == 1
    assert cyc[0].rule == "lock-order-inversion"
    assert "AB._a_lock" in cyc[0].message and "AB._b_lock" in cyc[0].message


def test_lock_order_consistent_nesting_is_clean(tmp_path):
    code = """
        import threading

        class AB:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()
                self.x = 0  # guberlint: guarded-by _a_lock

            def one(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def two(self):
                with self._a_lock:
                    with self._b_lock:
                        pass
    """
    _, edges = _lock_findings(_src(tmp_path, code))
    assert lockcheck.order_findings(edges) == []


# --------------------------------------------------------------- trace


def test_trace_pass_catches_tracer_branch(tmp_path):
    code = """
        import jax
        import jax.numpy as jnp

        # guberlint: shapes x [n] on the pad ladder
        @jax.jit
        def f(x):
            if x.sum() > 0:
                return x
            return -x
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["trace-branch"]


def test_trace_pass_static_shape_branch_ok(tmp_path):
    code = """
        import jax

        # guberlint: shapes x [n] on the pad ladder
        @jax.jit
        def f(x):
            if x.shape[0] > 4:
                return x
            return x + 1
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert findings == [], "shape tests are static under trace"


def test_trace_pass_static_argnames_not_tainted(tmp_path):
    code = """
        import jax
        from functools import partial

        # guberlint: shapes x [n]; window static
        @partial(jax.jit, static_argnames=("window",))
        def f(x, window):
            if window > 4:
                return x
            return x + 1
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_trace_pass_catches_host_transfer(tmp_path):
    code = """
        import jax
        import numpy as np

        # guberlint: shapes x [n]
        @jax.jit
        def f(x):
            y = x + 1
            return np.asarray(y)
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["trace-transfer"]


def test_trace_pass_transfer_reaches_helpers(tmp_path):
    code = """
        import jax

        def helper(v):
            return float(v)

        # guberlint: shapes x [n]
        @jax.jit
        def f(x):
            return helper(x * 2)
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert any(
        f.rule == "trace-transfer" and f.scope == "helper" for f in findings
    ), "helpers called from jit roots execute traced"


def test_trace_pass_requires_shapes_annotation(tmp_path):
    code = """
        import jax

        @jax.jit
        def f(x):
            return x + 1
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["trace-shapes"]
    # ... and the annotation satisfies it (any of the eligible lines).
    ok = code.replace(
        "@jax.jit", "# guberlint: shapes x [n] padded pow2\n@jax.jit"
    )
    assert tracecheck.check_file(_src(tmp_path, ok, "ok.py")) == []


def test_trace_pass_suppression(tmp_path):
    code = """
        import jax

        # guberlint: ok trace — host callback by design (io_callback wrapper)
        @jax.jit
        def f(x):
            return x + 1
    """
    findings = tracecheck.check_file(_src(tmp_path, code))
    assert findings == []


# -------------------------------------------------------------- thread


def test_thread_pass_catches_orphan_daemon(tmp_path):
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                pass
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["thread-orphan"]


def test_thread_pass_join_via_local_alias_ok(tmp_path):
    """`shipper = self._shipper` under the lock, then
    `shipper.join()` — the snapshot-under-lock shape the lock pass
    encourages for guarded thread handles — must count as a join path
    (membership.close regression, post-PR-3 audit)."""
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._t = threading.Thread(target=print, daemon=True)
                self._t.start()

            def close(self):
                with self._lock:
                    t = self._t
                t.join(timeout=5.0)
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_thread_pass_start_before_publish_ok(tmp_path):
    """`t = Thread(...); t.start(); self._t = t` — start-before-publish
    (so close() can never join an unstarted thread) still counts as a
    self-owned thread with a class join path."""
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                t = threading.Thread(target=print, daemon=True)
                t.start()
                self._t = t

            def close(self):
                with self._lock:
                    t = self._t
                t.join(timeout=5.0)
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_thread_pass_joined_daemon_ok(tmp_path):
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._stop = threading.Event()
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                while not self._stop.wait(1.0):
                    pass

            def close(self):
                self._stop.set()
                self._t.join(timeout=2.0)
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_thread_pass_local_threads_joined_via_loop(tmp_path):
    code = """
        import threading

        def run(n):
            threads = [
                threading.Thread(target=print, daemon=True) for _ in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_thread_pass_fire_and_forget_needs_suppression(tmp_path):
    code = """
        import threading

        def kick(fn):
            threading.Thread(target=fn, daemon=True).start()
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["thread-orphan"]
    ok = code.replace(
        "    threading.Thread",
        "    # guberlint: ok thread — bounded one-shot drain\n"
        "    threading.Thread",
    )
    assert threadcheck.check_file(_src(tmp_path, ok, "ok.py")) == []


def test_thread_pass_catches_silent_swallow(tmp_path):
    code = """
        import threading

        def loop():
            while True:
                try:
                    work()
                except Exception:
                    pass
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert [f.rule for f in findings] == ["thread-swallow"]


def test_thread_pass_logged_swallow_ok(tmp_path):
    code = """
        import logging
        import threading

        def loop():
            while True:
                try:
                    work()
                except Exception:
                    logging.getLogger("x").exception("work failed")
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


def test_thread_pass_non_threaded_module_exempt_from_swallow(tmp_path):
    code = """
        def f():
            try:
                work()
            except Exception:
                pass
    """
    findings = threadcheck.check_file(_src(tmp_path, code))
    assert findings == []


# ------------------------------------------------------------ baseline


def test_baseline_round_trip_and_staleness(tmp_path):
    f1 = Finding("lock", "unguarded-access", "a.py", 3, "C.m", "self.x", "x")
    f2 = Finding("trace", "trace-branch", "b.py", 9, "f", "if@f", "y")
    path = tmp_path / "base.json"
    baseline_mod.save(path, [f1, f2])
    base = baseline_mod.load(path)
    assert len(base) == 2
    # f2 fixed; f3 new.
    f3 = Finding("thread", "thread-orphan", "c.py", 1, "S", "thread@S._t", "z")
    new, accepted, stale = baseline_mod.partition([f1, f3], base)
    assert [f.rule for f in new] == ["thread-orphan"]
    assert [f.rule for f in accepted] == ["unguarded-access"]
    assert len(stale) == 1 and stale[0][1] == "trace-branch"


def test_baseline_save_preserves_audit_record(tmp_path):
    path = tmp_path / "base.json"
    path.write_text(json.dumps({"findings": [], "audited_clean": {"lock": {}}}))
    baseline_mod.save(path, [])
    assert "audited_clean" in json.loads(path.read_text())


def test_repo_is_clean_against_committed_baseline():
    """The acceptance gate: `python -m tools.guberlint` exits 0."""
    from tools.guberlint.__main__ import main

    assert main([]) == 0


# ----------------------------------------------------- fix-annotations


def test_fix_annotations_inserts_stub(tmp_path, monkeypatch):
    import tools.guberlint.__main__ as main_mod

    p = tmp_path / "mod.py"
    p.write_text(
        textwrap.dedent(
            """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    with self._lock:
                        self._n += 1
            """
        )
    )
    monkeypatch.setattr(main_mod, "REPO_ROOT", tmp_path)
    inserted = main_mod.fix_annotations([p])
    assert inserted == 1
    assert "self._n = 0  # guberlint: guarded-by _lock" in p.read_text()
    # The annotated file now verifies clean.
    src = SourceFile(p, "mod.py")
    findings, _ = _lock_findings(src)
    assert findings == []


def test_fix_annotations_skips_mixed_lock_attrs(tmp_path, monkeypatch):
    import tools.guberlint.__main__ as main_mod

    p = tmp_path / "mod.py"
    p.write_text(
        textwrap.dedent(
            """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0

                def bump(self):
                    with self._lock:
                        self._n += 1

                def read(self):
                    return self._n
            """
        )
    )
    monkeypatch.setattr(main_mod, "REPO_ROOT", tmp_path)
    assert main_mod.fix_annotations([p]) == 0, (
        "an attr with unlocked accesses must not get a stub"
    )


# ----------------------------------------------------------------- net


NET_RETRY_BAD = """
    from gubernator_tpu.cluster.peer_client import PeerError

    def forward(groups, pick):
        while groups:
            retry = []
            for p, ids in groups:
                try:
                    p.rpc(ids)
                except PeerError as e:
                    if e.not_ready:
                        retry.extend(ids)
                        continue
            groups = pick(retry)
"""


def test_net_pass_catches_retry_without_backoff(tmp_path):
    from tools.guberlint import netcheck

    findings = netcheck.check_file(_src(tmp_path, NET_RETRY_BAD))
    assert any(f.rule == "net-retry-no-backoff" for f in findings)


def test_net_pass_backoff_in_enclosing_loop_ok(tmp_path):
    from tools.guberlint import netcheck

    code = NET_RETRY_BAD.replace(
        "            groups = pick(retry)",
        "            time.sleep(backoff_delay(1, 0.01, 0.25))\n"
        "            groups = pick(retry)",
    )
    findings = netcheck.check_file(_src(tmp_path, code))
    assert not [f for f in findings if f.rule == "net-retry-no-backoff"]


def test_net_pass_log_and_continue_is_not_a_retry_loop(tmp_path):
    """multiregion-style per-peer iteration: catching PeerError to
    skip a peer (no not_ready decision, no retry collection) is not a
    retry loop and must not demand backoff."""
    from tools.guberlint import netcheck

    code = """
        from gubernator_tpu.cluster.peer_client import PeerError

        def send_all(by_peer, log):
            for addr, reqs in by_peer.items():
                try:
                    addr.rpc(reqs)
                except PeerError as e:
                    log.error("send to %s failed: %s", addr, e)
                    continue
    """
    findings = netcheck.check_file(_src(tmp_path, code))
    assert not [f for f in findings if f.rule == "net-retry-no-backoff"]


def test_net_pass_flags_backoffless_crossregion_retry(tmp_path):
    """ISSUE 14: the multiregion log-and-continue exemption is gone —
    a cross-region push loop that RE-QUEUES failed deltas (a requeue
    IS a retry decision, one window removed) without any backoff must
    flag.  The live multiregion send path passes because its handler
    computes a backoff_delay for the deferred requeue."""
    from tools.guberlint import netcheck

    code = """
        from gubernator_tpu.cluster.peer_client import PeerError

        def push_regions(self, by_region, conf):
            for region, (peer, reqs) in by_region.items():
                try:
                    peer.send_peer_hits(
                        reqs, timeout=conf.multi_region_timeout
                    )
                except PeerError as e:
                    self._requeue_region(region, reqs)
                    continue
    """
    findings = netcheck.check_file(_src(tmp_path, code))
    assert any(f.rule == "net-retry-no-backoff" for f in findings), (
        findings
    )


def test_net_pass_crossregion_retry_with_backoff_ok(tmp_path):
    """The §12 multiregion shape: the handler computes a capped
    full-jitter backoff_delay for the deferred requeue — clean."""
    from tools.guberlint import netcheck

    code = """
        from gubernator_tpu.cluster.peer_client import PeerError
        from gubernator_tpu.cluster.health import backoff_delay

        def push_regions(self, by_region, conf):
            for region, (peer, reqs) in by_region.items():
                try:
                    peer.send_peer_hits(
                        reqs, timeout=conf.multi_region_timeout
                    )
                except PeerError as e:
                    delay = backoff_delay(
                        self.attempts.get(region, 0), 0.05, 2.0
                    )
                    self._requeue_region(region, reqs, delay)
                    continue
    """
    findings = netcheck.check_file(_src(tmp_path, code))
    assert not [
        f for f in findings if f.rule == "net-retry-no-backoff"
    ], findings


def test_net_pass_catches_rpc_without_timeout(tmp_path):
    from tools.guberlint import netcheck

    code = """
        def flush(peer, reqs):
            peer.send_peer_hits(reqs)
    """
    findings = netcheck.check_file(_src(tmp_path, code))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "net-rpc-no-timeout"
    assert "send_peer_hits" in f.message


def test_net_pass_rpc_with_timeout_ok(tmp_path):
    from tools.guberlint import netcheck

    code = """
        def flush(peer, reqs, conf):
            peer.send_peer_hits(reqs, timeout=conf.global_timeout)
    """
    assert netcheck.check_file(_src(tmp_path, code)) == []


def test_net_pass_server_side_receivers_exempt(tmp_path):
    from tools.guberlint import netcheck

    code = """
        class Adapter:
            def handle(self, reqs):
                return self.instance.get_peer_rate_limits(reqs)

        class Client:
            def one(self, req):
                return self.get_peer_rate_limits([req], timeout=1.0)
    """
    assert netcheck.check_file(_src(tmp_path, code)) == []


def test_net_pass_suppression_escape_hatch(tmp_path):
    from tools.guberlint import netcheck

    code = """
        def flush(peer, reqs):
            peer.send_peer_hits(reqs)  # guberlint: ok net — probe uses channel default
    """
    assert netcheck.check_file(_src(tmp_path, code)) == []


# Handoff RPC discipline (ISSUE 7): TransferBuckets call sites are held
# to the same rules as every peer RPC — an epoch commit waits on the
# sender, so an unbudgeted send or a backoff-free retry loop stalls a
# membership transition, not just one request.

HANDOFF_BAD = """
    from gubernator_tpu.cluster.peer_client import PeerError

    def ship(pending, window):
        while pending:
            for addr, (peer, rows) in list(pending.items()):
                try:
                    peer.transfer_buckets_raw(rows[:window])
                except PeerError as e:
                    if e.not_ready:
                        continue
                pending.pop(addr)
"""


def test_net_pass_catches_handoff_rpc_without_timeout(tmp_path):
    from tools.guberlint import netcheck

    findings = netcheck.check_file(_src(tmp_path, HANDOFF_BAD))
    assert any(
        f.rule == "net-rpc-no-timeout"
        and "transfer_buckets_raw" in f.message
        for f in findings
    )


def test_net_pass_catches_handoff_retry_without_backoff(tmp_path):
    from tools.guberlint import netcheck

    findings = netcheck.check_file(_src(tmp_path, HANDOFF_BAD))
    assert any(f.rule == "net-retry-no-backoff" for f in findings)


def test_net_pass_handoff_with_timeout_and_backoff_ok(tmp_path):
    from tools.guberlint import netcheck

    code = """
        import time
        from gubernator_tpu.cluster.health import backoff_delay
        from gubernator_tpu.cluster.peer_client import PeerError

        def ship(pending, window, deadline):
            attempt = 0
            while pending:
                for addr, (peer, rows) in list(pending.items()):
                    try:
                        peer.transfer_buckets_raw(rows[:window], timeout=1.0)
                    except PeerError as e:
                        if e.not_ready:
                            continue
                    pending.pop(addr)
                time.sleep(backoff_delay(attempt, 0.01, 0.25))
                attempt += 1
    """
    assert netcheck.check_file(_src(tmp_path, code)) == []


# Replication RPC discipline (the hot-key promotion plane): the
# ReplicateKeys call sites are held to the same rules — an unbudgeted
# grant stalls the owner's whole promotion tick, and a backoff-free
# grant-retry loop would hammer a broken replica the health plane
# already refused.

REPLICATION_BAD = """
    from gubernator_tpu.cluster.peer_client import PeerError

    def grant_all(peers, payload):
        retry = list(peers)
        while retry:
            for peer in list(retry):
                try:
                    peer.replicate_keys_raw(payload)
                except PeerError as e:
                    if e.not_ready:
                        retry.append(peer)
                        continue
                retry.remove(peer)
"""


def test_net_pass_catches_replication_rpc_without_timeout(tmp_path):
    from tools.guberlint import netcheck

    findings = netcheck.check_file(_src(tmp_path, REPLICATION_BAD))
    assert any(
        f.rule == "net-rpc-no-timeout"
        and "replicate_keys_raw" in f.message
        for f in findings
    )


def test_net_pass_catches_replication_retry_without_backoff(tmp_path):
    from tools.guberlint import netcheck

    findings = netcheck.check_file(_src(tmp_path, REPLICATION_BAD))
    assert any(f.rule == "net-retry-no-backoff" for f in findings)


def test_net_pass_replication_with_timeout_and_backoff_ok(tmp_path):
    from tools.guberlint import netcheck

    code = """
        import time
        from gubernator_tpu.cluster.health import backoff_delay
        from gubernator_tpu.cluster.peer_client import PeerError

        def grant_all(peers, payload, conf):
            retry = list(peers)
            attempt = 0
            while retry:
                for peer in list(retry):
                    try:
                        peer.replicate_keys_raw(
                            payload, timeout=conf.global_timeout
                        )
                    except PeerError as e:
                        if e.not_ready:
                            retry.append(peer)
                            continue
                    retry.remove(peer)
                time.sleep(backoff_delay(attempt, 0.01, 0.25))
                attempt += 1
    """
    assert netcheck.check_file(_src(tmp_path, code)) == []


# -------------------------------------------------------------- native
# The C tier (tools/guberlint/csource.py + nativecheck.py): each rule
# proves it fires on a seeded bad fixture and that the escape hatches
# (suppression, *_locked, holds) work — mirroring the Python passes.


def _csrc(tmp_path: Path, code: str, name: str = "fix.cpp"):
    from tools.guberlint.csource import CSourceFile

    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    return CSourceFile(p, name)


C_GUARD_BAD = """
    #include <mutex>

    struct Plane {
      std::mutex mu;
      long count = 0;  // guberlint: guarded-by mu
    };

    void good(Plane* p) {
      std::lock_guard<std::mutex> lock(p->mu);
      ++p->count;
    }

    long bad(Plane* p) {
      return p->count;
    }
"""


def test_native_pass_catches_unguarded_c_field(tmp_path):
    from tools.guberlint import nativecheck

    findings = nativecheck.check_files([_csrc(tmp_path, C_GUARD_BAD)])
    assert [f.rule for f in findings] == ["unguarded-access"]
    f = findings[0]
    assert f.scope == "bad" and f.detail == "Plane.count"


def test_native_pass_suppression_and_locked_convention(tmp_path):
    from tools.guberlint import nativecheck

    ok = C_GUARD_BAD.replace(
        "    long bad(Plane* p) {\n      return p->count;\n    }",
        "    long read_locked(Plane* p) {\n      return p->count;\n    }\n"
        "\n"
        "    // guberlint: holds mu\n"
        "    long read_held(Plane* p) {\n      return p->count;\n    }\n"
        "\n"
        "    long scrape(Plane* p) {\n"
        "      return p->count;  // guberlint: ok native — racy stats read tolerated\n"
        "    }",
    )
    assert nativecheck.check_files([_csrc(tmp_path, ok)]) == []


def test_native_pass_struct_registry_form(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        #include <mutex>

        struct S {
          // guberlint: guard a, b by mu
          std::mutex mu;
          long a = 0;
          long b = 0;
        };

        long bad(S* s) { return s->a + s->b; }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert sorted(f.detail for f in findings) == ["S.a", "S.b"]


def test_native_pass_member_function_bare_access(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        #include <mutex>

        struct Conn {
          // guberlint: guard window by write_mu
          std::mutex write_mu;
          long window = 0;

          void good() {
            std::lock_guard<std::mutex> lock(write_mu);
            ++window;
          }

          long bad() { return window; }
        };
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert [(f.scope, f.detail) for f in findings] == [("bad", "Conn.window")]


def test_native_pass_gil_violation_direct_and_transitive(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        long helper(long x) {
          PyGILState_Ensure();
          return x;
        }

        // guberlint: gil-free
        long serve(long x) {
          return helper(x);
        }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert [f.rule for f in findings] == ["gil-call"]
    assert findings[0].scope == "serve"
    assert "PyGILState_Ensure" in findings[0].message


def test_native_pass_gil_callback_trampoline(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        struct Srv { long (*callback)(long); };

        // guberlint: gil-free
        long serve(Srv* s) {
          return s->callback(1);
        }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert [f.rule for f in findings] == ["gil-call"]
    assert "callback" in findings[0].detail


def test_native_pass_gil_free_clean_path_ok(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        long helper(long x) { return x * 2; }

        // guberlint: gil-free
        long serve(long x) { return helper(x); }
    """
    assert nativecheck.check_files([_csrc(tmp_path, code)]) == []


def test_native_pass_blocking_call_under_mutex(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        #include <mutex>

        struct C { std::mutex mu; int fd; };

        void bad(C* c, const char* buf, long n) {
          std::lock_guard<std::mutex> lock(c->mu);
          send(c->fd, buf, n, 0);
        }

        void fine(C* c, const char* buf, long n) {
          send(c->fd, buf, n, 0);
        }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert [f.rule for f in findings] == ["blocking-under-lock"]
    assert findings[0].scope == "bad"
    ok = code.replace(
        "          send(c->fd, buf, n, 0);\n        }\n\n        void fine",
        "          // guberlint: ok native — bounded by the socket buffer\n"
        "          send(c->fd, buf, n, 0);\n        }\n\n        void fine",
    )
    assert nativecheck.check_files([_csrc(tmp_path, ok, "ok.cpp")]) == []


def test_native_pass_atomic_order_needs_reason(tmp_path):
    from tools.guberlint import nativecheck

    code = """
        #include <atomic>

        void f(std::atomic<long>* a) {
          a->fetch_add(1, std::memory_order_relaxed);
        }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert [f.rule for f in findings] == ["atomic-order"]
    ok = code.replace(
        "std::memory_order_relaxed);",
        "std::memory_order_relaxed);  // guberlint: ok native — join publishes",
    )
    assert nativecheck.check_files([_csrc(tmp_path, ok, "ok.cpp")]) == []


def test_native_pass_blocking_in_reactor(tmp_path):
    """The epoll-root reachability rule: send/recv without
    MSG_DONTWAIT and accept without SOCK_NONBLOCK flag anywhere in
    the call graph under an epoll loop root — directly or
    transitively."""
    from tools.guberlint import nativecheck

    code = """
        #include <sys/socket.h>

        void drain(int fd) {
          char buf[64];
          recv(fd, buf, sizeof(buf), 0);
        }

        // guberlint: epoll-root
        void loop(int epfd, int lfd) {
          int c = accept(lfd, nullptr, nullptr);
          (void)c;
          drain(lfd);
        }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert sorted(f.rule for f in findings) == [
        "blocking-in-reactor", "blocking-in-reactor",
    ]
    details = sorted(f.detail for f in findings)
    assert details == ["loop->accept", "loop->recv"]
    assert all(f.scope == "loop" for f in findings)
    # The transitive finding names the path and the real call site.
    recv_f = [f for f in findings if f.detail == "loop->recv"][0]
    assert "loop->drain" in recv_f.message


def test_native_pass_reactor_nonblocking_and_suppression_ok(tmp_path):
    """Nonblocking variants (MSG_DONTWAIT, accept4+SOCK_NONBLOCK) and
    reasoned call-site suppressions pass; functions NOT under an
    epoll root may block freely."""
    from tools.guberlint import nativecheck

    code = """
        #include <sys/socket.h>

        void drain(int fd) {
          char buf[64];
          recv(fd, buf, sizeof(buf), MSG_DONTWAIT);
        }

        void legacy_branch(int fd) {
          char buf[64];
          // guberlint: ok native — threaded-plane branch, runtime-gated off the reactor
          send(fd, buf, sizeof(buf), 0);
        }

        // guberlint: epoll-root
        void loop(int epfd, int lfd) {
          int c = accept4(lfd, nullptr, nullptr, SOCK_NONBLOCK);
          (void)c;
          drain(lfd);
          legacy_branch(lfd);
        }

        void not_a_reactor(int fd) {
          char buf[64];
          recv(fd, buf, sizeof(buf), 0);  // blocking is fine here
        }
    """
    assert nativecheck.check_files([_csrc(tmp_path, code)]) == []


def test_native_pass_reasonless_c_suppression_is_a_finding(tmp_path):
    from tools.guberlint import nativecheck

    code = C_GUARD_BAD.replace(
        "      return p->count;",
        "      return p->count;  // guberlint: ok native",
    )
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    assert any(f.rule == "bad-suppression" for f in findings)


# ------------------------------------------------------------ contract
# The Python<->C boundary pins: each fixture mutates ONE side and the
# pass must trip (the acceptance criterion).


def _contract_repo(tmp_path: Path, proto: str) -> Path:
    root = tmp_path / "repo"
    pdir = root / "gubernator_tpu" / "net" / "proto"
    pdir.mkdir(parents=True)
    (pdir / "contract.proto").write_text(textwrap.dedent(proto))
    return root


CONTRACT_PROTO = """
    syntax = "proto3";
    message Ping {
      string name = 1;
      int64 hits = 2;
    }
    enum Verdict {
      UNDER = 0;
      OVER = 1;
    }
"""

CONTRACT_CPP_OK = """
    // guberlint: wire Ping name=1:len hits=2:varint
    long encode(long* out) {
      out[0] = (1 << 3) | 2;
      out[1] = (2 << 3) | 0;
      return 2;
    }
"""


def _contract_check(root, csrc, **kw):
    from tools.guberlint import contractcheck

    kw.setdefault(
        "proto_files", ("gubernator_tpu/net/proto/contract.proto",)
    )
    kw.setdefault("constants", ())
    kw.setdefault("enum_contracts", ())
    return contractcheck.check([csrc], root, **kw)


def test_contract_pass_wire_clean_when_aligned(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    assert _contract_check(root, _csrc(tmp_path, CONTRACT_CPP_OK)) == []


def test_contract_pass_trips_on_proto_field_move(tmp_path):
    """Mutating the PYTHON-side contract (the proto the pb codec is
    generated from) trips the pin."""
    root = _contract_repo(
        tmp_path, CONTRACT_PROTO.replace("int64 hits = 2;", "int64 hits = 9;")
    )
    findings = _contract_check(root, _csrc(tmp_path, CONTRACT_CPP_OK))
    assert [f.rule for f in findings] == ["wire-mismatch"]
    assert findings[0].detail == "Ping.hits"


def test_contract_pass_trips_on_c_literal_move(tmp_path):
    """Mutating the C side (the tag literal) trips both directions of
    the code pin: the declared field is no longer built, and an
    undeclared number appears."""
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    bad = CONTRACT_CPP_OK.replace("(2 << 3) | 0", "(9 << 3) | 0")
    findings = _contract_check(root, _csrc(tmp_path, bad))
    assert sorted(f.rule for f in findings) == [
        "wire-undeclared-field", "wire-unimplemented-field",
    ]


def test_contract_pass_trips_on_annotation_drift(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    bad = CONTRACT_CPP_OK.replace("hits=2:varint", "hits=2:len")
    findings = _contract_check(root, _csrc(tmp_path, bad))
    assert [f.rule for f in findings] == ["wire-mismatch"]


def test_contract_pass_decode_idioms_recognized(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    code = """
        // guberlint: wire Ping name=1:len hits=2:varint
        long decode(const unsigned char* p, long tag) {
          if ((tag >> 3) != 1) return -1;
          long field = tag;
          if (field == 2) return 2;
          return 0;
        }
    """
    assert _contract_check(root, _csrc(tmp_path, code)) == []


def test_contract_pass_constant_mismatch(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    (root / "gubernator_tpu" / "core").mkdir(parents=True)
    (root / "gubernator_tpu" / "core" / "ledger.py").write_text(
        "_K_OVER = 1\n_K_LEASE = 2\n"
    )
    cpp = _csrc(
        tmp_path,
        "constexpr int kOver = 3, kLease = 2;\nlong f(long x) { return x; }\n",
        "plane.cpp",
    )
    cpp.rel = "plane.cpp"
    findings = _contract_check(
        root, cpp,
        constants=(
            ("plane.cpp", "kOver", "gubernator_tpu/core/ledger.py", "_K_OVER"),
            ("plane.cpp", "kLease", "gubernator_tpu/core/ledger.py", "_K_LEASE"),
        ),
    )
    assert [f.rule for f in findings] == ["constant-mismatch"]
    assert "kOver" in findings[0].detail


def test_contract_pass_enum_mismatch(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    (root / "gubernator_tpu").mkdir(exist_ok=True)
    (root / "gubernator_tpu" / "types.py").write_text(
        textwrap.dedent(
            """
            import enum

            class Verdict(enum.IntEnum):
                UNDER = 0
                OVER = 5
            """
        )
    )
    findings = _contract_check(
        root, _csrc(tmp_path, CONTRACT_CPP_OK),
        enum_contracts=(("Verdict", "gubernator_tpu/types.py"),),
    )
    assert [f.rule for f in findings] == ["enum-mismatch"]
    assert findings[0].detail == "Verdict.OVER"


def test_contract_pass_c_getenv_needs_config_home(tmp_path):
    root = _contract_repo(tmp_path, CONTRACT_PROTO)
    (root / "gubernator_tpu" / "config.py").write_text(
        '"""knobs"""\nKNOWN = ("GUBER_REAL_KNOB",)\n'
    )
    code = """
        #include <cstdlib>
        long f() {
          const char* a = getenv("GUBER_REAL_KNOB");
          const char* b = getenv("GUBER_PHANTOM_KNOB");
          return (a != 0) + (b != 0);
        }
    """
    findings = _contract_check(
        root, _csrc(tmp_path, code),
        knob_home="gubernator_tpu/config.py",
    )
    assert [f.rule for f in findings] == ["knob-homeless"]
    assert findings[0].detail == "GUBER_PHANTOM_KNOB"


def test_contract_repo_constants_actually_resolve():
    """The committed CONTRACT_CONSTANTS pairs must all resolve — an
    unresolved pin (rename without updating config) is itself caught,
    but a silently-empty table would check nothing."""
    from pathlib import Path as P

    from tools.guberlint import contractcheck
    from tools.guberlint.__main__ import REPO_ROOT
    from tools.guberlint.config import CONTRACT_CONSTANTS
    from tools.guberlint.csource import iter_c_files

    csrcs = iter_c_files(
        [REPO_ROOT / "gubernator_tpu" / "core" / "native"], REPO_ROOT
    )
    findings = contractcheck.check(csrcs, P(REPO_ROOT))
    assert not [f for f in findings if f.rule == "constant-unresolved"]
    assert len(CONTRACT_CONSTANTS) >= 3


# --------------------------------------------------------------- drift


def _drift_repo(tmp_path: Path) -> Path:
    root = tmp_path / "repo"
    (root / "gubernator_tpu" / "utils").mkdir(parents=True)
    (root / "scripts").mkdir()
    (root / "gubernator_tpu" / "config.py").write_text(
        'KNOWN = ("GUBER_DOCUMENTED",)\n'
    )
    (root / "gubernator_tpu" / "mod.py").write_text(
        'import os\n'
        'A = os.environ.get("GUBER_DOCUMENTED")\n'
        'B = os.environ.get("GUBER_ORPHAN")\n'
    )
    (root / "gubernator_tpu" / "utils" / "metrics.py").write_text(
        textwrap.dedent(
            """
            from prometheus_client.core import CounterMetricFamily

            def collect():
                yield CounterMetricFamily("gubernator_documented_total", "d")
                yield CounterMetricFamily("gubernator_secret_total", "s")
            """
        )
    )
    (root / "README.md").write_text(
        "| `GUBER_DOCUMENTED` | - | a knob |\n"
        "`gubernator_documented_total` counts things.\n"
    )
    (root / "PERF.md").write_text("perf notes\n")
    (root / "RESILIENCE.md").write_text("resilience notes\n")
    (root / "STATIC_ANALYSIS.md").write_text("lint notes\n")
    return root


def test_drift_pass_orphan_knob_and_undocumented_metric(tmp_path):
    from tools.guberlint import driftcheck

    findings = driftcheck.check(_drift_repo(tmp_path), [])
    rules = sorted((f.rule, f.detail) for f in findings)
    assert ("knob-no-config-home", "GUBER_ORPHAN") in rules
    assert ("knob-undocumented", "GUBER_ORPHAN") in rules
    assert ("metric-undocumented", "gubernator_secret_total") in rules
    assert not any(r == "knob-stale" for r, _ in rules)
    assert not any(
        d == "GUBER_DOCUMENTED" or d == "gubernator_documented_total"
        for _, d in rules
    )


def test_drift_pass_stale_doc_rows(tmp_path):
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "README.md").write_text(
        "| `GUBER_DOCUMENTED` | - | a knob |\n"
        "| `GUBER_GHOST` | - | removed years ago |\n"
        "`gubernator_documented_total` and `gubernator_ghost_total`.\n"
    )
    findings = driftcheck.check(root, [])
    details = {(f.rule, f.detail) for f in findings}
    assert ("knob-stale", "GUBER_GHOST") in details
    assert ("metric-stale", "gubernator_ghost_total") in details


def _slo_repo(tmp_path: Path, slo_body: str) -> Path:
    """A drift fixture repo with an SLI registry (obs/slo.py) — the
    slo sub-rule's seed bed."""
    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "obs").mkdir()
    (root / "gubernator_tpu" / "obs" / "slo.py").write_text(slo_body)
    return root


def test_drift_slo_unregistered_metric_is_a_finding(tmp_path):
    """An SLI naming a metric the registry never exports flags — the
    burn rate would watch a series that does not exist."""
    from tools.guberlint import driftcheck

    root = _slo_repo(
        tmp_path,
        textwrap.dedent(
            """
            from dataclasses import dataclass

            @dataclass(frozen=True)
            class SLI:
                name: str = ""
                metric: str = ""
                kind: str = ""

            GOOD = SLI(
                name="ok",
                metric="gubernator_documented_total",
                kind="ratio",
            )
            BAD = SLI(
                name="ghost",
                metric="gubernator_never_registered",
                kind="ratio",
            )
            """
        ),
    )
    findings = driftcheck.check(root, [])
    details = {(f.rule, f.detail) for f in findings}
    assert ("slo-metric-unregistered", "gubernator_never_registered") \
        in details
    assert not any(
        d == "gubernator_documented_total" for _, d in details
    )


def test_drift_slo_computed_metric_name_is_a_finding(tmp_path):
    """An SLI without a literal metric= is unverifiable — it must
    flag (or carry a reasoned suppression)."""
    from tools.guberlint import driftcheck

    root = _slo_repo(
        tmp_path,
        textwrap.dedent(
            """
            class SLI:
                def __init__(self, **kw):
                    pass

            NAME = "gubernator_documented_total"
            COMPUTED = SLI(name="dyn", metric=NAME, kind="ratio")
            SUPPRESSED = SLI(name="dyn2", metric=NAME, kind="ratio")  # guberlint: ok drift — resolved at import, pinned by tests
            """
        ),
    )
    findings = driftcheck.check(root, [])
    rules = [f.rule for f in findings if f.rule.startswith("slo")]
    assert rules == ["slo-no-metric"]


def test_drift_pass_prose_mention_is_not_a_read(tmp_path):
    """Docstrings and comments naming a knob must not count as reads
    (only call-argument string literals do)."""
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "mod.py").write_text(
        '"""GUBER_PROSE_ONLY is merely mentioned here."""\n'
        'import os\n'
        'A = os.environ.get("GUBER_DOCUMENTED")\n'
    )
    findings = driftcheck.check(root, [])
    assert not any("GUBER_PROSE_ONLY" in f.detail for f in findings)


# -------------------------------------------------- C fix-annotations


def test_fix_c_annotations_inserts_stub(tmp_path, monkeypatch):
    import tools.guberlint.__main__ as main_mod
    from tools.guberlint.csource import CSourceFile

    p = tmp_path / "mod.cpp"
    p.write_text(
        textwrap.dedent(
            """
            #include <mutex>

            struct Plane {
              std::mutex mu;
              long count = 0;
            };

            void bump(Plane* p) {
              std::lock_guard<std::mutex> lock(p->mu);
              ++p->count;
            }

            void bump2(Plane* p) {
              std::lock_guard<std::mutex> lock(p->mu);
              p->count += 2;
            }
            """
        )
    )
    monkeypatch.setattr(main_mod, "REPO_ROOT", tmp_path)
    inserted = main_mod.fix_c_annotations([p])
    assert inserted == 1
    assert "long count = 0;  // guberlint: guarded-by mu" in p.read_text()
    # The annotated file now verifies clean.
    from tools.guberlint import nativecheck

    assert nativecheck.check_files([CSourceFile(p, "mod.cpp")]) == []


def test_fix_c_annotations_skips_unlocked_access(tmp_path, monkeypatch):
    import tools.guberlint.__main__ as main_mod

    p = tmp_path / "mod.cpp"
    p.write_text(
        textwrap.dedent(
            """
            #include <mutex>

            struct Plane {
              std::mutex mu;
              long count = 0;
            };

            void bump(Plane* p) {
              std::lock_guard<std::mutex> lock(p->mu);
              ++p->count;
            }

            long read(Plane* p) { return p->count; }
            """
        )
    )
    monkeypatch.setattr(main_mod, "REPO_ROOT", tmp_path)
    assert main_mod.fix_c_annotations([p]) == 0


# ------------------------------------------------------- sarif / only


def test_sarif_output_structure(tmp_path):
    from tools.guberlint.__main__ import to_sarif

    f = Finding(
        "native", "unguarded-access", "a.cpp", 7, "bad", "Plane.count",
        "unguarded",
    )
    doc = to_sarif([f])
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "guberlint"
    assert run["tool"]["driver"]["rules"][0]["id"] == "native/unguarded-access"
    res = run["results"][0]
    assert res["ruleId"] == "native/unguarded-access"
    assert res["locations"][0]["physicalLocation"]["region"]["startLine"] == 7
    assert "guberlint/v1" in res["fingerprints"]


def test_sarif_file_mode_writes_and_keeps_exit_semantics(tmp_path):
    from tools.guberlint.__main__ import main

    out = tmp_path / "guberlint.sarif"
    rc = main(["--sarif", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["runs"][0]["results"] == []


def test_only_flag_restricts_passes(tmp_path, monkeypatch):
    """--only lock on a file full of thread findings reports none (and
    the thread pass on the same file does)."""
    import tools.guberlint.__main__ as main_mod
    from tools.guberlint.__main__ import run

    monkeypatch.setattr(main_mod, "REPO_ROOT", tmp_path)
    p = tmp_path / "mod.py"
    p.write_text(
        textwrap.dedent(
            """
            import threading

            def kick(fn):
                threading.Thread(target=fn, daemon=True).start()
            """
        )
    )
    assert run([p], only="lock") == []
    assert [f.rule for f in run([p], only="thread")] == ["thread-orphan"]


def test_suite_stays_inside_the_ci_budget():
    """ci_fast.sh keeps guberlint as stage one only while the whole
    suite (all seven passes over the repo) stays under 10 s."""
    import time as _time

    from tools.guberlint.__main__ import REPO_ROOT, run
    from tools.guberlint.config import LINT_ROOTS

    t0 = _time.monotonic()
    run([REPO_ROOT / r for r in LINT_ROOTS])
    assert _time.monotonic() - t0 < 10.0


# -------------------------------------------------- drift: trace sub-rule
# (ISSUE 9 satellite: span-name catalog discipline — every span(...)
# site unique + snake_case; deliberate twins need reasoned
# suppressions.)


def test_drift_span_name_style_and_duplicate(tmp_path):
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "spans.py").write_text(
        textwrap.dedent(
            """
            from gubernator_tpu.utils.tracing import span

            def a():
                with span("BadName.CamelCase"):
                    pass

            def b():
                with span("dup.site"):
                    pass

            def c():
                with span("dup.site"):
                    pass

            def ok():
                with span("fine.snake_case"):
                    pass
            """
        )
    )
    findings = driftcheck.check(root, [])
    rules = {(f.rule, f.detail) for f in findings}
    assert ("span-name-style", "BadName.CamelCase") in rules
    assert ("span-name-duplicate", "dup.site") in rules
    assert not any(
        d == "fine.snake_case" for _r, d in rules
    )
    # Exactly one duplicate finding (the twin, not the first site).
    assert (
        sum(1 for f in findings if f.rule == "span-name-duplicate") == 1
    )


def test_drift_span_twin_suppression_respected(tmp_path):
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "spans.py").write_text(
        textwrap.dedent(
            """
            from gubernator_tpu.utils.tracing import span

            def a():
                with span("twin.site"):
                    pass

            def b():
                # guberlint: ok drift — deliberate sharded twin
                with span("twin.site"):
                    pass
            """
        )
    )
    findings = driftcheck.check(root, [])
    assert not any(f.rule.startswith("span-name") for f in findings)


@pytest.mark.parametrize("suppressed", [False, True])
def test_drift_stage_sites_are_phases_with_one_home_module(tmp_path, suppressed):
    """stage("name") / self._stage("name") sites: snake-dot style like
    spans; several sites in one module are one phase; a second module
    timing the same stage is a twin and says so at its first site."""
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "a_engine.py").write_text(
        textwrap.dedent(
            """
            from gubernator_tpu.utils.metrics import stage

            def f(stat):
                with stage("engine.pack", stat):
                    pass
                with stage("engine.pack", stat):
                    pass
                with stage("Bad.Name", stat):
                    pass
            """
        )
    )
    mark = "# guberlint: ok drift — sharded twin of a_engine.py's engine.pack"
    (root / "gubernator_tpu" / "b_sharded.py").write_text(
        textwrap.dedent(
            f"""
            class E:
                def f(self):
                    {mark if suppressed else "pass"}
                    with self._stage("engine.pack"):
                        pass
                    with self._stage("engine.pack"):
                        pass
            """
        )
    )
    findings = driftcheck.check(root, [])
    rules = [(f.rule, f.detail, f.file) for f in findings
             if f.rule.startswith("span-name")]
    assert ("span-name-style", "Bad.Name", "gubernator_tpu/a_engine.py") in rules
    twins = [r for r in rules if r[0] == "span-name-duplicate"]
    if suppressed:
        assert twins == []
    else:
        assert twins == [
            ("span-name-duplicate", "engine.pack", "gubernator_tpu/b_sharded.py")
        ]


def test_drift_span_variable_name_not_scanned(tmp_path):
    """Helper-routed spans (variable name argument) are outside the
    literal catalog — no style/duplicate findings for them."""
    from tools.guberlint import driftcheck

    root = _drift_repo(tmp_path)
    (root / "gubernator_tpu" / "spans.py").write_text(
        textwrap.dedent(
            """
            from gubernator_tpu.utils.tracing import span

            def helper(name):
                with span(name):
                    pass
            """
        )
    )
    findings = driftcheck.check(root, [])
    assert not any(f.rule.startswith("span-name") for f in findings)


# -------------------------------------------------- native: event ring
# (ISSUE 9 satellite: an event-ring write that calls a Py* API must
# trip the gil-free check — the ring is reachable from conn_loop.)


def test_native_event_ring_write_calling_py_api_trips_gil_check(tmp_path):
    from tools.guberlint import nativecheck

    code = """
    // guberlint: gil-free
    long evr_record(void* ring, long kind, long dur) {
      PyGILState_Ensure();
      return 1;
    }

    // guberlint: gil-free
    void conn_loop(void* srv, void* ring) {
      evr_record(ring, 1, 42);
    }
    """
    findings = nativecheck.check_files([_csrc(tmp_path, code)])
    gil = [f for f in findings if f.rule == "gil-call"]
    # Both the write itself and the conn_loop root reach the Py* call.
    roots = {f.scope for f in gil}
    assert "evr_record" in roots and "conn_loop" in roots


def test_native_event_ring_clean_write_passes(tmp_path):
    from tools.guberlint import nativecheck

    code = """
    #include <atomic>

    // guberlint: gil-free
    long evr_record(void* ring, long kind, long dur) {
      return kind + dur;
    }

    // guberlint: gil-free
    void conn_loop(void* srv, void* ring) {
      evr_record(ring, 1, 42);
    }
    """
    assert nativecheck.check_files([_csrc(tmp_path, code)]) == []


# --------------------------------------------------------------- proto


def _proto_repo(tmp_path: Path) -> Path:
    """A fixture repo where every REAL registered property is both
    anchored (source annotation) and documented (RESILIENCE.md
    marker): clean by construction, so each test seeds exactly one
    drift."""
    from tools.gubercheck import properties as props

    root = tmp_path / "repo"
    (root / "gubernator_tpu").mkdir(parents=True)
    names = sorted(props.registry())
    (root / "gubernator_tpu" / "mod.py").write_text(
        "\n".join(f"# guberlint: invariant {n}" for n in names) + "\n"
    )
    (root / "RESILIENCE.md").write_text(
        "\n".join(f"- gubercheck: `{n}` — checked" for n in names)
        + "\n"
    )
    return root


def test_proto_pass_synced_fixture_is_clean(tmp_path):
    from tools.guberlint import protocheck

    assert protocheck.check(_proto_repo(tmp_path)) == []


def test_proto_pass_orphan_annotation(tmp_path):
    """A source annotation naming an unregistered property claims
    model-checked protection that does not exist."""
    from tools.guberlint import protocheck

    root = _proto_repo(tmp_path)
    with (root / "gubernator_tpu" / "mod.py").open("a") as f:
        f.write("# guberlint: invariant ghost-prop\n")
    findings = protocheck.check(root)
    assert [(f.rule, f.detail) for f in findings] == [
        ("proto-orphan-annotation", "ghost-prop")
    ]


def test_proto_pass_orphan_annotation_suppression(tmp_path):
    from tools.guberlint import protocheck

    root = _proto_repo(tmp_path)
    with (root / "gubernator_tpu" / "mod.py").open("a") as f:
        # Trailing annotation on a code line so the same-line
        # suppression targets it.
        f.write(
            "X = 1  # guberlint: invariant ghost-prop"
            "  # guberlint: ok proto — registry lands next PR\n"
        )
    assert protocheck.check(root) == []


def test_proto_pass_doc_marker_unregistered(tmp_path):
    """RESILIENCE.md promising a checked bound nothing checks."""
    from tools.guberlint import protocheck

    root = _proto_repo(tmp_path)
    with (root / "RESILIENCE.md").open("a") as f:
        f.write("- gubercheck: `ghost-bound` — totally checked\n")
    findings = protocheck.check(root)
    assert [(f.rule, f.detail, f.file) for f in findings] == [
        ("proto-doc-unregistered", "ghost-bound", "RESILIENCE.md")
    ]


def test_proto_pass_registered_but_undocumented(tmp_path):
    """Dropping one doc marker flags exactly that property."""
    from tools.gubercheck import properties as props
    from tools.guberlint import protocheck

    root = _proto_repo(tmp_path)
    victim = sorted(props.registry())[0]
    doc = root / "RESILIENCE.md"
    doc.write_text(
        "\n".join(
            ln for ln in doc.read_text().splitlines()
            if f"`{victim}`" not in ln
        ) + "\n"
    )
    findings = protocheck.check(root)
    assert [(f.rule, f.detail) for f in findings] == [
        ("proto-invariant-undocumented", victim)
    ]


def test_proto_pass_registered_but_unanchored(tmp_path):
    """Dropping one source annotation flags exactly that property —
    a registry row with no protected site is drift."""
    from tools.gubercheck import properties as props
    from tools.guberlint import protocheck

    root = _proto_repo(tmp_path)
    victim = sorted(props.registry())[-1]
    mod = root / "gubernator_tpu" / "mod.py"
    mod.write_text(
        "\n".join(
            ln for ln in mod.read_text().splitlines()
            if not ln.endswith(f" {victim}")
        ) + "\n"
    )
    findings = protocheck.check(root)
    assert [(f.rule, f.detail) for f in findings] == [
        ("proto-property-unanchored", victim)
    ]


def test_proto_registry_rows_match_scenario_claims():
    """Every property a scenario claims to check is registered, and
    every registered property is claimed by at least one scenario —
    the registry carries no dead rows the model checker never
    exercises."""
    from tools.gubercheck import properties as props
    from tools.gubercheck import scenarios as scn_mod

    registered = set(props.registry())
    claimed = set()
    for name in scn_mod.scenario_names():
        cls = scn_mod.get_scenario(name)
        for p in cls.properties:
            assert p in registered, f"{name} claims unregistered {p}"
            claimed.add(p)
    assert claimed == registered, (
        f"registered but never checked by any scenario: "
        f"{sorted(registered - claimed)}"
    )


# ---------------------------------------------- stale suppressions


def _tracker(declared, hits=()):
    from tools.guberlint.common import SuppressionTracker

    t = SuppressionTracker()
    for rel, line, pass_name in declared:
        t.declared.setdefault(rel, {}).setdefault(line, set()).add(
            pass_name
        )
    for rel, line, pass_name in hits:
        t.hits.setdefault(rel, set()).add((line, pass_name))
    return t


def test_stale_suppression_detected():
    t = _tracker([("gubernator_tpu/x.py", 10, "lock")])
    findings = baseline_mod.stale_suppressions(t, ())
    assert [(f.rule, f.file, f.line) for f in findings] == [
        ("stale-suppression", "gubernator_tpu/x.py", 10)
    ]


def test_hit_suppression_is_not_stale():
    t = _tracker(
        [("gubernator_tpu/x.py", 10, "lock")],
        hits=[("gubernator_tpu/x.py", 10, "lock")],
    )
    assert baseline_mod.stale_suppressions(t, ()) == []


def test_native_and_contract_suppressions_exempt():
    """The C-side passes don't consult SourceFile.suppressed(), so
    their suppressions never register hits — they must not be
    reported stale."""
    t = _tracker(
        [
            ("gubernator_tpu/x.py", 3, "native"),
            ("gubernator_tpu/x.py", 4, "contract"),
        ]
    )
    assert baseline_mod.stale_suppressions(t, ()) == []


def test_trace_suppression_outside_scope_exempt():
    """trace only runs on TRACE_SCOPES files; elsewhere an unhit
    trace suppression proves nothing."""
    t = _tracker([("gubernator_tpu/cluster/x.py", 7, "trace")])
    scopes = ("gubernator_tpu/models/",)
    assert baseline_mod.stale_suppressions(t, scopes) == []
    t2 = _tracker([("gubernator_tpu/models/x.py", 7, "trace")])
    findings = baseline_mod.stale_suppressions(t2, scopes)
    assert [f.rule for f in findings] == ["stale-suppression"]


def test_live_tracker_records_declarations_and_hits(tmp_path):
    """End-to-end through SourceFile: declaring a suppression under an
    active tracker records it; an imminent-finding consult records a
    hit; stale detection then distinguishes the two."""
    from tools.guberlint.common import SuppressionTracker

    code = textwrap.dedent(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0  # guberlint: guarded-by _lock

            def bump(self):
                with self._lock:
                    self._n += 1

            def peek(self):
                return self._n  # guberlint: ok lock — racy read is fine here
        """
    )
    with SuppressionTracker() as t:
        src = _src(tmp_path, code, "y.py")
        findings, _ = _lock_findings(src)
        assert findings == []
        stale = baseline_mod.stale_suppressions(t, ())
    assert src.rel in t.declared
    assert t.hits.get(src.rel), "the consulted suppression must hit"
    assert stale == [], "a hit suppression is not stale"


# ------------------------------------------------------- incremental


def test_changed_flag_rejects_explicit_paths():
    from tools.guberlint.__main__ import main

    assert main(["--changed", "gubernator_tpu/clock.py"]) == 2


def test_changed_lint_paths_filters_to_lint_roots():
    """Whatever git reports, the result only ever contains existing
    .py files under LINT_ROOTS minus EXCLUDE (or None when git can't
    answer — never a silently-empty list standing in for 'clean')."""
    from tools.guberlint.__main__ import changed_lint_paths
    from tools.guberlint.config import EXCLUDE, LINT_ROOTS

    paths = changed_lint_paths()
    if paths is None:
        pytest.skip("not a usable git checkout")
    for p in paths:
        rel = p.relative_to(
            Path(__file__).resolve().parents[1]
        ).as_posix()
        assert rel.endswith(".py")
        assert any(
            rel == r or rel.startswith(r.rstrip("/") + "/")
            for r in LINT_ROOTS
        )
        assert not any(rel.startswith(e) for e in EXCLUDE)
        assert p.exists()


def test_changed_mode_runs_clean_on_this_checkout():
    """`--changed` end-to-end: the current working tree's changed
    files (possibly none) lint clean — same acceptance bar as the
    full run, a fraction of the work."""
    from tools.guberlint.__main__ import main

    assert main(["--changed"]) == 0
