"""Repo-specific guberlint configuration.

Everything here is DATA the passes consult; the pass logic itself is
repo-agnostic.  Documented in STATIC_ANALYSIS.md.
"""

from __future__ import annotations

# Files/dirs (repo-relative prefixes) scanned by the trace pass: the
# jit-reachable kernel surface.  The lock and thread passes scan the
# whole package.
TRACE_SCOPES = (
    "gubernator_tpu/ops/",
    "gubernator_tpu/core/engine.py",
    "gubernator_tpu/core/pump.py",
    "gubernator_tpu/core/readback.py",
    "gubernator_tpu/parallel/",
)

# Lint roots (repo-relative).
LINT_ROOTS = ("gubernator_tpu",)

# Prefixes excluded from all passes (generated code).
EXCLUDE = ("gubernator_tpu/net/pb/",)

# Attribute-name -> class hints for qualifying dotted lock paths in
# the acquisition-order graph: `with self.engine._lock` inside
# StepPump orders against DecisionEngine's own `with self._lock`.
ATTR_CLASS_HINTS = {
    "engine": "DecisionEngine",
    "_engine": "DecisionEngine",
    "ledger": "DecisionLedger",
    "led": "DecisionLedger",
    "pump": "StepPump",
    "_hits": "IntervalBatcher",
    "_updates": "IntervalBatcher",
    "combiner": "ReadbackCombiner",
    # Elastic-membership plane (post-PR-3 audit): the membership
    # manager's epoch state machine and the handoff sender/receiver
    # state it drives (cluster/membership.py, cluster/handoff.py).
    "membership": "MembershipManager",
    "mem": "MembershipManager",
    "_membership": "MembershipManager",
    "sender": "HandoffSender",
    "_sender": "HandoffSender",
}

# ---------------------------------------------------------------------
# Native tier (tools/guberlint/csource.py + nativecheck.py): the C
# decision plane under gubernator_tpu/core/native/.

# C/C++ sources scanned by the native + contract passes.
NATIVE_ROOTS = ("gubernator_tpu/core/native",)

# Calls that can block the calling thread for an unbounded/system-
# scheduler amount of time: making one while a mutex is held convoys
# every thread contending that mutex behind the kernel (the h2 front's
# per-connection threads share per-conn and per-server mutexes).  The
# designed exceptions (the write path serializes on write_mu) carry
# reasoned suppressions.
NATIVE_BLOCKING_CALLS = (
    "send", "recv", "sendmsg", "recvmsg", "sendto", "recvfrom",
    "accept", "connect", "poll", "select", "epoll_wait",
    "sleep", "usleep", "nanosleep", "sleep_for", "sleep_until",
    "getaddrinfo",
)

# Call names that re-enter Python (acquire the GIL) even though they
# are not Py* API: the h2 server's window callback is a ctypes-built
# CFUNCTYPE trampoline, so any call through it blocks on the GIL.
NATIVE_GIL_CALLS = ("callback",)

# Reactor discipline (nativecheck blocking-in-reactor): inside code
# reachable from an `// guberlint: epoll-root` function, these socket
# calls must carry the named nonblocking token in their argument list
# — a reactor thread parked in a blocking syscall stalls EVERY
# connection on its lane (h2_server.cpp reactor_loop owns thousands).
# Plain accept() can never carry SOCK_NONBLOCK (it is accept4's
# flag), so bare accept in a reactor always flags: use accept4.
REACTOR_NONBLOCK_TOKENS = {
    "send": "MSG_DONTWAIT",
    "recv": "MSG_DONTWAIT",
    "sendto": "MSG_DONTWAIT",
    "recvfrom": "MSG_DONTWAIT",
    "sendmsg": "MSG_DONTWAIT",
    "recvmsg": "MSG_DONTWAIT",
    "accept": "SOCK_NONBLOCK",
    "accept4": "SOCK_NONBLOCK",
}

# ---------------------------------------------------------------------
# Contract pass (tools/guberlint/contractcheck.py): the Python<->C
# boundary, pinned bit-equal.

# Proto files — the wire-layout source of truth for BOTH tiers (the
# Python codec is generated from these; the C codec declares its
# layout via `// guberlint: wire` annotations checked against them).
PROTO_FILES = (
    "gubernator_tpu/net/proto/gubernator.proto",
    "gubernator_tpu/net/proto/peers.proto",
)

# Cross-tier constants that must be numerically identical: (file_a,
# symbol_a, file_b, symbol_b).  .cpp symbols parse from constexpr/const
# declarations; .py symbols evaluate module-level int expressions
# (types.py enum members resolve).
CONTRACT_CONSTANTS = (
    # Decision-plane record kinds: the C table's kOver/kLease are the
    # ledger's _K_OVER/_K_LEASE (dp_pull returns them; core/ledger.py
    # branches on the value).
    ("gubernator_tpu/core/native/decision_plane.cpp", "kOver",
     "gubernator_tpu/core/ledger.py", "_K_OVER"),
    ("gubernator_tpu/core/native/decision_plane.cpp", "kLease",
     "gubernator_tpu/core/ledger.py", "_K_LEASE"),
    # Lease-eligibility breaker mask: duplicated on the bridge side so
    # the plane declines exactly what the ledger would revoke on.
    ("gubernator_tpu/core/ledger.py", "_BREAKERS",
     "gubernator_tpu/core/native_plane.py", "_BREAKERS"),
)

# Proto enums pinned against the Python IntEnum twins: every proto
# member must exist with the same value (Python may EXTEND the enum —
# Behavior.SKETCH is a repo extension with no wire presence).
ENUM_CONTRACTS = (
    ("Algorithm", "gubernator_tpu/types.py"),
    ("Behavior", "gubernator_tpu/types.py"),
    ("Status", "gubernator_tpu/types.py"),
)

# Every getenv("GUBER_*") in C must have its home in this file (the
# canonical env-surface index).
KNOB_HOME = "gubernator_tpu/config.py"

# ---------------------------------------------------------------------
# Drift pass (tools/guberlint/driftcheck.py): knob/metric/doc surface.

# Where GUBER_* knob reads are collected from (the package + native
# sources; scripts and tests consume knobs, they don't define them).
KNOB_SCAN_ROOTS = ("gubernator_tpu",)

# Every knob read anywhere must have a row in the README table.
KNOB_DOC_FILE = "README.md"

# Metric registry + the doc surface every registered metric must
# appear in (at least one of these files).
METRIC_REGISTRY = "gubernator_tpu/utils/metrics.py"
METRIC_DOC_FILES = (
    "README.md", "PERF.md", "RESILIENCE.md", "STATIC_ANALYSIS.md",
    "OBSERVABILITY.md",
)

# The SLI declaration file (obs/slo.py): the drift `slo` sub-rule
# checks every SLI(...) declaration there names a metric the registry
# actually exports — an SLI over a dropped series would silently
# evaluate nothing.
SLO_REGISTRY = "gubernator_tpu/obs/slo.py"

# Methods known to acquire a lock at their top level: a call to one of
# these while holding other locks creates an acquisition-order edge
# (one level of indirection across the ledger/batch_loop/
# global_manager/pump trio).
KNOWN_LOCKING_CALLS = {
    # DecisionEngine serializes on its RLock.
    "apply_columnar": "DecisionEngine._lock",
    "get_rate_limits": "DecisionEngine._lock",
    "sweep": "DecisionEngine._lock",
    # DecisionLedger entry points.
    "plan": "DecisionLedger._lock",
    "flush_settles": "DecisionLedger._lock",
    "invalidate_keys": "DecisionLedger._lock",
    "readonly_overlay": "DecisionLedger._lock",
    # IntervalBatcher producers/drains.
    "add_chunk": "IntervalBatcher._lock",
    "add_many": "IntervalBatcher._lock",
    "flush_now": "IntervalBatcher._lock",
    # StepPump flush path runs under the engine lock.
    "flush_for": "DecisionEngine._lock",
}
