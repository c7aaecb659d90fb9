#!/usr/bin/env bash
# ci_fast.sh — the fast correctness gate for one host.
#
# Runs exactly ten things:
#   1. guberlint (tools/guberlint): fails on static-analysis findings
#      not in the committed guberlint_baseline.json — lock discipline,
#      JAX trace hygiene, thread lifecycle, peer-network discipline,
#      the NATIVE tier (C guard/GIL/blocking/atomics over
#      core/native/*.cpp), the Python<->C CONTRACT (wire layout,
#      decision-plane constants, GUBER_* knobs), knob/metric/doc
#      DRIFT, and PROTO invariant drift (annotations vs the gubercheck
#      property registry vs RESILIENCE.md, STATIC_ANALYSIS.md);
#      findings also land in guberlint.sarif so CI surfaces them as
#      annotations, and the stage is held to a 10 s wall budget so it
#      stays cheap enough to run first; the passes' seeded bad
#      fixtures run inside the tier-1 pytest below
#      (tests/test_guberlint.py);
#   2. the gubercheck smoke (tools/gubercheck --smoke): CHESS-bounded
#      (dpor + preemption_bound=2) interleaving exploration of every
#      protocol scenario over the REAL lease/handoff/replication code,
#      plus both resurrected-bug mutation fixtures (which must be
#      CAUGHT) — jax-free, 30 s wall budget (measured: ~1 s; the
#      exhaustive full-budget explorations are @slow in
#      tests/test_gubercheck.py, STATIC_ANALYSIS.md);
#   3. the trace smoke (scripts/trace_smoke.py): one in-memory-traced
#      decision end-to-end through the real router, asserting a
#      non-empty stitched span tree (root + engine child sharing one
#      trace id) — jax-free, same 10 s wall budget as guberlint;
#   4. the feeder smoke (scripts/feeder_smoke.py): the native
#      columnar feeder's C-packed columns bit-equal to the Python
#      columnar decode for a multi-RPC window, plus the ring window
#      lifecycle and drain-then-close teardown — jax-free, 30 s wall
#      budget (cold .so rebuild included);
#   5. the event-front smoke (scripts/event_front_smoke.py): a few
#      hundred concurrent connections through the epoll reactor plane
#      from the connscale client — zero errors, reactor stages in the
#      event ring, and a non-starved feeder ring wait — jax-free, 30 s
#      wall budget (PERF.md section 26);
#   6. the paged smoke (scripts/paged_smoke.py): the GUBER_PAGED
#      plane's fault-then-hit roundtrip — cold keys past the resident
#      frames fault (counted), spill a victim, and answer from the
#      refilled page with the spilled bucket's exact remaining —
#      jax CPU, 30 s wall budget (PERF.md section 30);
#   7. the replication smoke (tests/test_replication.py promote/demote
#      round trip on a live 3-node cluster): a measured-hot key
#      promotes to replica credit leases, answers go local, cooldown
#      demotes and the credit reconciles — the hot-key adaptive
#      ownership gate (RESILIENCE.md section 11), 120 s wall budget;
#   8. the crossregion smoke (scripts/crossregion_smoke.py): a
#      jax-free 2×2 region×peer loopback harness driven through a
#      full partition-heal-converge arc — failed cross-region deltas
#      re-queue (counted, zero dropped), the region aggregate circuit
#      reads `open`, and the healed region converges — the
#      multi-region federation gate (RESILIENCE.md section 12), 30 s
#      wall budget;
#   9. the obs smoke (scripts/obs_smoke.py): a jax-free 2×2 loopback
#      harness through the fleet rollup merge (all four nodes, real
#      histogram-merged quantiles), a partition that burns the
#      degraded-fraction SLI past its fast-pair factor, and the
#      admission-bound headroom recovering after the heal — the fleet
#      observability gate (OBSERVABILITY.md sections 9-10), 30 s wall
#      budget;
#  10. the tier-1 tests on six xdist workers, a file to a worker
#      (fuzz soaks marked `slow` are excluded so the suite stays
#      inside its 1,470 s timeout) — includes the served step programs
#      bit-equal to models/spec.py (tests/test_fused_parity.py) and
#      the chaos fast cases (tests/test_chaos.py:
#      kill/partition/heal invariants; tests/test_membership.py:
#      join/drain/kill-during-handoff reshard invariants;
#      tests/test_multiregion.py: the full-stack 2×2 federation
#      invariants; the multi-cycle soaks are @slow).
#
# Speed is not measured here: that is `python3 benchmarks/run.py` on a
# TPU (BENCHMARK.json, PERF.md).
#
# Usage: scripts/ci_fast.sh
# Exit code: an earlier stage's failure, else the pytest result.

set -o pipefail
cd "$(dirname "$0")/.."

echo "=== guberlint (static analysis vs baseline) ===" >&2
LINT_T0=$(date +%s%N)
if ! python -m tools.guberlint --sarif guberlint.sarif; then
  echo "guberlint: NEW findings vs guberlint_baseline.json — fix or" >&2
  echo "suppress with '# guberlint: ok <pass> — <why>' (STATIC_ANALYSIS.md;" >&2
  echo "machine-readable findings in guberlint.sarif)" >&2
  exit 1
fi
LINT_MS=$(( ($(date +%s%N) - LINT_T0) / 1000000 ))
echo "guberlint: ${LINT_MS} ms (budget 10000 ms)" >&2
if [ "${LINT_MS}" -gt 10000 ]; then
  echo "guberlint: blew its 10 s budget — it must stay cheap enough" >&2
  echo "to run as ci_fast stage one; profile the new pass" >&2
  exit 1
fi

echo "=== gubercheck smoke (protocol interleaving exploration) ===" >&2
GCK_T0=$(date +%s%N)
if ! timeout -k 10 60 python -m tools.gubercheck --smoke; then
  echo "gubercheck: a protocol scenario hit an invariant violation /" >&2
  echo "deadlock, or a resurrected-bug mutation went UNCAUGHT — run" >&2
  echo "'python -m tools.gubercheck --scenario <name>' for the repro" >&2
  echo "schedule (tools/gubercheck; STATIC_ANALYSIS.md)" >&2
  exit 1
fi
GCK_MS=$(( ($(date +%s%N) - GCK_T0) / 1000000 ))
echo "gubercheck smoke: ${GCK_MS} ms (budget 30000 ms)" >&2
if [ "${GCK_MS}" -gt 30000 ]; then
  echo "gubercheck smoke blew its 30 s budget — trim the smoke budgets" >&2
  echo "in scenarios.py (CHESS preemption_bound / max_runs), never the" >&2
  echo "scenario itself; the full budgets live in the @slow suite" >&2
  exit 1
fi

echo "=== trace smoke (in-memory stitched tree) ===" >&2
SMOKE_T0=$(date +%s%N)
if ! python scripts/trace_smoke.py; then
  echo "trace smoke: a traced decision no longer yields a stitched" >&2
  echo "span tree (scripts/trace_smoke.py; OBSERVABILITY.md)" >&2
  exit 1
fi
SMOKE_MS=$(( ($(date +%s%N) - SMOKE_T0) / 1000000 ))
echo "trace smoke: ${SMOKE_MS} ms (budget 10000 ms)" >&2
if [ "${SMOKE_MS}" -gt 10000 ]; then
  echo "trace smoke blew its 10 s budget — it must stay jax-free and" >&2
  echo "cheap enough to run before the tier-1 suite" >&2
  exit 1
fi

echo "=== feeder smoke (columnar pack parity + window lifecycle) ===" >&2
FEED_T0=$(date +%s%N)
if ! timeout -k 10 60 python scripts/feeder_smoke.py; then
  echo "feeder smoke: the native columnar feeder's packed columns no" >&2
  echo "longer match the Python columnar decode, or the ring window" >&2
  echo "lifecycle broke (scripts/feeder_smoke.py; PERF.md section 25)" >&2
  exit 1
fi
FEED_MS=$(( ($(date +%s%N) - FEED_T0) / 1000000 ))
echo "feeder smoke: ${FEED_MS} ms (budget 30000 ms)" >&2
if [ "${FEED_MS}" -gt 30000 ]; then
  echo "feeder smoke blew its 30 s budget — it must stay jax-free and" >&2
  echo "cheap enough to gate every native edit (a cold .so rebuild is" >&2
  echo "the only legitimate slow path)" >&2
  exit 1
fi

echo "=== event-front smoke (epoll reactor plane, C10K canary) ===" >&2
EVF_T0=$(date +%s%N)
if ! timeout -k 10 60 python scripts/event_front_smoke.py; then
  echo "event-front smoke: the reactor plane dropped RPCs, starved the" >&2
  echo "serve thread (feeder ring wait p99 over the bar), or broke its" >&2
  echo "teardown contract (scripts/event_front_smoke.py; PERF.md section 26)" >&2
  exit 1
fi
EVF_MS=$(( ($(date +%s%N) - EVF_T0) / 1000000 ))
echo "event-front smoke: ${EVF_MS} ms (budget 30000 ms)" >&2
if [ "${EVF_MS}" -gt 30000 ]; then
  echo "event-front smoke blew its 30 s budget — it must stay jax-free" >&2
  echo "and cheap enough to gate every native edit (a cold .so rebuild" >&2
  echo "is the only legitimate slow path)" >&2
  exit 1
fi

echo "=== paged smoke (page-table fault-then-hit roundtrip) ===" >&2
PGD_T0=$(date +%s%N)
if ! timeout -k 10 60 env JAX_PLATFORMS=cpu python scripts/paged_smoke.py; then
  echo "paged smoke: the paged state plane stopped translating, lost a" >&2
  echo "spilled bucket across the refill roundtrip, or faulted silently" >&2
  echo "(scripts/paged_smoke.py; PERF.md section 30)" >&2
  exit 1
fi
PGD_MS=$(( ($(date +%s%N) - PGD_T0) / 1000000 ))
echo "paged smoke: ${PGD_MS} ms (budget 30000 ms)" >&2
if [ "${PGD_MS}" -gt 30000 ]; then
  echo "paged smoke blew its 30 s budget — the fault path must stay" >&2
  echo "cheap enough to gate every engine edit on CPU" >&2
  exit 1
fi

echo "=== replication smoke (promote/demote round trip) ===" >&2
REPL_T0=$(date +%s%N)
if ! timeout -k 10 150 env JAX_PLATFORMS=cpu \
  python -m pytest tests/test_replication.py::test_promote_demote_smoke \
  -q -p no:cacheprovider -p no:xdist -p no:randomly; then
  echo "replication smoke: the hot-key promote/demote round trip broke" >&2
  echo "(tests/test_replication.py; RESILIENCE.md section 11)" >&2
  exit 1
fi
REPL_MS=$(( ($(date +%s%N) - REPL_T0) / 1000000 ))
echo "replication smoke: ${REPL_MS} ms (budget 120000 ms)" >&2
if [ "${REPL_MS}" -gt 120000 ]; then
  echo "replication smoke blew its 120 s budget — promotion must engage" >&2
  echo "within seconds on a test-timescale cluster or the plane is" >&2
  echo "too slow to matter in a real flash crowd" >&2
  exit 1
fi

echo "=== crossregion smoke (2x2 partition-heal-converge) ===" >&2
XR_T0=$(date +%s%N)
if ! timeout -k 10 60 python scripts/crossregion_smoke.py; then
  echo "crossregion smoke: the multi-region federation plane dropped" >&2
  echo "deltas, failed to re-queue across a partition, or did not" >&2
  echo "converge after the heal (scripts/crossregion_smoke.py;" >&2
  echo "RESILIENCE.md section 12)" >&2
  exit 1
fi
XR_MS=$(( ($(date +%s%N) - XR_T0) / 1000000 ))
echo "crossregion smoke: ${XR_MS} ms (budget 30000 ms)" >&2
if [ "${XR_MS}" -gt 30000 ]; then
  echo "crossregion smoke blew its 30 s budget — it must stay jax-free" >&2
  echo "and cheap enough to gate every federation-plane edit" >&2
  exit 1
fi

echo "=== obs smoke (fleet rollup + SLO burn + headroom) ===" >&2
OBS_T0=$(date +%s%N)
if ! timeout -k 10 60 python scripts/obs_smoke.py; then
  echo "obs smoke: the fleet rollup stopped merging all nodes, the" >&2
  echo "degraded-fraction SLI no longer burns under a partition, or" >&2
  echo "the admission-bound headroom failed to recover after heal" >&2
  echo "(scripts/obs_smoke.py; OBSERVABILITY.md sections 9-10)" >&2
  exit 1
fi
OBS_MS=$(( ($(date +%s%N) - OBS_T0) / 1000000 ))
echo "obs smoke: ${OBS_MS} ms (budget 30000 ms)" >&2
if [ "${OBS_MS}" -gt 30000 ]; then
  echo "obs smoke blew its 30 s budget — it must stay jax-free and" >&2
  echo "cheap enough to gate every observability-plane edit" >&2
  exit 1
fi

echo "=== tier-1 tests ===" >&2
T1="${TMPDIR:-/tmp}/_t1"
rm -f "$T1.log" "$T1.xml"
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile --junitxml="$T1.xml" -p no:randomly 2>&1 | tee "$T1.log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$T1.log" | tr -cd . | wc -c)" >&2

exit "$rc"
