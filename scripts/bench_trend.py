"""Fold the loose BENCH_r*?_*.json artifacts into one committed trend.

Every perf round leaves a pile of per-config artifacts in the repo
root; reading the trajectory of, say, herd p50 across rounds means
opening a dozen files by hand.  This script normalizes them all into

  * BENCH_TREND.json — {config: {round: {value, p50_ms, p99_ms,
    dispatches_per_decision, native_answered, platform, file}}}
  * a config × round markdown table replaced in PERF_HISTORY.md between the
    `<!-- bench-trend:begin -->` / `<!-- bench-trend:end -->` markers
    (appended to the end when absent), so the trajectory is readable
    in one screen.

Naming convention handled: BENCH_r06_cpu_herd.json (round r06, config
herd), BENCH_r04_default.json (no platform tag), BENCH_r03.json (a
driver headline wrapper with n/cmd/rc/parsed — config "default").
A/B companions (*_ledger0, *_native0, *_seedbaseline) keep their
suffix as part of the config name so each pair shows as two columns.

Usage: python scripts/bench_trend.py [--check]
  --check: exit 1 if BENCH_TREND.json or the PERF_HISTORY.md table is stale
  (CI can keep the trend honest without rewriting files).
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREND_PATH = os.path.join(ROOT, "BENCH_TREND.json")
PERF_PATH = os.path.join(ROOT, "PERF_HISTORY.md")
BEGIN = "<!-- bench-trend:begin -->"
END = "<!-- bench-trend:end -->"

_NAME = re.compile(r"^BENCH_(r\d+)(?:_cpu)?(?:_(.+))?\.json$")


def _extract(data: dict) -> dict | None:
    """Normalize one artifact's interesting fields; None if it carries
    no measurement (errored runs keep their error string)."""
    if "parsed" in data and "value" not in data:
        # Round-1 wrapper: {n, cmd, rc, tail, parsed}.
        parsed = data.get("parsed")
        if not isinstance(parsed, dict):
            return {"error": f"rc={data.get('rc')}"}
        data = parsed
    if "configs" in data:  # fast_capture combined tier: skip (its
        return None  # sub-configs land as their own artifacts)
    out: dict = {}
    if "value" in data:
        out["value"] = data["value"]
    for k in ("p50_ms", "p99_ms", "platform", "error"):
        if data.get(k) is not None:
            out[k] = data[k]
    led = data.get("ledger")
    if isinstance(led, dict):
        if "dispatches_per_decision" in led:
            out["dispatches_per_decision"] = led["dispatches_per_decision"]
        if led.get("native_answered"):
            out["native_answered"] = led["native_answered"]
    # Dead-peer A/B artifacts (deadpeer mode): fold the health-plane
    # counters + the same-session healthy control so the trend shows
    # availability under failure alongside throughput.
    dead = data.get("dead")
    if isinstance(dead, dict):
        for k in ("errors", "degraded_answers", "backoff_retries"):
            if dead.get(k) is not None:
                out[k] = dead[k]
        if dead.get("requests"):
            out["error_rate"] = round(
                dead.get("errors", 0) / dead["requests"], 4
            )
    healthy = data.get("healthy")
    if isinstance(healthy, dict) and healthy.get("value") is not None:
        out["healthy_value"] = healthy["value"]
        if healthy.get("p99_ms") is not None:
            out["healthy_p99_ms"] = healthy["p99_ms"]
    # Reshard A/B artifacts (reshard mode): fold the membership-plane
    # counters so the trend shows live-resharding cost alongside
    # throughput (handoff rows shipped/forfeited/received, dual-ring
    # window time, and the end-to-end error rate under the reshard).
    mem = data.get("membership")
    if isinstance(mem, dict):
        hoff = mem.get("handoff") or {}
        for k in ("shipped", "forfeited", "received"):
            if hoff.get(k) is not None:
                out[f"handoff_{k}"] = hoff[k]
        if mem.get("dual_seconds") is not None:
            out["dual_seconds"] = mem["dual_seconds"]
        if data.get("errors") is not None and data.get("requests"):
            out["error_rate"] = round(
                data["errors"] / data["requests"], 4
            )
    # Device-plane fused A/B artifacts (devfused mode): fold the
    # unfused arm, the median pair delta, and each arm's device
    # dispatches/batch — the fused steady state must read 1.0.
    if data.get("fused_delta_pct") is not None:
        if data.get("unfused_value") is not None:
            out["unfused_value"] = data["unfused_value"]
        out["fused_delta_pct"] = data["fused_delta_pct"]
        if data.get("fused_mode") is not None:
            out["fused_mode"] = data["fused_mode"]
    if data.get("dispatches_per_batch") is not None:
        out["dispatches_per_batch"] = data["dispatches_per_batch"]
    if data.get("dispatches_per_batch_unfused") is not None:
        out["dispatches_per_batch_unfused"] = data[
            "dispatches_per_batch_unfused"
        ]
    # Columnar feeder artifacts (feeder mode): fold the pack line vs
    # the Python columnar line, plus the front A/B's queue-wait p99
    # per ingest path — the §23→§25 tail trajectory.
    if data.get("python_line_rows_per_s") is not None:
        out["python_line_rows_per_s"] = data["python_line_rows_per_s"]
        if data.get("pack_speedup") is not None:
            out["pack_speedup"] = data["pack_speedup"]
        ab = data.get("front_ab")
        if isinstance(ab, dict):
            for k in (
                "window_wait_p99_ms_off",
                "feeder_ring_wait_p99_ms_on",
                "feeder_ring_wait_p99_ms_light",
            ):
                if ab.get(k) is not None:
                    out[k] = ab[k]
    # Connection-scale artifacts (connscale mode): fold the conns
    # held, the reactor-front stage attribution (feeder ring wait p99
    # under client load — the §26 starvation acceptance), and the
    # event-vs-threaded equal-load delta with its fd footprint.
    if data.get("conns_held") is not None:
        out["conns_held"] = data["conns_held"]
        if data.get("ring_wait_p99_ms_top") is not None:
            out["ring_wait_p99_ms"] = data["ring_wait_p99_ms_top"]
        if data.get("errors") is not None:
            out["errors"] = data["errors"]
        ab = data.get("ab_equal_load")
        if isinstance(ab, dict):
            if ab.get("event_delta_pct") is not None:
                out["event_delta_pct"] = ab["event_delta_pct"]
            if ab.get("threaded_rate") is not None:
                out["threaded_rate"] = ab["threaded_rate"]
        rungs = data.get("rungs")
        if isinstance(rungs, list) and rungs:
            top = rungs[-1]
            if top.get("server_fd_peak") is not None:
                out["server_fd_peak"] = top["server_fd_peak"]
            if top.get("reactors") is not None:
                out["reactors"] = top["reactors"]
    # Flash-crowd replication artifacts (flashcrowd mode): fold the
    # hot-set-rotation p99 vs steady p99 (the flat-while-moving bar),
    # the replica-answered count, and the canary key's measured
    # over-admission against the N_replicas x lease bound.
    if data.get("rotation_p99_ms") is not None:
        out["rotation_p99_ms"] = data["rotation_p99_ms"]
        if data.get("steady_p99_ms") is not None:
            out["steady_p99_ms"] = data["steady_p99_ms"]
        if data.get("rotation_over_steady") is not None:
            out["rotation_over_steady"] = data["rotation_over_steady"]
        repl = data.get("replication")
        if isinstance(repl, dict):
            if repl.get("answered") is not None:
                out["replicated_answered"] = repl["answered"]
            if repl.get("promoted") is not None:
                out["keys_promoted"] = repl["promoted"]
        can = data.get("canary")
        if isinstance(can, dict) and can.get("over_admission") is not None:
            out["over_admission"] = can["over_admission"]
            out["over_admission_bound"] = can.get("bound")
        if data.get("errors") is not None:
            out["errors"] = data["errors"]
    # Multi-region federation artifacts (crossregion mode): fold the
    # partitioned phase's error rate + degraded-region answers (the
    # 0-errors acceptance), the drift canary's over-admission against
    # its N_regions x limit bound, the post-heal convergence seconds,
    # and the requeue drop count (0 inside the age cap).
    if data.get("heal_convergence_s") is not None:
        out["heal_convergence_s"] = data["heal_convergence_s"]
        part = data.get("partitioned")
        if isinstance(part, dict):
            if part.get("requests"):
                out["error_rate"] = round(
                    part.get("errors", 0) / part["requests"], 4
                )
            if part.get("degraded_region_answers") is not None:
                out["degraded_region_answers"] = part[
                    "degraded_region_answers"
                ]
        can = data.get("canary")
        if isinstance(can, dict) and can.get("over_admission") is not None:
            out["over_admission"] = can["over_admission"]
            out["over_admission_bound"] = can.get("bound")
        if data.get("hits_dropped") is not None:
            out["multiregion_hits_dropped"] = data["hits_dropped"]
    # Multi-node stage budgets: artifacts captured since the PR 15
    # histogram-merge fix carry real cross-node merged p50/p99 per
    # stage (bench.py _stage_budget_diff diffs and merges the nodes'
    # gubernator_stage_seconds buckets); older artifacts folded
    # per-node count/sum into means — the means-of-means lie.  Mark
    # every row so legacy numbers read as the means they are, not as
    # quantiles.
    sb = data.get("stage_budget_ms")
    if isinstance(sb, dict) and sb:
        legacy = not any(
            isinstance(v, dict) and "p99_ms" in v for v in sb.values()
        )
        out["stage_budget_kind"] = (
            "per-node means (legacy)" if legacy else "merged quantiles"
        )
    # Fleet observability A/B artifacts (fleetobs mode): fold the
    # off arm + median pair delta (the < 2% acceptance bar), the live
    # SLO burn-rate / admission-bound headroom columns
    # (gubernator_slo_burn_rate / gubernator_invariant_headroom as
    # measured during the run), and the rollup's scrape coverage.
    if data.get("fleetobs_delta_pct") is not None:
        out["fleetobs_off_value"] = data.get("fleetobs_off_value")
        out["fleetobs_delta_pct"] = data["fleetobs_delta_pct"]
        slo = data.get("slo")
        if isinstance(slo, dict):
            if slo.get("max_burn") is not None:
                out["slo_max_burn"] = slo["max_burn"]
            if slo.get("breaches") is not None:
                out["slo_breaches"] = slo["breaches"]
        can = data.get("canary")
        if isinstance(can, dict) and can.get("headroom") is not None:
            out["invariant_headroom"] = can["headroom"]
            out["invariant_bound"] = can.get("bound")
        fl = data.get("fleet")
        if isinstance(fl, dict) and fl.get("scrape_ok") is not None:
            out["fleet_scrape_ok"] = fl["scrape_ok"]
    # Paged-state artifacts (zipfpaged mode): fold the fault economy
    # (fault rate, spill p99), the residency footprint, and the hot
    # A/B against the dense arm (the ≤10% acceptance bar), so the
    # trend shows what serving 10x the resident key space costs.
    pg = data.get("paged")
    if isinstance(pg, dict):
        for src, dst in (
            ("fault_rate", "fault_rate"),
            ("spill_p99_ms", "spill_p99_ms"),
            ("resident_ratio", "resident_ratio"),
            ("keyspace_ratio", "keyspace_ratio"),
        ):
            if pg.get(src) is not None:
                out[dst] = pg[src]
        hot = data.get("hot")
        if isinstance(hot, dict):
            if hot.get("delta_pct") is not None:
                out["hot_delta_pct"] = hot["delta_pct"]
            if hot.get("dense_value") is not None:
                out["hot_dense_value"] = hot["dense_value"]
        dense = data.get("dense")
        if isinstance(dense, dict) and dense.get("churn_value") is not None:
            out["dense_churn_value"] = dense["churn_value"]
    # Tracing A/B artifacts (herdtrace mode): fold the off-arm value,
    # the delta (the < 2% acceptance bar), and the event-ring drop
    # count so the trend shows observability's cost alongside its
    # coverage.
    if data.get("tracing_delta_pct") is not None:
        out["tracing_off_value"] = data.get("tracing_off_value")
        out["tracing_delta_pct"] = data["tracing_delta_pct"]
    ev = data.get("native_events")
    if isinstance(ev, dict):
        ring = ev.get("ring") or {}
        if ring.get("dropped") is not None:
            out["ring_dropped"] = ring["dropped"]
        if ring.get("written") is not None:
            out["ring_written"] = ring["written"]
    return out or None


def collect() -> dict:
    trend: dict[str, dict] = {}
    for name in sorted(os.listdir(ROOT)):
        m = _NAME.match(name)
        if m is None:
            continue
        rnd, config = m.group(1), m.group(2) or "default"
        if config == "fast_capture":
            continue
        try:
            with open(os.path.join(ROOT, name)) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        row = _extract(data)
        if row is None:
            continue
        row["file"] = name
        trend.setdefault(config, {})[rnd] = row
    return trend


def _fmt(row: dict | None) -> str:
    if row is None:
        return "–"
    if "value" not in row:
        return "err"
    v = row["value"]
    val = f"{v / 1000:.1f}k" if v >= 10_000 else f"{v:g}"
    parts = [val]
    if row.get("p50_ms") is not None:
        parts.append(f"p50 {row['p50_ms']:g}")
    if row.get("dispatches_per_decision") is not None:
        parts.append(f"d/d {row['dispatches_per_decision']:g}")
    if row.get("dispatches_per_batch") is not None:
        parts.append(f"d/b {row['dispatches_per_batch']:g}")
    return " · ".join(parts)


def render_table(trend: dict) -> str:
    rounds = sorted({r for cfg in trend.values() for r in cfg})
    lines = [
        BEGIN,
        "",
        "### Bench trend (generated by `scripts/bench_trend.py` from "
        "the committed `BENCH_*` artifacts — dec/s · p50 ms · "
        "dispatches/decision; `–` = not captured that round)",
        "",
        "| config | " + " | ".join(rounds) + " |",
        "|---| " + " | ".join("---" for _ in rounds) + " |",
    ]
    for config in sorted(trend):
        cells = [_fmt(trend[config].get(r)) for r in rounds]
        lines.append(f"| {config} | " + " | ".join(cells) + " |")
    lines += ["", END]
    return "\n".join(lines)


def splice_perf(table: str) -> str:
    with open(PERF_PATH) as f:
        text = f.read()
    if BEGIN in text and END in text:
        pre = text[: text.index(BEGIN)]
        post = text[text.index(END) + len(END):]
        return pre + table + post
    return text.rstrip("\n") + "\n\n" + table + "\n"


def main() -> int:
    check = "--check" in sys.argv[1:]
    trend = collect()
    trend_json = json.dumps(trend, indent=1, sort_keys=True) + "\n"
    perf_text = splice_perf(render_table(trend))
    if check:
        try:
            with open(TREND_PATH) as f:
                current = f.read()
        except OSError:
            current = ""
        with open(PERF_PATH) as f:
            perf_current = f.read()
        if current != trend_json or perf_current != perf_text:
            print(
                "bench trend stale: run python scripts/bench_trend.py",
                file=sys.stderr,
            )
            return 1
        return 0
    with open(TREND_PATH, "w") as f:
        f.write(trend_json)
    with open(PERF_PATH, "w") as f:
        f.write(perf_text)
    print(
        f"BENCH_TREND.json: {len(trend)} configs, "
        f"{sum(len(v) for v in trend.values())} captures"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
