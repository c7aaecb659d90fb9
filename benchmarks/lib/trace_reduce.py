"""From a profiler trace to numbers: device busy time and idle share,
the events of named step programs, the top device operations and the
longest idle gaps.

Everything works on a compact form of the trace — planes, their lines,
and `[name, start_ns, duration_ns]` events — so that the arithmetic
can be checked on a small fixture (`benchmarks/fixtures/`).  Only
`load_xplane` touches jax, and only to read the file.
"""

from __future__ import annotations

import bisect
import fnmatch
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return found[-1] if found else None


def load_xplane(path: str) -> dict:
    """The profiler's .xplane.pb in compact form."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    return [
        p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE_PREFIX)
    ]


def host_planes(trace: dict) -> List[dict]:
    return [
        p for p in trace["planes"]
        if not p["name"].startswith("/device:")
    ]


def line_events(plane: dict, line_name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def op_events(plane: dict) -> List[list]:
    """What ran on the device: its operations, or its whole programs
    where the trace has no line of operations."""
    return line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)


def merge_intervals(events: Iterable[Sequence]) -> List[Interval]:
    """The union of [start, start + duration) intervals, sorted."""
    spans = sorted((e[1], e[1] + e[2]) for e in events if e[2] > 0)
    merged: List[Interval] = []
    for start, end in spans:
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def traced_window_ns(trace: dict) -> Interval:
    """First start and last end over every event of every plane."""
    first, last = None, None
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for _name, start, dur in line["events"]:
                if first is None or start < first:
                    first = start
                if last is None or start + dur > last:
                    last = start + dur
    if first is None:
        raise ValueError("the trace holds no event")
    return first, last


def device_busy(trace: dict) -> Optional[dict]:
    """Seconds in which an operation ran, per device plane, over the
    traced window.  None where no operation ran on any device."""
    planes = device_planes(trace)
    first, last = traced_window_ns(trace)
    per_chip = []
    for plane in planes:
        merged = merge_intervals(op_events(plane))
        per_chip.append(sum(e - s for s, e in merged) / 1e9)
    if not per_chip or max(per_chip) <= 0:
        return None
    window_s = (last - first) / 1e9
    busiest = max(per_chip)
    if busiest > window_s * 1.0001:
        raise ValueError(
            f"device busy {busiest:.6f}s exceeds the traced window "
            f"{window_s:.6f}s"
        )
    return {
        "busy_s": sum(per_chip) / len(per_chip),
        "busiest_busy_s": busiest,
        "per_chip_busy_s": per_chip,
        "window_s": window_s,
    }


def idle_pct(trace: dict) -> Optional[float]:
    """Idle share of the busiest chip over the traced window, in %."""
    busy = device_busy(trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy["busiest_busy_s"] / busy["window_s"])


def matches(name: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def module_events(trace: dict, patterns: Sequence[str]) -> List[list]:
    """The whole-program events whose name matches one of the glob
    patterns, on the first device (every chip of a mesh runs each
    program)."""
    planes = device_planes(trace)
    if not planes:
        return []
    return [
        e for e in line_events(planes[0], MODULES_LINE)
        if matches(e[0], patterns)
    ]


def module_seconds(trace: dict, patterns: Sequence[str]) -> Tuple[float, int]:
    """(summed device seconds, count) of the matching programs."""
    evs = module_events(trace, patterns)
    return sum(e[2] for e in evs) / 1e9, len(evs)


_HLO = re.compile(r"^(%?[\w.\-]+) = .*? ([\w\-]+)\((.*)$")


def short_op_name(name: str) -> str:
    """An operation's HLO text cut to `%result opcode(%first operand)`:
    the trace names a TPU operation by its whole instruction."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    operand = re.search(r"%[\w.\-]+", m.group(3))
    return f"{m.group(1)} {m.group(2)}({operand.group(0) if operand else ''})"[:120]


def top_device_ops(trace: dict, n: int = 10) -> List[list]:
    """[[name, seconds], ...] of the operations that took most device
    time, on the busiest plane."""
    planes = device_planes(trace)
    if not planes:
        return []
    totals: Dict[str, int] = {}
    plane = max(planes, key=lambda p: sum(e[2] for e in op_events(p)))
    for name, _start, dur in op_events(plane):
        name = short_op_name(name)
        totals[name] = totals.get(name, 0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: dict, n: int = 10, consider: int = 50,
              min_host_ns: int = 20_000) -> List[list]:
    """[[what the host was doing, seconds], ...]: the longest gaps
    between device operations on the busiest plane, each named by the
    host event that overlaps it most, summed by name."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = max(planes, key=lambda p: sum(e[2] for e in op_events(p)))
    merged = merge_intervals(op_events(plane))
    gaps = sorted(
        ((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])),
        reverse=True,
    )[:consider]
    host = sorted(
        (start, start + dur, name)
        for p in host_planes(trace) for line in p["lines"]
        for name, start, dur in line["events"] if dur >= min_host_ns
    )
    starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0)
    totals: Dict[str, int] = {}
    for length, g0, g1 in gaps:
        best, best_overlap = "host:untraced", 0
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        for h0, h1, name in host[lo:hi]:
            overlap = min(h1, g1) - max(h0, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        totals[best] = totals.get(best, 0) + length
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_device_ops(trace), "idle_gaps": idle_gaps(trace)}
