"""One rule for the end-to-end bounds of BENCHMARK.json, applied to
the readings kept in a file (`fixtures/spread_pr27.json`).

A run is `{"cell", "set", "seed", "started_utc", "values": {metric:
value}}`; a set is the runs of one cell that carry one `set` name, made
one after another on different seeds, as the driver makes a side's.  Two
sets named `<x>_A` and `<x>_B` are a pair: the same code interleaved on
the same seeds, as the driver interleaves parent and change.  A reading
of the driver's is `{"pr", "cell", "spread": {metric: share}}`: what
its own check read on the same code, copied from PERF_LEDGER.jsonl or
from its refusal of a bound.

The driver holds a bound to two tests, and on these cells the machine a
run gets decides the spread (a calm call reads a quarter of a rough
one's), so a bound has to pass the first on the roughest check and the
second on the calmest.  For each metric:

  lower  TIGHT times the widest reading of any cell: the driver refuses
         a bound as too tight where the mean of its two sets' spreads
         passes half of it.  A set's spread there is the quartile
         distance over median (Python's `statistics.quantiles(values,
         n=4)`) of its runs with the one farthest from the median left
         out; a pair reads the mean of its two sets; a set of four runs
         or more with no partner, and a reading of the driver's, count
         as they stand.  One run that a machine pause threw far off
         widens nothing here, two in a set do; (b) keeps it.
  upper  LOOSE times the calmest reading: the driver refuses a bound as
         too loose where it passes eight times the wider of its two
         sets' spreads, every run kept, in the widest cell.  A cell's
         calmest two sets of six read the second smallest of its sets'
         whole spreads, and the widest cell's counts.
  (b)    the 95th percentile of |median A - median B| / median over
         DRAWS draws, with replacement, of two sets of six from all of
         a cell's runs: what two sides of the same code differ by.

A metric's bound is the geometric middle of lower and upper, or (b) in
its widest cell where that is more, rounded up to the next STEP, inside
FLOOR..CEILING.  `setup_s` is not by the rule: the driver judges it by
its median alone and it keeps 0.25.
"""

from __future__ import annotations

import math
import random
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List

TIGHT, LOOSE = 2.0, 8.0
DRAWS = 10_000
SET_SIZE = 6
STEP = 0.005
FLOOR, CEILING = 0.01, 0.25
SETUP = ("setup_s", 0.25)


def quartile_spread(values: List[float]) -> float:
    """The distance between the first and the third quartile as a
    share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def set_spread(values: List[float]) -> float:
    """A set's spread as the driver reads it for tightness: without
    the run farthest from the median."""
    middle = statistics.median(values)
    kept = list(values)
    kept.remove(max(kept, key=lambda v: abs(v - middle)))
    return quartile_spread(kept)


def two_sides_p95(values: List[float], seed: int = 27) -> float:
    """(b): how far the medians of two sets of six of the same code lie
    apart, as a share of the median, at the 95th percentile."""
    rng = random.Random(seed)
    middle = statistics.median(values)
    gaps = sorted(
        abs(statistics.median(rng.choices(values, k=SET_SIZE))
            - statistics.median(rng.choices(values, k=SET_SIZE))) / middle
        for _ in range(DRAWS)
    )
    return gaps[math.ceil(0.95 * DRAWS) - 1]


def round_up(x: float) -> float:
    """To the next STEP, inside FLOOR..CEILING."""
    stepped = math.ceil(round(x / STEP, 9)) * STEP
    return round(min(CEILING, max(FLOOR, stepped)), 6)


def pair_name(name: str) -> str:
    """`check_A` and `check_B` are the pair `check`; any other set
    stands alone under its own name."""
    return name[:-2] if name.endswith(("_A", "_B")) else name


def readings(runs: Iterable[dict], driver: Iterable[dict] = ()) -> Dict[str, Dict[str, dict]]:
    """{metric: {cell: {"spreads": {pair, set or "driver PR n": reading},
    "widest", "lower", "whole": {set: spread}, "upper", "b"}}} for every
    metric but `setup_s`; `upper` is None where the cell has no two sets
    of six."""
    sets = defaultdict(lambda: defaultdict(list))  # (metric, cell) -> set -> values
    for r in runs:
        for metric, value in r["values"].items():
            sets[metric, r["cell"]][r["set"]].append(value)
    spreads = defaultdict(dict)
    for key, by_set in sets.items():
        pairs = defaultdict(list)
        for name, v in by_set.items():
            if len(v) >= 4:
                pairs[pair_name(name)].append(set_spread(v))
        spreads[key] = {name: statistics.mean(s) for name, s in pairs.items()}
    for entry in driver:
        for metric, spread in entry["spread"].items():
            spreads[metric, entry["cell"]][f"driver PR {entry['pr']}"] = spread
    out = defaultdict(dict)
    for (metric, cell), read in sorted(spreads.items()):
        if metric == SETUP[0] or not read:
            continue
        by_set = sets.get((metric, cell), {})
        every = [v for values in by_set.values() for v in values]
        whole = {name: quartile_spread(v) for name, v in by_set.items()
                 if len(v) >= SET_SIZE}
        widest = max(read, key=read.get)
        out[metric][cell] = {
            "spreads": read, "widest": widest, "lower": TIGHT * read[widest],
            "whole": whole,
            "upper": LOOSE * sorted(whole.values())[1] if len(whole) > 1 else None,
            "b": two_sides_p95(every) if every else 0.0,
        }
    return out


def window(cells: Dict[str, dict]) -> tuple:
    """(lower, upper) of one metric over its cells; upper is None where
    no cell has two sets of six."""
    uppers = [c["upper"] for c in cells.values() if c["upper"] is not None]
    return max(c["lower"] for c in cells.values()), max(uppers, default=None)


def bounds(runs: Iterable[dict], driver: Iterable[dict] = ()) -> Dict[str, float]:
    """The bound of every end-to-end metric the readings report."""
    out = {}
    for metric, cells in readings(runs, driver).items():
        lower, upper = window(cells)
        middle = lower if upper is None else math.sqrt(lower * upper)
        out[metric] = round_up(max(middle, max(c["b"] for c in cells.values())))
    out[SETUP[0]] = SETUP[1]
    return out
