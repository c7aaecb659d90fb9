"""lib/bounds.py's rule on made-up runs, BENCHMARK.json's bounds held
to the rule applied to fixtures/spread_pr27.json, and a set made after
the bounds were set held to them."""

import json
import os
import statistics

import pytest

from conftest import BENCH, ROOT
from lib import bounds

ONE_CHIP = ("node100m_ledger0.batch1000_zipf", "node100m_ledger0.herd100")


def runs_of(cell, name, values, metric="rpc_p50_ms"):
    return [{"cell": cell, "set": name, "seed": i, "values": {metric: v}}
            for i, v in enumerate(values)]


def test_quartiles_are_the_drivers():
    # statistics.quantiles(n=4), exclusive: wider than numpy's default
    assert bounds.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert bounds.quartile_spread([100.0] * 6) == 0.0


def test_one_run_thrown_far_off_widens_no_bound_and_two_do():
    steady = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0]
    paused = [100.0, 100.2, 99.8, 100.1, 99.9, 77.0]   # one run lost 23 %
    twice = [100.0, 100.2, 99.8, 100.1, 92.0, 77.0]
    # the whole set's quartiles lean on the outermost run with weight 1/4
    assert bounds.quartile_spread(paused) == pytest.approx(
        bounds.quartile_spread(steady) + 0.25 * 0.23, rel=0.1)
    # the driver's reading for tightness leaves the farthest run out
    assert bounds.set_spread(paused) < 0.004
    alone = bounds.readings(runs_of("c", "A", paused))["rpc_p50_ms"]["c"]
    assert alone["lower"] < 0.01
    # (b) still knows: one such run in six does part two sides' medians
    assert alone["b"] > alone["lower"]
    assert bounds.set_spread(twice) > 0.03
    r = bounds.readings(runs_of("c", "A", paused) + runs_of("c", "B", twice))
    r = r["rpc_p50_ms"]["c"]
    assert r["widest"] == "B"
    assert r["lower"] == pytest.approx(bounds.TIGHT * bounds.set_spread(twice))


CALM = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0]
ROUGH = [100.0, 103.0, 97.0, 101.5, 98.5, 100.0]


def test_a_pair_reads_the_mean_of_its_two_sets_as_the_drivers_check_does():
    mean = (bounds.set_spread(CALM) + bounds.set_spread(ROUGH)) / 2
    pair = runs_of("c", "check_A", CALM) + runs_of("c", "check_B", ROUGH)
    r = bounds.readings(pair)["rpc_p50_ms"]["c"]
    assert list(r["spreads"]) == ["check"]
    assert r["lower"] == pytest.approx(bounds.TIGHT * mean)
    # the same two sets with no partner each: the rougher one decides
    apart = runs_of("c", "one", CALM) + runs_of("c", "other", ROUGH)
    r = bounds.readings(apart)["rpc_p50_ms"]["c"]
    assert r["widest"] == "other"
    assert r["lower"] == pytest.approx(bounds.TIGHT * bounds.set_spread(ROUGH))


def test_the_upper_end_is_what_the_calmest_two_sets_would_admit():
    # three sets of six: the driver's check reads the wider of two whole
    # sets, so the calmest check reads the second calmest set
    middling = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    runs = (runs_of("c", "one", CALM) + runs_of("c", "two", ROUGH)
            + runs_of("c", "three", middling))
    r = bounds.readings(runs)["rpc_p50_ms"]["c"]
    assert set(r["whole"]) == {"one", "two", "three"}
    assert r["upper"] == pytest.approx(bounds.LOOSE * bounds.quartile_spread(middling))
    # under two sets of six a cell has no upper end; four runs are no set of six
    few = runs_of("c", "one", CALM) + runs_of("c", "four", ROUGH[:4])
    r = bounds.readings(few)["rpc_p50_ms"]["c"]
    assert r["upper"] is None and list(r["spreads"]) == ["one", "four"]
    # the widest cell's calmest reading counts, as the widest cell's roughest does
    calm_cell = runs_of("mesh", "one", CALM) + runs_of("mesh", "two", CALM)
    cells = bounds.readings(runs + calm_cell)["rpc_p50_ms"]
    lower, upper = bounds.window(cells)
    assert upper == cells["c"]["upper"] > cells["mesh"]["upper"]
    assert lower == cells["c"]["lower"]


def test_the_bound_is_the_geometric_middle_of_its_window():
    middling = [100.0, 101.0, 99.0, 100.5, 99.5, 100.0]
    runs = (runs_of("c", "one", CALM) + runs_of("c", "two", ROUGH)
            + runs_of("c", "three", middling))
    cells = bounds.readings(runs)["rpc_p50_ms"]
    lower, upper = bounds.window(cells)
    assert lower < upper
    want = bounds.round_up(max((lower * upper) ** 0.5, cells["c"]["b"]))
    assert bounds.bounds(runs)["rpc_p50_ms"] == want
    assert lower < want < upper
    # the same distance from both ends, as a ratio, to the rounding
    assert want / lower == pytest.approx(upper / want, rel=0.1)


def test_two_sets_a_known_distance_apart_are_covered_by_the_draws():
    # each set steady to 0.1 %, the second 4 % above the first: the
    # window sees two tight sets, (b) sees that two sides of one code
    # lie 4 % apart
    low = [100.0, 100.1, 99.9, 100.05, 99.95, 100.0]
    high = [x * 1.04 for x in low]
    runs = runs_of("c", "A", low) + runs_of("c", "B", high)
    r = bounds.readings(runs)["rpc_p50_ms"]["c"]
    assert r["lower"] < 0.01 and r["upper"] < 0.02
    assert 0.03 < r["b"] < 0.045
    assert bounds.bounds(runs)["rpc_p50_ms"] == bounds.round_up(r["b"])
    assert 0.035 <= bounds.bounds(runs)["rpc_p50_ms"] <= 0.045


def test_a_steady_cell_lands_on_the_floor_and_the_widest_cell_decides():
    steady = runs_of("mesh", "A", [167.70, 167.71, 167.69, 167.70, 167.72, 167.70])
    assert bounds.bounds(steady) == {"rpc_p50_ms": 0.01, "setup_s": 0.25}
    wide = runs_of("herd", "A", [210.0, 214.0, 208.0, 216.0, 211.0, 213.0])
    both = bounds.bounds(steady + wide)
    alone = bounds.bounds(wide)
    assert both["rpc_p50_ms"] == alone["rpc_p50_ms"] > 0.02


def test_the_drivers_reading_counts_as_a_set_does():
    runs = runs_of("herd", "A", [210.0, 214.0, 208.0, 216.0, 211.0, 213.0])
    own = bounds.readings(runs)["rpc_p50_ms"]["herd"]
    wider = [{"pr": 26, "cell": "herd", "spread": {"rpc_p50_ms": 0.03}}]
    r = bounds.readings(runs, wider)["rpc_p50_ms"]["herd"]
    assert (r["widest"], r["b"]) == ("driver PR 26", own["b"])
    assert r["lower"] == pytest.approx(0.06)
    # one set of six: no upper end, and the bound is the lower one
    assert r["upper"] is None
    assert bounds.bounds(runs, wider)["rpc_p50_ms"] == 0.06
    narrower = [{"pr": 26, "cell": "herd", "spread": {"rpc_p50_ms": 0.001}}]
    assert bounds.bounds(runs, narrower) == bounds.bounds(runs)
    # a cell the ledger alone has read: a lower end from the reading, no (b)
    other = [{"pr": 26, "cell": "filled", "spread": {"rpc_p50_ms": 0.04}}]
    assert bounds.readings(runs, other)["rpc_p50_ms"]["filled"]["b"] == 0.0
    assert bounds.bounds(runs, other)["rpc_p50_ms"] == 0.08


def test_a_set_under_four_runs_feeds_the_draws_only():
    six = runs_of("c", "A", [100.0, 100.1, 99.9, 100.05, 99.95, 100.0])
    three = runs_of("c", "restarts", [100.0, 104.0, 96.0])
    r = bounds.readings(six + three)["rpc_p50_ms"]["c"]
    assert list(r["spreads"]) == ["A"]
    assert r["b"] > bounds.readings(six)["rpc_p50_ms"]["c"]["b"]


@pytest.mark.parametrize("x,want", [
    (0.0, 0.01), (0.0100001, 0.015), (0.015, 0.015), (0.0312, 0.035),
    (0.2, 0.2), (0.31, 0.25),
])
def test_rounding_up_to_the_step_inside_the_contract(x, want):
    assert bounds.round_up(x) == pytest.approx(want)


def test_the_draws_are_the_same_every_time():
    values = [100.0, 101.0, 99.0, 102.0, 98.5, 100.5, 100.2, 99.7]
    assert bounds.two_sides_p95(values) == bounds.two_sides_p95(values)


@pytest.fixture(scope="module")
def spread_file():
    with open(os.path.join(BENCH, "fixtures", "spread_pr27.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def have():
    """BENCHMARK.json's bounds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}


def sets_of(rows, cell):
    out = {}
    for r in rows:
        if r["cell"] == cell:
            out.setdefault(r["set"], []).append(r)
    return out


def test_benchmark_json_carries_what_the_rule_gives(spread_file, have):
    assert have == bounds.bounds(spread_file["runs"],
                                 spread_file["ledger"] + spread_file["refusal"])


@pytest.mark.parametrize("metric", ["decisions_per_s", "rpc_p50_ms", "rpc_p95_ms"])
def test_each_bound_lies_inside_what_every_check_so_far_would_admit(
        spread_file, have, metric):
    """Above twice the roughest reading, the driver's own among them,
    and under eight times the calmest, with room on both sides."""
    driver = spread_file["ledger"] + spread_file["refusal"]
    cells = bounds.readings(spread_file["runs"], driver)[metric]
    lower, upper = bounds.window(cells)
    assert 1.05 * lower < have[metric] < upper / 1.05, (lower, upper)
    (refused,) = spread_file["refusal"]
    assert cells[refused["cell"]]["spreads"]["driver PR 27"] == refused["spread"][metric]
    assert refused["spread"][metric] <= have[metric] / 2 / 1.1


@pytest.mark.parametrize("metric", ["decisions_per_s", "rpc_p50_ms", "rpc_p95_ms"])
def test_the_seed_does_not_change_the_work(spread_file, metric):
    """`refusal_step1`: two starts of one seed lie as far apart as
    starts of different seeds do, so no seed draws other work."""
    rows = sets_of(spread_file["runs"], ONE_CHIP[1])["refusal_step1"]
    by_seed = {}
    for r in rows:
        by_seed.setdefault(r["seed"], []).append(r["values"][metric])
    (twice,) = [v for v in by_seed.values() if len(v) == 2]
    once = [v[0] for v in by_seed.values() if len(v) == 1]
    assert len(once) == 2
    every = twice + once
    same_seed = abs(twice[0] - twice[1])
    assert same_seed >= 0.25 * (max(every) - min(every)), (twice, once)


def test_the_ledgers_readings_are_the_ledgers(spread_file):
    path = os.path.join(ROOT, "PERF_LEDGER.jsonl")
    if not os.path.exists(path):
        pytest.skip("no ledger beside this checkout")
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    read = {(r["pr"], r["workload"]): r["spread"] for r in lines if r.get("spread")}
    if not any(pr == 26 for pr, _ in read):
        pytest.skip("the ledger no longer holds PR 26's lines")
    for entry in spread_file["ledger"]:
        theirs = read[entry["pr"], entry["cell"]]
        assert entry["spread"] == {m: theirs[m] for m in entry["spread"]}
        assert "setup_s" not in entry["spread"] and entry["source"]


@pytest.mark.parametrize("metric", ["decisions_per_s", "rpc_p50_ms", "rpc_p95_ms"])
def test_a_set_the_rule_never_read_spreads_by_at_most_half_its_bound(
        spread_file, have, metric):
    """`held_out`: a set of six in the widest cell made after the bounds
    were set.  Read as the driver reads a set."""
    (rows,) = sets_of(spread_file["held_out"], ONE_CHIP[1]).values()
    assert len(rows) == 6 and len({r["seed"] for r in rows}) == 6
    spread = bounds.set_spread([r["values"][metric] for r in rows])
    assert spread <= have[metric] / 2, (metric, spread)


@pytest.mark.parametrize("pair", [("final_A", "final_B"), ("check_A", "check_B")])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_the_tree_against_itself_reads_unchanged(spread_file, have, cell, pair):
    """Two sets of the same code, interleaved on the same seeds: the
    second's median within the bound of the first's, on all four."""
    sets = sets_of(spread_file["runs"], cell)
    a, b = sets[pair[0]], sets[pair[1]]
    assert sorted(r["seed"] for r in a) == sorted(r["seed"] for r in b)
    for metric, bound in have.items():
        first, second = (statistics.median(r["values"][metric] for r in rows)
                         for rows in (a, b))
        assert abs(second - first) / first <= bound, (cell, metric, first, second)


def test_the_file_keeps_every_run_with_seed_start_and_four_values(spread_file):
    rows = spread_file["runs"] + spread_file["held_out"]
    assert len(rows) >= 75
    for r in rows:
        assert set(r["values"]) == {"decisions_per_s", "rpc_p50_ms",
                                    "rpc_p95_ms", "setup_s"}
        assert isinstance(r["seed"], int) and r["started_utc"].endswith("Z")
        assert r["correct"] is True and r["seconds"] == 20
    for cell in ONE_CHIP:
        sets = {name: [r["seed"] for r in rows]
                for name, rows in sets_of(spread_file["runs"], cell).items()}
        assert sum(len(s) == 6 and len(set(s)) == 6 for s in sets.values()) >= 5
    restarts = sets_of(spread_file["runs"], ONE_CHIP[0])["step0_restarts"]
    assert len(restarts) == 3 and len({r["seed"] for r in restarts}) == 1
