"""The two readers that need the program's annotations on the
profiler's timeline, on a hand-built fixture: two host threads — one
working, one waiting in a blocking read across the same gaps —
nested-free annotations and three step events."""

import json
import os

import pytest

from conftest import BENCH
from lib import trace_reduce
from lib.manifest import Manifest, read_metric

STEP = ["*_collapsed_step_core*"]
READS = ["np.asarray(jax.Array)"]
WORK = ["wire.decode", "engine.intern", "engine.pack", "device.h2d",
        "device.launch", "engine.unpack", "wire.encode"]


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(BENCH, "fixtures", "trace_stages_small.json")) as f:
        return json.load(f)


def tail(trace, **args):
    spec = {"reader": "trace_host_tail",
            "args": dict({"patterns": STEP, "host_events": READS}, **args)}
    return read_metric(spec, {"trace": trace})


def cover(trace, stages=WORK):
    spec = {"reader": "trace_gap_cover", "args": {"stages": stages}}
    return read_metric(spec, {"trace": trace})


def test_answer_tail_is_the_median_of_each_steps_own_read(trace):
    # step ends 3000, 7000, 11000; the reads that span them end soonest
    # at 3100 (thread 0), 7400 (thread 0), 11300 (thread 1): 100, 400, 300 ns
    assert tail(trace) == pytest.approx(0.3)


def test_a_read_that_spans_several_steps_is_each_ones_when_alone(trace):
    # without thread 0 only thread 1's long wait is left: it spans all
    # three ends and is every step's earliest: 8300, 4300, 300 ns
    only_waiter = json.loads(json.dumps(trace))
    only_waiter["planes"][1]["lines"] = only_waiter["planes"][1]["lines"][1:]
    assert tail(only_waiter) == pytest.approx(4.3)


def test_gap_cover_is_work_over_idle_whoever_waits(trace):
    # gaps [3000, 5000) and [7000, 9000): 4000 ns idle; work under them
    # 1700 ns (pack, h2d, launch) + 1450 ns (unpack .. launch)
    assert cover(trace) == pytest.approx(100 * 3150 / 4000)
    # the benchmark's most-overlap rule gives both gaps to the thread
    # that waits across them (PERF.md 7): the reason this metric exists
    gaps = dict(trace_reduce.idle_gaps(trace, min_host_ns=0))
    assert gaps == {"np.asarray(jax.Array)": pytest.approx(4000e-9)}


def test_gap_cover_counts_only_the_named_stages(trace):
    assert cover(trace, ["engine.pack"]) == pytest.approx(100 * 1200 / 4000)
    # a wait is not work, however it is named
    assert cover(trace, READS) == pytest.approx(100.0)


def test_annotations_in_the_fixture_never_nest(trace):
    for line in trace["planes"][1]["lines"]:
        spans = sorted((e[1], e[1] + e[2]) for e in line["events"])
        assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))


@pytest.mark.parametrize("what", ["no_trace", "parent_trace", "no_gap"])
def test_readers_find_nothing_and_do_not_raise(trace, what):
    """The parent commit writes no annotation; a CPU run has no trace."""
    if what == "no_trace":
        assert tail(None) is None and cover(None) is None
        return
    t = json.loads(json.dumps(trace))
    if what == "parent_trace":
        for line in t["planes"][1]["lines"]:
            line["events"] = [e for e in line["events"] if e[0] in READS]
        assert cover(t) is None  # gaps, and nothing of the program's to lay over them
        assert tail(t) == pytest.approx(0.3)  # PJRT's own events are there
        for line in t["planes"][1]["lines"]:
            line["events"] = []
        t["planes"][1]["lines"][0]["events"] = [["other", 0, 10]]
        assert tail(t) is None
    else:
        t["planes"][0]["lines"][1]["events"] = [["%fusion.1 = fusion()", 1000, 10000]]
        assert cover(t) is None
    with open(os.path.join(BENCH, "fixtures", "trace_small.json")) as f:
        old = json.load(f)
    assert tail(old) is None and cover(old) is None


def test_mesh_roofline_sets_the_work_against_chip_seconds(trace):
    """Four chips, each with the fixture's three 2,000 ns steps: the
    mesh's decisions over one chip's step time times four."""
    from lib import roofline

    mesh = json.loads(json.dumps(trace))
    for i in range(1, 4):
        chip = json.loads(json.dumps(trace["planes"][0]))
        chip["name"] = f"/device:TPU:{i}"
        mesh["planes"].append(chip)
    meta = {"vars_start": {"counters": {"requests_total": 1000}},
            "vars_stop": {"counters": {"requests_total": 1400}}}
    spec = {"reader": "trace_roofline_mesh", "args": {"patterns": STEP}}
    ctx = {"trace": mesh, "trace_meta": meta, "device_kind": "TPU v5 lite"}
    seconds, count = trace_reduce.module_seconds(mesh, STEP)
    assert count == 3
    want = roofline.roofline_pct(400, 4 * seconds, "TPU v5 lite")
    assert read_metric(spec, ctx) == pytest.approx(want)
    # one chip: the accepted reader's number
    one = dict(ctx, trace=trace)
    assert read_metric(spec, one) == pytest.approx(
        read_metric(dict(spec, reader="trace_roofline"), one))
    assert read_metric(spec, dict(ctx, trace=None)) is None
    assert read_metric(spec, dict(ctx, trace_meta=None)) is None


def test_pack_time_is_per_rpc_however_many_slices_observed_it():
    """`engine.pack` is observed slice by slice between the dispatches;
    the metric sets its sum against the RPCs that held the lock."""
    m = Manifest(os.path.dirname(BENCH))

    def key(sample, stage):
        return ("gubernator_stage_duration_" + sample, (("stage", stage),))

    before = {key("sum", "engine.pack"): 1.0, key("count", "engine.pack"): 30.0,
              key("count", "engine.lock_hold"): 10.0}
    after = {key("sum", "engine.pack"): 1.5, key("count", "engine.pack"): 90.0,
             key("count", "engine.lock_hold"): 30.0}
    ctx = {"prom_before": before, "prom_after": after}
    assert read_metric(m.layer_metric("host.pack_us"), ctx) == pytest.approx(
        1e6 * 0.5 / 20)
    # the parent commit has neither stage
    assert read_metric(m.layer_metric("host.pack_us"),
                       {"prom_before": {}, "prom_after": {}}) is None


def test_the_mesh_cells_own_entries_read_as_the_accepted_ones():
    m = Manifest(os.path.dirname(BENCH))
    for name in ("generator.cpu_busy_pct", "listener.grpc_server_ms",
                 "host.engine_serve_ms", "pump.dispatches_per_kdecision",
                 "device.idle_pct"):
        accepted, own = m.layer_metric(name), m.layer_metric(name + ".mesh4")
        assert (own["reader"], own["args"]) == (accepted["reader"], accepted["args"])
        assert [x["workloads"] for x in m.doc["per_layer"]
                if x["name"] == name + ".mesh4"] == [["mesh4_ledger0.batch1000_zipf"]]
    assert (m.layer_metric("mesh_step_roofline")["args"]["patterns"]
            == m.layer_metric("mesh.step_us_per_dispatch")["args"]["patterns"])


def test_the_new_metrics_files_name_these_readers():
    m = Manifest(os.path.dirname(BENCH))
    assert m.layer_metric("pump.answer_tail_us")["reader"] == "trace_host_tail"
    assert m.layer_metric("device.idle_explained_pct")["reader"] == "trace_gap_cover"
    assert m.layer_metric("device.idle_explained_pct")["args"]["stages"]
