"""The reduction from a trace to metrics, on a hand-built fixture."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH  # noqa: F401 — puts benchmarks/ on the path
from lib import roofline, trace_reduce
from lib.manifest import read_metric

STEP = ["*_fused_step_core*", "*_multi_fused_core*", "*_uniform_step_core*"]


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(BENCH, "fixtures", "trace_small.json")) as f:
        return json.load(f)


def test_busy_is_the_union_of_operation_intervals(trace):
    busy = trace_reduce.device_busy(trace)
    # [1000,1400) merges two overlapping ops; then 1000, 100 and 600 ns.
    assert busy["busy_s"] == pytest.approx(2100e-9)
    assert busy["window_s"] == pytest.approx(10000e-9)  # host events count
    assert busy["per_chip_busy_s"] == [pytest.approx(2100e-9)]


def test_idle_share(trace):
    assert trace_reduce.idle_pct(trace) == pytest.approx(79.0)


@pytest.mark.parametrize("patterns,count,seconds", [
    (STEP, 3, 2000e-9),
    (["*_multi_fused_core*"], 1, 1000e-9),
    (["*stack_outputs*"], 1, 100e-9),
    (["*local_merge*"], 0, 0.0),
])
def test_step_modules_match_by_name_pattern(trace, patterns, count, seconds):
    got_s, got_n = trace_reduce.module_seconds(trace, patterns)
    assert got_n == count and got_s == pytest.approx(seconds)


def test_kernel_us_per_dispatch_reader(trace):
    spec = {"reader": "trace_modules",
            "args": {"patterns": STEP, "stat": "us_per_event"}}
    assert read_metric(spec, {"trace": trace}) == pytest.approx(2.0 / 3)


def test_reader_that_finds_nothing_returns_nothing(trace):
    merge = {"reader": "trace_modules", "args": {"patterns": ["*local_merge*"]}}
    assert read_metric(merge, {"trace": trace}) is None
    assert read_metric(merge, {"trace": None}) is None
    assert read_metric({"reader": "trace_idle"}, {"trace": None}) is None


def test_breakdown_names_ops_and_gaps(trace):
    b = trace_reduce.breakdown(trace)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(1250e-9)]
    # every host event counts here; a real trace drops those under 20 µs
    gaps = dict(trace_reduce.idle_gaps(trace, min_host_ns=0))
    # the 2.7 µs gap before the uniform step lies under intern_and_pack
    assert gaps["intern_and_pack"] == pytest.approx(2700e-9)
    assert gaps["host:untraced"] == pytest.approx(800e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_busy_over_the_window_is_refused(trace):
    bad = json.loads(json.dumps(trace))
    bad["planes"] = bad["planes"][:1]
    bad["planes"][0]["lines"][1]["events"] = [["a", 0, 500], ["b", 9000, 500]]
    bad["planes"][0]["lines"][0]["events"] = [["m", 100, 100]]
    ok = trace_reduce.device_busy(bad)
    assert ok["busy_s"] == pytest.approx(1000e-9)
    # an event reaching past every other one cannot exceed the window it defines
    assert ok["busy_s"] <= ok["window_s"]


def test_roofline_arithmetic():
    # 1e6 decisions x (2 x 48 + 40 + 20) B = 156 MB; at 819 GB/s 190.5 us.
    assert roofline.decision_bytes(1_000_000) == 156_000_000
    share = roofline.roofline_pct(1_000_000, 0.01, "TPU v5 lite")
    assert share == pytest.approx(100 * 156e6 / 819e9 / 0.01)


def test_roofline_reader_counts_decisions_inside_the_slice(trace):
    ctx = {
        "trace": trace, "device_kind": "TPU v5 lite",
        "trace_meta": {
            "vars_start": {"counters": {"requests_total": 100}},
            "vars_stop": {"counters": {"requests_total": 110}},
        },
    }
    spec = {"reader": "trace_roofline", "args": {"patterns": STEP}}
    want = 100 * (10 * 156 / 819e9) / 2000e-9
    assert read_metric(spec, ctx) == pytest.approx(want)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks known"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(10, 1.0, "cpu")


def test_share_over_100_raises():
    with pytest.raises(ValueError, match="> 100"):
        roofline.roofline_pct(10**9, 1e-6, "TPU v5 lite")


def test_import_touches_neither_jax_nor_libtpu():
    code = (
        "import sys; sys.path.insert(0, %r); "
        "from lib import trace_reduce, roofline, manifest; "
        "import readers.trace_modules, readers.trace_idle, readers.trace_roofline; "
        "assert 'jax' not in sys.modules and 'libtpu' not in sys.modules"
    ) % BENCH
    subprocess.run([sys.executable, "-c", code], check=True)
