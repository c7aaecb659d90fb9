"""The filled node's cell (`node100m_ledger0_filled.batch1000_zipf`):
its snapshot, its copy of the bounded-cache reference, its readers, a
CPU rehearsal at 20,000 rows, and the faults its launcher must catch —
each a whole run of run.py that has to end as a harness failure.  The
faults' runs are started together (a daemon's start is most of a
rehearsal) and each test reads its own."""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT
from harness import CONTRACT_KEYS, checkout_copy
from lib import clear_bytes, lru_reference, roofline, spec
from lib.manifest import Manifest, read_metric
from lib.snapshot import CHUNK_ROWS, Snapshot

CELL = "node100m_ledger0_filled.batch1000_zipf"
CONFIG = "node100m_ledger0_filled"
LAUNCHER = os.path.join(ROOT, "tests", "benchmark", "faulty_filled_launcher.py")
FAULTS = {
    # (the harness' first request may have taken the row left free)
    "one_row_short": "table not full: ",
    "wrong_remaining": "1 of 5000 restored keys do not answer from their restored state",
    "no_columnar_load": "the engine asked this Loader for load(), the per-item walk",
    "no_item_columns": "this program's Loader protocol has no columns",
}
DATED = 1_700_000_000_000


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def block(manifest):
    return manifest.config(CONFIG)["snapshot"]


# -- the configuration and its entries -----------------------------------


def test_configuration_is_its_sibling_filled(manifest):
    filled, sibling = manifest.config(CONFIG), manifest.config("node100m_ledger0")
    for key in ("env", "chips", "engine", "rows", "row_bytes",
                "departs_from_defaults"):
        assert filled[key] == sibling[key], key
    assert filled["rehearsal"] == sibling["rehearsal"]
    assert filled["snapshot"]["rows"] == filled["rows"] == 100_000_000
    assert set(filled["reduced"]) == {"gregorian_rows"}  # no rows_occupied_at_start
    assert set(sibling["guarantees"]) < set(filled["guarantees"])
    assert filled["launcher"] == "benchmarks/lib/launch_filled.py"
    assert os.path.exists(os.path.join(ROOT, filled["launcher"]))
    assert manifest.cell(CELL) == dict(
        manifest.cell("node100m_ledger0.batch1000_zipf"), name=CELL, config=CONFIG)


def test_new_entries_list_the_new_cell_alone(manifest):
    doc = manifest.doc
    mine = [m for m in doc["per_layer"] if CELL in m.get("workloads", [])]
    assert len(mine) == 20 and all(m["workloads"] == [CELL] for m in mine)
    assert doc["per_layer"][-len(mine):] == mine  # appended, in one block
    assert doc["workloads"][-1]["name"] == CELL and doc["workloads"][-1]["chips"] == 1
    assert doc["configs"][-1]["name"] == CONFIG
    by_name = {m["name"]: m for m in doc["per_layer"]}
    for m in mine:
        if not m["name"].endswith(".filled"):
            continue
        accepted = by_name[m["name"][: -len(".filled")]]
        assert {k: v for k, v in m.items() if k not in ("name", "workloads")} == {
            k: v for k, v in accepted.items() if k not in ("name", "workloads")}
        a = manifest.layer_metric(accepted["name"])
        f = manifest.layer_metric(m["name"])
        assert (f["reader"], f["args"]) == (a["reader"], a["args"])


# -- the snapshot ---------------------------------------------------------


def test_snapshot_rows_are_a_function_of_seed_row_and_date(block):
    snap = Snapshot(block, 100_000_000, DATED)
    r = np.array([0, 1, 99_999_999, 12_345_678], dtype=np.int64)
    a, b = snap.columns(r), Snapshot(block, 100_000_000, DATED).columns(r)
    assert all((a[k] == b[k]).all() for k in a)
    keys = bytes(a["key_buf"]).decode()
    assert [keys[i:i + 14] for i in range(0, len(keys), 14)] == [
        "fill_f00000000", "fill_f00000001", "fill_f99999999", "fill_f12345678"]
    assert a["key_offsets"].tolist() == [0, 14, 28, 42, 56]
    assert [snap.key(int(x)) for x in r][2] == "fill_f99999999"
    assert snap.unique_key(7) == b"f00000007"
    other = Snapshot(dict(block, seed=block["seed"] + 1), 100_000_000, DATED)
    assert (other.columns(np.arange(1000))["limit"]
            != snap.columns(np.arange(1000))["limit"]).any()
    later = Snapshot(block, 100_000_000, DATED + 5).columns(r)
    assert (later["t0"] == a["t0"] + 5).all() and (later["limit"] == a["limit"]).all()


def test_snapshot_rows_are_live_partly_spent_and_of_every_kind(block):
    snap = Snapshot(block, 100_000_000, DATED)
    (r,) = list(snap.chunks(CHUNK_ROWS))[:1]
    c = snap.columns(r)
    assert len(r) == CHUNK_ROWS and list(c)[2:] == list(
        ["algo", "status", "limit", "remaining", "remf_hi", "remf_lo",
         "duration", "t0", "expire_at", "burst", "invalid_at"])
    leaky = c["algo"] == 1
    for values, col in ((block["limits"], "limit"),
                        (block["durations_ms"], "duration")):
        seen, counts = np.unique(c[col], return_counts=True)
        assert seen.tolist() == values
        assert (counts > 0.3 * len(r)).all()
    assert 0.49 < leaky.mean() < 0.51
    # last touched under a quarter of its duration ago: live for the
    # 45 minutes a cold start and a run cannot take
    assert (c["t0"] <= DATED).all() and (c["t0"] > DATED - c["duration"] // 4).all()
    assert (c["expire_at"] == c["t0"] + c["duration"]).all()
    assert (c["expire_at"] >= DATED + 45 * 60_000).all()
    tok = ~leaky
    assert (c["remaining"][tok] >= 0).all() and (c["remaining"][tok] <= c["limit"][tok]).all()
    assert 0.3 < (c["remaining"][tok] / c["limit"][tok]).mean() < 0.7  # partly spent
    assert (c["remf_hi"][leaky] < c["limit"][leaky]).all() and c["remf_lo"][leaky].any()
    assert (c["burst"] == np.where(leaky, c["limit"], 0)).all()
    assert not c["remaining"][leaky].any() and not c["remf_hi"][tok].any()
    assert not c["status"].any() and not c["invalid_at"].any()


def test_snapshot_keys_lie_outside_the_traffics(manifest, block):
    mix = manifest.mix("batch1000_zipf")
    names = set(mix["mixed"]["names"]) | {mix["uniform"]["name"]}
    assert block["name"] not in names
    assert block["unique_key"][0] != mix["keys"]["unique_key"][0]


def test_sample_is_seeded_and_from_the_newest_quarter(block):
    snap = Snapshot(block, 100_000_000, DATED)
    s = snap.sample()
    assert len(s) == len(set(s.tolist())) == 10_000 and (np.diff(s) > 0).all()
    assert s.min() >= 75_000_000 and s.max() < 100_000_000
    assert (s == Snapshot(block, 100_000_000, DATED + 9).sample()).all()
    small = Snapshot(block, 20_000, DATED).sample()
    assert len(small) == 5_000 and small.min() == 15_000
    # the reference's rows are the columns' rows
    states = snap.states(s[:50])
    c = snap.columns(s[:50])
    for i, (key, st) in enumerate(states):
        assert key == snap.key(int(s[i]))
        assert (st.algorithm, st.limit, st.duration, st.t0, st.expire_at, st.burst) == (
            c["algo"][i], c["limit"][i], c["duration"][i], c["t0"][i],
            c["expire_at"][i], c["burst"][i])
        if st.algorithm == 1:
            assert st.remaining_f == c["remf_hi"][i] + c["remf_lo"][i] * 2.0**-32
        else:
            assert st.remaining == c["remaining"][i]


# -- the benchmark's copy of the bounded-cache reference -------------------


def test_lru_reference_copy_agrees_with_the_programs():
    sys.path.insert(0, ROOT)
    from gubernator_tpu.models import lru_reference as theirs
    from gubernator_tpu.models import spec as their_spec

    rng = np.random.default_rng(28)
    mine, other = lru_reference.LRUReference(64), theirs.LRUReference(64)
    rows = [(f"fill_f{i}", dict(
        algorithm=i % 2, limit=10, remaining=i % 11, remaining_f=i % 10 + 0.25,
        duration=60_000, t0=DATED - i, expire_at=DATED + (60_000 if i % 5 else -1),
        burst=10 * (i % 2))) for i in range(80)]
    mine.load([(k, spec.SlotState(**s)) for k, s in rows], DATED)
    other.load([(k, their_spec.SlotState(**s)) for k, s in rows], DATED)
    for step in range(3000):
        i = int(rng.zipf(1.2)) % 300
        key = f"fill_f{i}" if i % 3 else f"mix_0_k{i}"
        q = dict(hits=int(rng.integers(0, 3)), limit=10, duration=60_000,
                 burst=10 * (i % 2), algorithm=i % 2)
        a = mine.get_rate_limit(key, spec.SpecInput(**q), DATED + step)
        b = other.get_rate_limit(key, their_spec.SpecInput(**q), DATED + step)
        assert (a.status, a.limit, a.remaining, a.reset_time) == (
            int(b.status), b.limit, b.remaining, b.reset_time), step
    assert mine.evicted == other.evicted and len(mine.evicted) > 100
    assert (mine.evictions, mine.unexpired_evictions) == (
        other.evictions, other.unexpired_evictions)
    assert 0 < mine.unexpired_evictions < mine.evictions
    assert list(mine.buckets) == list(other.buckets)


def test_lru_reference_evicts_the_oldest_and_an_evicted_key_starts_empty():
    ref = lru_reference.LRUReference(2)
    q = spec.SpecInput(hits=1, limit=5, duration=60_000)
    assert ref.get_rate_limit("a", q, DATED).remaining == 4
    assert ref.get_rate_limit("b", q, DATED).remaining == 4
    assert ref.get_rate_limit("a", q, DATED).remaining == 3  # a is the newest
    assert ref.get_rate_limit("c", q, DATED).remaining == 4  # evicts b
    assert ref.evicted == ["b"] and ref.unexpired_evictions == 1
    assert ref.get_rate_limit("b", q, DATED).remaining == 4  # from an empty bucket
    assert ref.evicted == ["b", "a"]
    ref.load([("d", spec.SlotState(limit=5, remaining=1, duration=60_000,
                                   t0=DATED, expire_at=DATED - 1))], DATED)
    ref.load([("e", None)], DATED)  # evicts b; then d, expired, goes uncounted
    ref.get_rate_limit("f", q, DATED)
    assert ref.evicted == ["b", "a", "c", "b", "d"]
    assert (ref.evictions, ref.unexpired_evictions) == (5, 4)


# -- the new readers and the clear's byte count ----------------------------


def test_clear_bytes_and_its_roofline():
    assert clear_bytes.CLEAR_ROW_BYTES == 12
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # 404 rows in a 64.2 us clear (ledger, PR 26): 0.0092 %
    assert clear_bytes.clear_roofline_pct(404, 64.2e-6, "TPU v5 lite") == (
        pytest.approx(100 * 404 * 12 / peak / 64.2e-6))
    with pytest.raises(ValueError, match="> 100 %"):
        clear_bytes.clear_roofline_pct(10**9, 1e-6, "TPU v5 lite")
    with pytest.raises(ValueError):
        clear_bytes.clear_roofline_pct(0, 1e-6, "TPU v5 lite")
    with pytest.raises(KeyError):
        clear_bytes.clear_roofline_pct(404, 64.2e-6, "TPU v9")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(ROOT, "benchmarks", "fixtures", "trace_small.json")) as f:
        trace = json.load(f)
    trace["planes"][0]["lines"][0]["events"] += [
        ["jit__clear_occupied_impl(555)", 7000, 300],
        ["jit__clear_occupied_impl(555)", 8000, 100],
    ]
    return trace


def meta(start, stop):
    return {"vars_start": {"counters": start}, "vars_stop": {"counters": stop}}


@pytest.mark.parametrize("metric,ctx,want", [
    ("step.clear_us_per_dispatch", {}, 0.2),
    ("clear_roofline",
     {"trace_meta": meta({"evictions_total": 100}, {"evictions_total": 900})},
     100 * 800 * 12 / 819e9 / 400e-9),
    # a program with no such counter (the parent), or no eviction: nothing
    ("clear_roofline", {"trace_meta": meta({}, {})}, None),
    ("clear_roofline",
     {"trace_meta": meta({"evictions_total": 5}, {"evictions_total": 5})}, None),
    ("clear_roofline", {"trace_meta": None}, None),
    ("filled_step_roofline",
     {"trace_meta": meta({"requests_total": 0}, {"requests_total": 2000})},
     100 * 2000 * 156 / 819e9 / 2000e-9),
    ("setup.load_s",
     {"prom_after": {("gubernator_stage_duration_sum", (("stage", "engine.load"),)): 17.4}},
     17.4),
    ("setup.load_s", {"prom_after": {}}, None),
    ("setup.load_krows_per_s",
     {"prom_after": {
         ("gubernator_stage_duration_sum", (("stage", "engine.load"),)): 20.0,
         ("gubernator_loaded_rows_count_total", ()): 100_000_000.0}}, 5000.0),
    ("setup.load_krows_per_s", {"prom_after": {}}, None),
    ("evict.rows_per_kdecision",
     {"vars_before": {"device": {"counters": {"evictions_total": 1000}}},
      "vars_after": {"device": {"counters": {"evictions_total": 1_201_000}}},
      "run": {"decisions": 3_000_000}}, 400.0),
    ("evict.rows_per_kdecision",
     {"vars_before": {"device": {"counters": {}}},
      "vars_after": {"device": {"counters": {}}}, "run": {"decisions": 9}}, None),
    ("host.evict_clear_us",
     {"prom_before": {
         ("gubernator_stage_duration_sum", (("stage", "engine.evict_clear"),)): 1.0,
         ("gubernator_stage_duration_count", (("stage", "engine.lock_hold"),)): 100.0},
      "prom_after": {
         ("gubernator_stage_duration_sum", (("stage", "engine.evict_clear"),)): 2.0,
         ("gubernator_stage_duration_count", (("stage", "engine.lock_hold"),)): 2100.0}},
     500.0),
    ("host.evict_clear_us", {"prom_before": {}, "prom_after": {}}, None),
])
def test_new_layer_metrics_on_fixtures(manifest, trace, metric, ctx, want):
    ctx = dict({"trace": trace, "device_kind": "TPU v5 lite"}, **ctx)
    got = read_metric(manifest.layer_metric(metric), ctx)
    assert got is None if want is None else got == pytest.approx(want)


# -- whole runs: the rehearsal, and the faults the launcher must catch ------


def start(root, *args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    return subprocess.Popen(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args,
         "--rehearse-cpu"],
        cwd=root, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


def finish(proc) -> tuple:
    try:
        stdout, stderr = proc.communicate(timeout=900)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise
    return proc.returncode, stdout, stderr


@pytest.fixture(scope="module")
def rehearsal():
    return finish(start(
        ROOT, "--workload", CELL, "--seed", "2900000011", "--seconds", "3",
        "--trace", "1"))


@pytest.fixture(scope="module")
def fault_runs(tmp_path_factory):
    """Every fault's run, started together: (rc, stdout, stderr, the
    daemon's log)."""
    root = checkout_copy(tmp_path_factory.mktemp("filled"))
    with open(os.path.join(root, f"benchmarks/configs/{CONFIG}.json")) as f:
        config = json.load(f)
    procs = {}
    for fault in FAULTS:
        broken = dict(config, name=f"broken_{fault}", launcher=LAUNCHER,
                      env=dict(config["env"], FAULTY_FILLED_FAULT=fault))
        with open(os.path.join(root, f"benchmarks/configs/broken_{fault}.json"), "w") as f:
            json.dump(broken, f)
        cell = f"broken_{fault}.batch1000_zipf"
        with open(os.path.join(root, f"benchmarks/cells/{cell}.json"), "w") as f:
            json.dump({"name": cell, "config": f"broken_{fault}",
                       "traffic": "batch1000_zipf", "min_checked": 1}, f)
        procs[fault] = start(root, "--workload", cell, "--seed", "23",
                             "--seconds", "3", "--trace", "0")
    out = {}
    for fault, proc in procs.items():
        ended = finish(proc)
        logs = glob.glob(os.path.join(
            root, ".bench_run", f"broken_{fault}.batch1000_zipf-*", "daemon.log"))
        out[fault] = ended + (open(logs[0]).read() if logs else "",)
    return out


def test_rehearsal_is_correct_on_a_full_table_and_counts_its_evictions(rehearsal):
    rc, stdout, stderr = rehearsal
    assert rc == 0, stderr[-2000:]
    result = json.loads(stdout.strip().splitlines()[-1])
    assert list(result)[:5] == CONTRACT_KEYS
    assert result["correct"] is True, (result["compared"], stdout[-3000:])
    assert result["failed"] == 0
    assert result["compared"]["mismatched"] == {"value": 0, "limit": 0}
    assert result["compared"]["unanswered_rpcs"] == {"value": 0, "limit": 0}
    # full at the window's start, and still
    assert result["state"] == {"rows": 20000, "rows_occupied_start": 20000.0,
                               "rows_occupied_end": 20000.0}
    m = {name: v["value"] for name, v in result["metrics"].items()}
    assert m["evict.rows_per_kdecision"] > 0
    assert m["host.evict_clear_us"] > 0
    assert 0 < m["setup.load_s"] < 30 and m["setup.load_krows_per_s"] > 0
    assert m["step.compiles_in_window.filled"] == 0
    assert m["pump.dispatches_per_kdecision.filled"] > 1.0
    for name in ("listener.grpc_server_ms", "host.engine_serve_ms",
                 "host.lock_hold_us", "host.intern_us", "host.pack_us",
                 "pump.h2d_us", "pump.launch_us", "host.sweep_ms_in_window"):
        assert name + ".filled" in m and name not in m
    # nothing read from a device trace is reported from a CPU run
    for name in ("clear_roofline", "filled_step_roofline",
                 "step.clear_us_per_dispatch", "device.idle_pct.filled"):
        assert name not in m


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_node_that_did_not_restore_is_refused(fault_runs, fault):
    rc, stdout, stderr, log = fault_runs[fault]
    assert rc != 0
    assert not any(line.startswith("{") for line in stdout.splitlines())
    # the harness fails by its own check or by the daemon going away
    # under it (a reset connection): never a result, never a window
    assert "[bench] FAILED:" in stderr or "Error" in stderr
    assert "window:" not in stdout
    # one line in the daemon's log says why
    (line,) = [x for x in log.splitlines() if "[launch_filled]" in x]
    assert line.startswith("[launch_filled] REFUSED: " + FAULTS[fault]), line
    if fault == "one_row_short":
        assert line.endswith("of 20000 rows occupied, 19999 rows restored"), line
    if fault in ("no_item_columns", "no_columnar_load"):
        # such a program never serves: the harness sees its daemon die
        assert "daemon died with rc=3 before answering" in stderr
