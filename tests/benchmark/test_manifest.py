"""BENCHMARK.json against the contract's letter, and the harness found
driven by data: a new cell, mix or layer metric is files added."""

import json
import os
import re
import shutil

import pytest

from conftest import BENCH, ROOT
from lib.manifest import Manifest, read_metric

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def doc(manifest):
    return manifest.doc


def all_metrics(doc):
    return doc["end_to_end"] + doc["per_layer"]


def test_top_level_keys_and_sizes(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert 1 <= len(doc["command"]) <= 32
    assert all(one_line(w) for w in doc["command"])
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(doc["configs"]) <= 24
    assert 1 <= len(doc["workloads"]) <= 24
    assert 1 <= len(doc["end_to_end"]) <= 16
    assert 1 <= len(doc["per_layer"]) <= 128


def test_names_units_and_entry_keys(doc):
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in doc[section]]
        assert len(names) == len(set(names)), f"duplicate name in {section}"
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in all_metrics(doc)]
    assert len(metric_names) == len(set(metric_names))
    for m in all_metrics(doc):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert "setup_s" in [m["name"] for m in doc["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_thing_is_a_file_of_its_own(manifest, doc):
    files = set()
    for c in doc["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
        assert c["file"] not in files
        assert c["file"] == f"benchmarks/configs/{c['name']}.json", "found by name"
        files.add(c["file"])
        body = manifest.config(c["name"])
        assert body["name"] == c["name"]
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["guarantees"], "the configuration states its guarantees"
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    for w in doc["workloads"]:
        cell = manifest.cell(w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
        assert manifest.config(w["config"])["chips"] == w["chips"]
        assert manifest.mix(w["traffic"])["name"] == w["traffic"]
    for m in doc["per_layer"]:
        spec = manifest.layer_metric(m["name"])
        assert (spec["name"], spec["unit"], spec["layer"]) == (
            m["name"], m["unit"], m["layer"])
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))


def test_file_names_keep_to_the_allowed_characters(doc):
    for p in doc["paths"]:
        for base, dirs, names in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), ROOT)
                assert PATH.match(rel), rel


def test_moves_is_reported_by_every_cell_of_the_metric(manifest, doc):
    cells = [w["name"] for w in doc["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert "setup_s" in [m["name"] for m in manifest.metrics_of("end_to_end", cell)]
        assert len(manifest.metrics_of("end_to_end", cell)) >= 2
        assert manifest.metrics_of("per_layer", cell)


def test_four_chip_cells_are_at_most_half(doc):
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 2)


def test_roofline_and_layer_names(doc):
    for m in doc["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    by_layer = {}
    for m in doc["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_new_cell_mix_and_metric_are_files_added(tmp_path):
    """A later PR's cell: nothing that exists is edited but
    BENCHMARK.json, which gains entries."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = {
        str(p): p.read_bytes()
        for p in (root / "benchmarks").rglob("*") if p.is_file()
    }
    doc = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "benchmarks/mixes/herd100.json").read_text())
    mix.update(name="herd7", callers=7, client_processes=1)
    (root / "benchmarks/mixes/herd7.json").write_text(json.dumps(mix))
    config = doc["workloads"][0]["config"]
    (root / "benchmarks/cells/throwaway.herd7.json").write_text(json.dumps({
        "name": "throwaway.herd7", "config": config, "traffic": "herd7",
        "min_checked": 10,
    }))
    (root / "benchmarks/layer_metrics/throwaway.rounds.json").write_text(
        json.dumps({
            "name": "throwaway.rounds", "layer": "pump and readback",
            "unit": "rounds", "reader": "vars_delta",
            "args": {"path": "device.counters.rounds_total"},
        }))
    doc["workloads"].append({
        "name": "throwaway.herd7", "config": config, "traffic": "herd7",
        "chips": 1, "why": "a cell a later PR adds"})
    doc["per_layer"].append({
        "name": "throwaway.rounds", "unit": "rounds", "better": "lower",
        "source": "program_counter", "layer": "pump and readback",
        "moves": "decisions_per_s", "workloads": ["throwaway.herd7"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    m = Manifest(str(root))
    assert m.mix(m.cell("throwaway.herd7")["traffic"])["callers"] == 7
    mine = [x["name"] for x in m.metrics_of("per_layer", "throwaway.herd7")]
    assert "throwaway.rounds" in mine
    assert "throwaway.rounds" not in [
        x["name"] for x in m.metrics_of("per_layer", doc["workloads"][0]["name"])]
    ctx = {"vars_before": {"device": {"counters": {"rounds_total": 5}}},
           "vars_after": {"device": {"counters": {"rounds_total": 12}}}}
    assert read_metric(m.layer_metric("throwaway.rounds"), ctx) == 7.0
    after = {
        str(p): p.read_bytes()
        for p in (root / "benchmarks").rglob("*") if p.is_file()
    }
    assert all(after[k] == v for k, v in before.items())


@pytest.mark.parametrize("reader,args,ctx,want", [
    ("prom_delta",
     {"samples": [{"name": "d", "labels": {"stage": "x"}}], "stat": "mean",
      "scale": 1000.0},
     {"prom_before": {("d_sum", (("stage", "x"),)): 1.0,
                      ("d_count", (("stage", "x"),)): 10.0},
      "prom_after": {("d_sum", (("stage", "x"),)): 3.0,
                     ("d_count", (("stage", "x"),)): 20.0}}, 200.0),
    ("prom_delta", {"samples": [{"name": "absent"}]},
     {"prom_before": {}, "prom_after": {}}, None),
    ("prom_delta", {"samples": [{"name": "c"}, {"name": "e"}]},
     {"prom_before": {("c", ()): 1.0}, "prom_after": {("c", ()): 4.0, ("e", ()): 2.0}},
     5.0),
    ("vars_delta", {"path": "a.b", "scale": 2.0},
     {"vars_before": {"a": {"b": 1}}, "vars_after": {"a": {"b": 4}}}, 6.0),
    ("vars_delta", {"path": "a.missing"},
     {"vars_before": {"a": {}}, "vars_after": {"a": {}}}, None),
    ("run_value", {"key": "decisions"}, {"run": {"decisions": 9}}, 9.0),
    ("run_value", {"key": "daemon_cpu_s"}, {"run": {"daemon_cpu_s": None}}, None),
    ("ratio",
     {"num": {"reader": "run_value", "args": {"key": "a"}},
      "den": [{"reader": "run_value", "args": {"key": "b"}},
              {"reader": "run_value", "args": {"key": "c"}}], "scale": 100.0},
     {"run": {"a": 3.0, "b": 2.0, "c": 3.0}}, 50.0),
    ("ratio",
     {"num": {"reader": "run_value", "args": {"key": "a"}},
      "den": {"reader": "run_value", "args": {"key": "b"}}},
     {"run": {"a": 3.0, "b": 0.0}}, None),
])
def test_readers(reader, args, ctx, want):
    got = read_metric({"reader": reader, "args": args}, ctx)
    assert got == want if want is None else got == pytest.approx(want)
