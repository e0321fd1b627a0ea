"""The manifest's shape, names and units, on the committed BENCHMARK.json."""
import copy
import json

import pytest

from tinybench import REPO
from snowbench.manifest import NAME, UNIT, Bench, problems

DOC = json.loads((REPO / "BENCHMARK.json").read_text())


def test_committed_manifest_has_no_problems():
    assert problems(DOC, REPO) == []


@pytest.mark.parametrize("name,ok", [
    ("breakdown-1m", True), ("device_idle_share.sweep", True),
    ("_x", True), ("a" * 64, True), ("a" * 65, False), ("two words", False),
    ("a/b", False), ("a,b", False), (".hidden", False), ("µs", False)])
def test_name_character_set(name, ok):
    assert bool(NAME.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("deliveries/s", True), ("GB/s", True), ("%", True), ("ms", True),
    ("tokens per second", False), ("µs", False), ("a" * 17, False)])
def test_unit_character_set(unit, ok):
    assert bool(UNIT.match(unit)) is ok


def test_every_name_and_unit_in_the_manifest_is_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in DOC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
    for w in DOC["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    bench = Bench.load(REPO)
    for m in DOC["per_layer"]:
        for cell in m["workloads"]:
            reported = {e["name"] for e in bench.end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)


def test_at_most_one_cell_on_four_chips():
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) <= 1


def test_sweep_cells_report_their_metrics():
    bench = Bench.load(REPO)
    for cell in ("breakdown-1m", "stable-coloring-1m"):
        assert {m["name"] for m in bench.end_to_end(cell)} == {
            "sweep_rate", "setup_s"}


def test_pending_cell_joins_the_manifest_with_no_problems():
    """The fan-out cell, once pending, is in the manifest: the one cell on
    four chips, reporting ``fanout_GBps`` and ``setup_s``, its bytes
    compared exactly."""
    assert [w["name"] for w in DOC["workloads"] if w["chips"] == 4] == [
        "ckpt-fanout-4chip"]
    bench = Bench.load(REPO)
    assert {m["name"] for m in bench.end_to_end("ckpt-fanout-4chip")} == {
        "fanout_GBps", "setup_s"}
    assert {m["name"] for m in bench.per_layer("ckpt-fanout-4chip")} == {
        "collective_ms_per_rollout", "device_idle_share.fanout"}
    assert bench.limits("ckpt-fanout-4chip") == {"elements_off": 0.0}


@pytest.mark.parametrize("breakage", [
    "bad name", "duplicate cell", "moves unreported", "second four-chip",
    "loose bound", "unused config", "extra key", "missing why",
    "shared source", "bad unit"])
def test_problems_are_caught(breakage):
    doc = copy.deepcopy(DOC)
    if breakage == "bad name":
        doc["workloads"][0]["name"] = "bad name"
    elif breakage == "duplicate cell":
        doc["workloads"].append(dict(doc["workloads"][0]))
    elif breakage == "moves unreported":
        doc["per_layer"][0]["workloads"].append("ckpt-fanout-4chip")
    elif breakage == "second four-chip":
        doc["workloads"][0]["chips"] = 4
        doc["workloads"][1]["chips"] = 4
    elif breakage == "loose bound":
        doc["end_to_end"][0]["bound"] = 0.5
    elif breakage == "unused config":
        doc["configs"].append(dict(doc["configs"][0], name="spare",
                                   source="elsewhere"))
    elif breakage == "extra key":
        doc["end_to_end"][0]["why"] = "a metric takes no why"
    elif breakage == "missing why":
        del doc["configs"][0]["why"]
    elif breakage == "shared source":
        doc["configs"].append(dict(doc["configs"][0], name="other"))
        doc["workloads"][1]["config"] = "other"
        assert problems(doc, REPO) == ["two configurations share a source"]
    elif breakage == "bad unit":
        doc["end_to_end"][0]["unit"] = "deliveries per second"
    assert problems(doc, REPO)


@pytest.mark.parametrize("cell", [w["name"] for w in DOC["workloads"]])
def test_limits_lie_between_their_readings(cell):
    """Each limit sits above the program's lower reading and, where the
    control gives one at three times the lower or more, below its upper
    reading; an exact comparison has the limit 0."""
    doc = json.loads((REPO / "bench/limits" / f"{cell}.json").read_text())
    for name, entry in doc.items():
        lower, limit, upper = entry["lower"], entry["limit"], entry["upper"]
        if limit == 0:
            assert lower == 0, name
            continue
        assert 3 * lower <= upper, name
        assert lower < limit < upper, name


def test_every_cell_has_limits_and_its_files():
    bench = Bench.load(REPO)
    for w in DOC["workloads"]:
        assert bench.limits(w["name"])
        assert bench.traffic(w["traffic"])["generator"] in ("sweep",
                                                             "rollout")
        assert bench.config(w["config"])["name"] == w["config"]
    for m in DOC["per_layer"]:
        assert callable(bench.reader(m["name"]))
