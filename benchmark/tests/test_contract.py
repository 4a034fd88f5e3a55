"""BENCHMARK.json against the character and size rules of the contract, and
against the benchmark's own files."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(line_ok(w) for w in BENCH["command"])
    assert len(json.dumps(BENCH)) < 64 * 1024
    n = len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(n // 4, 1)


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                              "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                              "source", "layer", "moves"}
        assert line_ok(metric["layer"])
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # the metric it moves is reported wherever this one is
        assert set(metric.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric["name"] + ".py"))


def test_names_are_unique():
    for group in (ALL_METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and line_ok(cell["why"])
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))
    mine = lambda group: [m for m in BENCH[group]
                          if cell["name"] in m.get("workloads",
                                                   [cell["name"]])]
    assert "setup_s" in {m["name"] for m in mine("end_to_end")}
    assert len(mine("end_to_end")) >= 2 and mine("per_layer")


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and line_ok(config["source"])
    assert line_ok(config["why"]) and len(config["reduced"]) <= 16
    assert config["file"].startswith("benchmark/")
    data = json.load(open(os.path.join(ROOT, config["file"])))
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert config["name"] in {w["config"] for w in BENCH["workloads"]}
    for kind, key in (("runners", "runner"), ("models", "model")):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", kind, data[key] + ".py"))


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, _dirs, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            for fn in files:
                rel = os.path.relpath(os.path.join(base, fn), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
