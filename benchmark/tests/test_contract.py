"""``BENCHMARK.json`` against the limits its contract sets on the file
itself, and against the files it points at: every name resolves to a file
of its own under ``benchmark/``."""

import json
import os
import re

import pytest

from conftest import ROOT

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    # what fits a check with the full 24 cells
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert BENCH["paths"] == ["benchmark"]
    assert all(one_line(w) for w in BENCH["command"])
    assert BENCH["command"][1].startswith("benchmark/")


def test_configs():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/")
        held = json.load(open(os.path.join(ROOT, c["file"])))
        assert held["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in held
            assert not key.endswith(("_dim", "_rank"))


def test_workloads():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "workloads", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(CELLS) // 2)


def test_metric_names_are_distinct():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.25
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert os.path.exists(os.path.join(ROOT, "benchmark", "end_to_end",
                                       m["name"] + ".py"))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert one_line(m["layer"])
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    # each cell the metric lists has to report the metric it moves
    assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS))
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       m["name"] + ".py"))
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_every_cell_reports_set_up_another_metric_and_a_layer():
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    for cell in CELLS:
        e2e = [m for m in BENCH["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        assert len(e2e) >= 2
        assert any(cell in m.get("workloads", CELLS)
                   for m in BENCH["per_layer"])


def test_the_texts_of_every_cell_have_a_domain_and_a_reference():
    for w in BENCH["workloads"]:
        traffic = json.load(open(os.path.join(
            ROOT, "benchmark", "workloads", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", traffic["driver"] + ".py"))
        for q in traffic["texts"]:
            for ending in (".sql", ".params.json"):
                assert os.path.exists(os.path.join(
                    ROOT, "benchmark", "queries", q + ending))
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "reference", q + ".py"))
