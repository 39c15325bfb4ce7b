"""The cell ``store_star_join`` (configuration ``tpcds_store_star_resident``:
q27 then q7, string parameters at the specification's qualification
values) and the two readers that came with it: each reader on hand-made
input and on the trace recorded on the chip, both in the last line of a
traced rehearsal of the cell, and ``correct`` false when a level of q27's
roll-up is altered where it is produced."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

from benchmark import run as R
from test_span_metrics import read

CELL = "store_star_join"
#: the order is part of the cell (ISSUE 31; ``order_why`` in the traffic
#: file): with q27 first no query ends near the 40 s deadline
TEXTS = ["q27_qual", "q7_qual"]
CONFIG = "tpcds_store_star_resident"
#: a twentieth of SF1: the demographic triple keeps one row in seventy, so
#: a smaller fact table leaves q27 no row to roll up
SCALE_DOWN = 20


def rehearse(trace, seed=3000000019, seconds=3):
    """One ``--rehearse`` run of the cell on the CPU, as ``test_run.py``
    makes them, at this configuration's own scale-down."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cmd = [sys.executable] + bench["command"][1:] + [
        "--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace), "--rehearse", "--scale-down", str(SCALE_DOWN)]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    done = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_the_configuration_and_the_traffic_say_what_the_issue_names():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == dict(entry, config=CONFIG, traffic=CELL, chips=1)
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", CONFIG + ".json")))
    resident = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "tpcds_store_resident.json")))
    # the same deployment but for what its texts hold resident
    for key in ("rows", "source_rows", "datagen", "datagen_args",
                "integer_type", "partitions", "guarantees", "session_conf",
                "scale_factor", "source_scale_factor", "fixed_tables"):
        assert config[key] == resident[key], key
    assert config["source"] != resident["source"]
    assert config["rows"]["customer_demographics"] == 1920800
    assert "q27_states" in config["assumed"]
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json")))
    assert traffic == dict(traffic, driver="session_loop", streams=1,
                           order="rotation", texts=TEXTS,
                           trace_seconds=40)
    assert "q19" in traffic["texts_left_out"]
    assert "350" in traffic["texts_left_out"]
    assert "deadline" in traffic["order_why"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {m["name"] for m in bench["per_layer"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["rollup_rows_padded_per_query"]["workloads"] == [CELL]
    assert by_name["agg_device_pct"]["workloads"] == ["store_scan_agg", CELL]


@pytest.mark.parametrize("text", TEXTS)
def test_a_qual_text_is_its_template_with_one_value_a_string(text):
    """The cell's texts are ``q27.sql`` and ``q7.sql`` over a narrower
    domain: the same text and reference, every string parameter at one
    value (the window sends no string set-up has not sent) and YEAR over
    the template's range."""
    from benchmark.literals import Query
    template = text[:-len("_qual")]
    qual, full = Query(text), Query(template)
    assert qual.template == full.template
    import importlib
    assert R.load_by_name("reference", text).run is \
        importlib.import_module("benchmark.reference." + template).run
    assert set(qual.params) == set(full.params)
    for key, domain in qual.params.items():
        if key == "YEAR":
            assert domain == full.params[key] == {"int_range": [1998, 2002]}
        else:
            assert len(domain["choice"]) == 1
            assert isinstance(domain["choice"][0], str)
            assert domain["choice"][0] in full.params[key]["choice"]
    assert qual.domain_size() == qual.distinct_texts == 5


def test_rollup_rows_padded_per_query_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [{"expand_rows_padded": 7}, {"expand_rows_padded": 0},
            {"expand_rows_padded": 98304}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read("rollup_rows_padded_per_query") == 49152.0
    assert isinstance(read("rollup_rows_padded_per_query"), float)
    assert read("rollup_rows_padded_per_query", records=3) == \
        pytest.approx(98311 / 3)
    # fewer summaries than queries, or a program that does not count the
    # fan-out (the parent of the PR that added the counter): silence
    assert read("rollup_rows_padded_per_query", records=4) is None
    held[2] = {"pair_rows_padded": 1}
    assert read("rollup_rows_padded_per_query") is None


def test_agg_device_pct_on_a_hand_made_trace():
    trace = {"programs": [["join.pair", 5.0], ["fused.agg_update", 1.5],
                          ["fused.agg_merge_final", 0.25],
                          ["expand.project", 0.25], ["agg.segmented", 0.5],
                          ["fused.stage", 2.0], ["sort.fused", 0.5]]}
    assert read("agg_device_pct", trace=trace) == pytest.approx(25.0)
    assert isinstance(read("agg_device_pct", trace=trace), float)
    # no device plane was traced (a rehearsal), or no trace was taken
    assert read("agg_device_pct", trace={"programs": []}) is None
    assert read("agg_device_pct", trace=None) is None
    assert read("agg_device_pct", trace={"busy_s": 0.0}) is None


def test_agg_device_pct_on_the_recorded_trace():
    """The fixture is one q3 of the parent of every join repair: its
    aggregation's share, worked out here from the reduction's own list."""
    from benchmark.trace.reduce import reduce_events
    with open(os.path.join(ROOT, "benchmark", "trace",
                           "fixture_trace.json")) as f:
        fixture = json.load(f)
    got = reduce_events({
        "devices": {k: {"ops": [tuple(e) for e in v["ops"]],
                        "modules": [tuple(e) for e in v["modules"]]}
                    for k, v in fixture["devices"].items()},
        "spans": [tuple(e) for e in fixture["spans"]]})
    programs = dict(got["programs"])
    assert "fused.agg_update" in programs
    want = 100.0 * sum(t for k, t in programs.items()
                       if k.startswith(("fused.agg", "agg.", "expand."))) \
        / sum(programs.values())
    value = read("agg_device_pct", trace=got)
    assert value == pytest.approx(want) and 0.0 < value < 100.0
    assert value + read("join_device_pct", trace=got) <= 100.0


def test_a_traced_rehearsal_of_the_cell_prints_both_that_it_can():
    line, err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    got = line["rehearsal_metrics"]
    said = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith('{"cell"')))
    texts = [q for q, _ in said["latency_s_in_order"]]
    assert texts[:2] == TEXTS
    # the mean over the window's queries: a q27 hands on three buckets of
    # the fan-out's floor, a q7 nothing
    n27 = texts.count("q27_qual")
    assert got["rollup_rows_padded_per_query"]["value"] == \
        pytest.approx(n27 * 3 * 32768 / len(texts))
    assert "agg_device_pct" not in got            # no device ran
    assert got["window_compiles"]["value"] == 0.0
    assert said["window_traces_by_kind"] == {}
    # one sync a q7, two a q27: the count its fan-out forces
    assert got["syncs_per_query"]["value"] == \
        pytest.approx((len(texts) + n27) / len(texts))


def drop_item_level(rows):
    """q27's roll-up without one row of its item level (an item's total
    over the states)."""
    rows.remove(next(r for r in rows if r.get("g_state") == 1
                     and r["i_item_id"] is not None))


def item_level_as_state_level(rows):
    """The same row claiming to be a by-state row."""
    next(r for r in rows if r.get("g_state") == 1
         and r["i_item_id"] is not None)["g_state"] = 0


@pytest.mark.parametrize("fault", (drop_item_level,
                                   item_level_as_state_level))
def test_a_fault_in_q27s_roll_up_level_is_not_correct(fault, monkeypatch,
                                                      capsys):
    real = R.load_by_name

    def broken(directory, name):
        module = real(directory, name)
        if directory == "drivers":
            run_one = module.Driver.run_one

            def run_one_broken(self, text):
                out = run_one(self, text)
                if "rollup" in text:
                    fault(out["rows"])
                return out
            module.Driver.run_one = run_one_broken
        return module

    monkeypatch.setattr(R, "load_by_name", broken)
    assert R.main(["--workload", CELL, "--seed", "3000000019", "--seconds",
                   "2", "--trace", "0", "--rehearse", "--scale-down",
                   str(SCALE_DOWN)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["rows_wrong"]["value"] > 0
    assert line["checks"]["max_rel_err"]["value"] <= 1e-9
