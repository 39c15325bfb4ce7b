"""The reader ``direct_joins_per_query``: the mean of the window's
summaries' ``direct_joins`` (the probe batches of hash joins that probed a
direct-address table), on hand-made summaries, silent where the program
does not count it, listed for every cell, and in the last line of a
traced rehearsal."""

import json
import os

import pytest

from conftest import ROOT

from test_span_metrics import read

NAME = "direct_joins_per_query"


def test_the_benchmark_lists_the_metric_for_every_cell():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter",
                     "layer": "operators exec/ and ops/", "moves": "qps",
                     "workloads": [w["name"] for w in bench["workloads"]]}


def test_on_hand_made_summaries(monkeypatch):
    from spark_rapids_tpu.aux import tracing
    held = [{"direct_joins": 9}, {"direct_joins": 2}, {"direct_joins": 4}]
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    # the newest two are the window's
    assert read(NAME) == 3.0
    assert read(NAME, records=3) == pytest.approx(15 / 3)
    assert read(NAME, records=4) is None


def test_a_program_without_the_counter_is_silence(monkeypatch):
    """The parent of the PR that added the counter."""
    from spark_rapids_tpu.aux import tracing
    held = [{"probe_gather_rounds": 4, "sized_joins": 2}] * 2
    monkeypatch.setattr(tracing, "recent_summaries", lambda: list(held))
    assert read(NAME) is None


def test_a_traced_rehearsal_prints_four_direct_joins_a_star_query():
    from test_star_join import rehearse
    line, _err = rehearse(1)
    assert line["correct"] is True and line["failed"] == 0
    # four fact-to-dimension joins a q27 and a q7, one probe batch each
    assert line["rehearsal_metrics"][NAME]["value"] == 4.0
    assert line["rehearsal_metrics"]["probe_gather_rounds_per_query"][
        "value"] == 4.0
